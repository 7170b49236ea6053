"""CLI entry point: `python -m ggrmcp_tpu [gateway|sidecar] ...`.

Capability parity with the reference CLI (cmd/grmcp/main.go:37-42 flags
--grpc-host/--grpc-port/--http-port/--log-level/--dev/--descriptor),
extended with config-file/env loading, multi-backend targets, and the
TPU mode that co-launches a JAX serving sidecar (BASELINE.json north
star: `cmd/grmcp --tpu`).
"""

from __future__ import annotations

import argparse
import sys

from ggrmcp_tpu.core import config as cfgmod

# One source of truth for the subcommand names: build_parser registers
# exactly these, and main's bare-flags rewrite checks against them
# (argparse keeps its choices in private attributes with no stability
# guarantee, so they are not derived from the parser).
SUBCOMMANDS = ("gateway", "train", "sidecar")


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="ggrmcp_tpu", description="TPU-native gRPC <-> MCP gateway"
    )
    sub = parser.add_subparsers(dest="command")

    gw = sub.add_parser(SUBCOMMANDS[0], help="run the MCP gateway")
    gw.add_argument("--grpc-host", default=None, help="backend gRPC host")
    gw.add_argument("--grpc-port", type=int, default=None, help="backend gRPC port")
    gw.add_argument("--http-port", type=int, default=None, help="HTTP listen port")
    gw.add_argument("--log-level", default=None, help="debug|info|warning|error")
    gw.add_argument("--dev", action="store_true", help="development mode")
    gw.add_argument(
        "--descriptor", default=None, help="FileDescriptorSet (.binpb) path"
    )
    gw.add_argument("--config", default=None, help="YAML/JSON config file")
    gw.add_argument(
        "--backend",
        action="append",
        default=None,
        metavar="HOST:PORT",
        help="backend target; repeat for a pool (overrides --grpc-host/port)",
    )
    gw.add_argument(
        "--tpu",
        action="store_true",
        help="co-launch a JAX TPU serving sidecar and register it",
    )
    gw.add_argument("--model", default=None, help="sidecar model (with --tpu)")
    gw.add_argument(
        "--quantize", default=None, help="sidecar weight quantization (int8)"
    )
    gw.add_argument(
        "--hf-checkpoint", default=None,
        help="sidecar HF Llama checkpoint dir (with --tpu); overrides --model",
    )
    gw.add_argument(
        "--tokenizer", default=None,
        help="sidecar HuggingFace tokenizer.json path (with --tpu)",
    )
    gw.add_argument(
        "--workers", type=int, default=None,
        help="gateway worker processes sharing the port (SO_REUSEPORT)",
    )

    tr = sub.add_parser(SUBCOMMANDS[1], help="fine-tune a model (checkpoint/resume)")
    tr.add_argument("--model", default=None, help="model registry key")
    tr.add_argument("--steps", type=int, default=None)
    tr.add_argument("--batch-size", type=int, default=None)
    tr.add_argument("--seq-len", type=int, default=None)
    tr.add_argument("--learning-rate", type=float, default=None)
    tr.add_argument(
        "--checkpoint-dir", default=None,
        help="root for step_N/{state,params} checkpoints",
    )
    tr.add_argument("--save-every", type=int, default=None)
    tr.add_argument(
        "--no-resume", action="store_true",
        help="start fresh even if checkpoints exist",
    )
    tr.add_argument("--data", default=None, help="raw text file to train on")
    tr.add_argument("--config", default=None, help="YAML/JSON config file")
    tr.add_argument("--log-level", default=None)

    sc = sub.add_parser(SUBCOMMANDS[2], help="run the TPU serving sidecar only")
    sc.add_argument("--port", type=int, default=None, help="gRPC listen port")
    sc.add_argument("--model", default=None, help="model registry key")
    sc.add_argument(
        "--quantize", default=None, help="weight quantization (int8)"
    )
    sc.add_argument(
        "--hf-checkpoint", default=None,
        help="HuggingFace Llama checkpoint dir (config.json + "
        "safetensors); overrides --model",
    )
    sc.add_argument(
        "--tokenizer", default=None, help="HuggingFace tokenizer.json path"
    )
    sc.add_argument("--config", default=None, help="YAML/JSON config file")
    sc.add_argument("--log-level", default=None)

    return parser


def load_config(args: argparse.Namespace) -> cfgmod.Config:
    cfg = cfgmod.load(
        path=getattr(args, "config", None),
        env=True,
        dev=getattr(args, "dev", False),
    )
    if getattr(args, "grpc_host", None):
        cfg.grpc.host = args.grpc_host
    if getattr(args, "grpc_port", None):
        cfg.grpc.port = args.grpc_port
    if getattr(args, "http_port", None):
        cfg.server.port = args.http_port
    if getattr(args, "log_level", None):
        cfg.logging.level = args.log_level
    if getattr(args, "descriptor", None):
        cfg.grpc.descriptor_set.enabled = True
        cfg.grpc.descriptor_set.path = args.descriptor
    if getattr(args, "model", None):
        cfg.serving.model = args.model
    if getattr(args, "quantize", None):
        cfg.serving.quantize = args.quantize
    if getattr(args, "port", None):
        cfg.serving.port = args.port
    if getattr(args, "hf_checkpoint", None):
        cfg.serving.hf_checkpoint_path = args.hf_checkpoint
    if getattr(args, "tokenizer", None):
        cfg.serving.tokenizer_path = args.tokenizer
    if getattr(args, "workers", None):
        cfg.server.workers = args.workers
    cfg.validate(colaunch=getattr(args, "tpu", False))
    return cfg


def main(argv: list[str] | None = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    parser = build_parser()
    # Reference-CLI compatibility (cmd/grmcp has no subcommands): bare
    # flags imply `gateway`. This must happen BEFORE parsing — argparse
    # rejects unknown top-level flags, so a post-parse retry never runs.
    if argv and argv[0] not in (*SUBCOMMANDS, "-h", "--help"):
        argv = ["gateway", *argv]
    args = parser.parse_args(argv)
    if args.command == "train":
        cfg = load_config(args)
        tc = cfg.training
        if args.model:
            tc.model = args.model
        for flag, attr in (
            ("steps", "steps"), ("batch_size", "batch_size"),
            ("seq_len", "seq_len"), ("learning_rate", "learning_rate"),
            ("checkpoint_dir", "checkpoint_dir"),
            ("save_every", "save_every_steps"), ("data", "data_path"),
        ):
            value = getattr(args, flag, None)
            if value is not None:
                setattr(tc, attr, value)
        if args.no_resume:
            tc.resume = False
        cfg.validate()  # re-check: train flags were applied after load
        from ggrmcp_tpu.gateway.app import setup_logging
        from ggrmcp_tpu.models.trainer import train

        setup_logging(cfg)
        train(tc)
        return 0
    if args.command == "sidecar":
        cfg = load_config(args)
        from ggrmcp_tpu.serving.sidecar import run as run_sidecar

        run_sidecar(cfg)
        return 0
    if args.command == "gateway" or args.command is None:
        if args.command is None:  # bare `python -m ggrmcp_tpu`
            args = build_parser().parse_args(["gateway"])
        cfg = load_config(args)
        targets = args.backend if args.backend else [cfg.grpc.target]
        if cfg.server.workers > 1:
            if args.tpu:
                raise SystemExit(
                    "--workers > 1 is incompatible with --tpu (each worker "
                    "would co-launch its own sidecar); run the sidecar "
                    "separately and point --backend at it"
                )
            from ggrmcp_tpu.gateway.app import run_multiworker

            run_multiworker(cfg, targets)
            return 0
        if args.tpu:
            from ggrmcp_tpu.serving.launcher import run_gateway_with_sidecar

            # An external backend joins the pool only when one was
            # actually configured: by --backend / host-port flags, or by
            # a config file / env var that moved grpc.target off the
            # built-in placeholder. `--config` alone (e.g. logging-only)
            # must NOT pool the dead placeholder, and an env-configured
            # target must not be dropped just because no flag was given.
            from ggrmcp_tpu.core.config import GRPCConfig

            explicit = bool(
                args.backend or args.grpc_host or args.grpc_port
                or cfg.grpc.target != GRPCConfig().target
            )
            run_gateway_with_sidecar(cfg, targets if explicit else [])
        else:
            from ggrmcp_tpu.gateway.app import run

            run(cfg, targets)
        return 0
    return 1


if __name__ == "__main__":
    sys.exit(main())
