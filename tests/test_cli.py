"""Unit tests for the CLI composition root (`ggrmcp_tpu/__main__.py`).

The e2e suite exercises the CLI in subprocesses (invisible to coverage
and slow to iterate); these test the parse/merge logic in-process:
flag → config precedence (cmd/grmcp/main.go:37-42 parity plus the
file/env loading the reference never plumbed), subcommand wiring, and
the guard rails (`--workers` × `--tpu`, validation re-check).
"""

import json

import pytest

from ggrmcp_tpu import __main__ as cli
from ggrmcp_tpu.core.config import GRPCConfig


class TestParser:
    def test_gateway_flags(self):
        args = cli.build_parser().parse_args([
            "gateway", "--grpc-host", "h", "--grpc-port", "9",
            "--http-port", "8", "--log-level", "debug", "--dev",
            "--descriptor", "d.binpb", "--backend", "a:1",
            "--backend", "b:2", "--workers", "3",
        ])
        assert args.command == "gateway"
        assert args.grpc_host == "h" and args.grpc_port == 9
        assert args.http_port == 8 and args.dev
        assert args.backend == ["a:1", "b:2"]
        assert args.workers == 3

    def test_sidecar_flags(self):
        args = cli.build_parser().parse_args([
            "sidecar", "--port", "7", "--model", "tiny-llama",
            "--quantize", "int8",
        ])
        assert args.command == "sidecar"
        assert args.port == 7 and args.model == "tiny-llama"
        assert args.quantize == "int8"

    def test_train_flags(self):
        args = cli.build_parser().parse_args([
            "train", "--model", "tiny-llama", "--steps", "5",
            "--no-resume",
        ])
        assert args.command == "train"
        assert args.steps == 5 and args.no_resume

    def test_unknown_flag_exits(self):
        with pytest.raises(SystemExit):
            cli.build_parser().parse_args(["gateway", "--nope"])


class TestLoadConfig:
    def test_flags_override_defaults(self):
        args = cli.build_parser().parse_args([
            "gateway", "--grpc-host", "h", "--grpc-port", "9",
            "--http-port", "8080", "--log-level", "warning",
        ])
        cfg = cli.load_config(args)
        assert cfg.grpc.host == "h" and cfg.grpc.port == 9
        assert cfg.server.port == 8080
        assert cfg.logging.level == "warning"

    def test_descriptor_flag_enables_fds(self, tmp_path):
        p = tmp_path / "x.binpb"
        p.write_bytes(b"")
        args = cli.build_parser().parse_args(
            ["gateway", "--descriptor", str(p)]
        )
        cfg = cli.load_config(args)
        assert cfg.grpc.descriptor_set.enabled
        assert cfg.grpc.descriptor_set.path == str(p)

    def test_config_file_then_flag_precedence(self, tmp_path):
        # file sets both; the flag wins for the one it names
        f = tmp_path / "cfg.json"
        f.write_text(json.dumps(
            {"server": {"port": 1111}, "logging": {"level": "error"}}
        ))
        args = cli.build_parser().parse_args([
            "gateway", "--config", str(f), "--http-port", "2222",
        ])
        cfg = cli.load_config(args)
        assert cfg.server.port == 2222  # flag beats file
        assert cfg.logging.level == "error"  # file beats default

    def test_env_layer(self, tmp_path, monkeypatch):
        monkeypatch.setenv("GGRMCP_SERVER_PORT", "3333")
        args = cli.build_parser().parse_args(["gateway"])
        cfg = cli.load_config(args)
        assert cfg.server.port == 3333

    def test_sidecar_serving_overrides(self, tmp_path):
        argv = [
            "sidecar", "--port", "7001", "--model", "tiny-llama",
            "--quantize", "int8",
        ]
        cfg = cli.load_config(cli.build_parser().parse_args(argv))
        assert cfg.serving.port == 7001
        assert cfg.serving.model == "tiny-llama"
        assert cfg.serving.quantize == "int8"
        # A file that still names a retired option is refused like any
        # other unknown key (config._merge); there is no alias.
        f = tmp_path / "retired.json"
        f.write_text('{"serving": {"speculative_draft": "tiny-llama"}}')
        args = cli.build_parser().parse_args([*argv, "--config", str(f)])
        with pytest.raises(ValueError, match="unknown config key"):
            cli.load_config(args)

    def test_invalid_flag_value_fails_validation(self):
        args = cli.build_parser().parse_args(
            ["gateway", "--http-port", "-5"]
        )
        with pytest.raises(ValueError):
            cli.load_config(args)


class TestMainWiring:
    def test_workers_with_tpu_rejected(self):
        with pytest.raises(SystemExit, match="workers"):
            cli.main(["gateway", "--workers", "2", "--tpu"])

    def test_gateway_default_subcommand(self, monkeypatch):
        """Bare flags (no subcommand) behave as `gateway ...` —
        reference CLI compatibility (it has no subcommands)."""
        seen = {}

        def fake_run(cfg, targets):
            seen["targets"] = targets
            seen["port"] = cfg.server.port

        monkeypatch.setattr("ggrmcp_tpu.gateway.app.run", fake_run)
        rc = cli.main(["--grpc-host", "hh", "--grpc-port", "12345",
                       "--http-port", "18080"])
        assert rc == 0
        assert seen["targets"] == ["hh:12345"]
        assert seen["port"] == 18080

    def test_gateway_backend_pool_targets(self, monkeypatch):
        seen = {}
        monkeypatch.setattr(
            "ggrmcp_tpu.gateway.app.run",
            lambda cfg, targets: seen.setdefault("targets", targets),
        )
        rc = cli.main([
            "gateway", "--backend", "a:1", "--backend", "b:2",
        ])
        assert rc == 0
        assert seen["targets"] == ["a:1", "b:2"]

    def test_tpu_mode_pools_external_backend_only_when_explicit(
        self, monkeypatch
    ):
        """--tpu alone serves only the sidecar; an explicit backend
        flag (or a non-placeholder grpc.target) joins the pool."""
        calls = []
        monkeypatch.setattr(
            "ggrmcp_tpu.serving.launcher.run_gateway_with_sidecar",
            lambda cfg, targets: calls.append(targets),
        )
        assert cli.main(["gateway", "--tpu"]) == 0
        assert calls[-1] == []
        assert cli.main(["gateway", "--tpu", "--backend", "x:1"]) == 0
        assert calls[-1] == ["x:1"]
        # default placeholder target never pools
        assert GRPCConfig().target not in calls[-1]

    def test_train_wiring(self, monkeypatch, tmp_path):
        seen = {}
        monkeypatch.setattr(
            "ggrmcp_tpu.models.trainer.train",
            lambda tc: seen.setdefault("tc", tc),
        )
        rc = cli.main([
            "train", "--model", "tiny-llama", "--steps", "3",
            "--batch-size", "2", "--seq-len", "32",
            "--checkpoint-dir", str(tmp_path), "--no-resume",
        ])
        assert rc == 0
        tc = seen["tc"]
        assert tc.model == "tiny-llama" and tc.steps == 3
        assert tc.batch_size == 2 and tc.seq_len == 32
        assert tc.checkpoint_dir == str(tmp_path)
        assert tc.resume is False
