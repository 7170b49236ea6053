#!/usr/bin/env python3
"""Compile the batcher's decode tick as the benchmark's configuration
serves it and list what it does to the paged K/V arena.

    python3 scripts/tick_hlo.py            # on the TPU, mistral-7b int8
    python3 scripts/tick_hlo.py --cpu      # rehearsal, tiny-llama
    python3 scripts/tick_hlo.py --config benchmark/configs/<name>.json
                                           # that cell's `stack`, any family

Writes the optimised HLO of `ContinuousBatcher._tick` to
`chiprun_out/tick_hlo/tick_hlo.<model>.txt` and prints the compiler's memory
analysis and every instruction whose result has the arena's
`[L, N, P, KVH, Dh]` shape or one layer's `[N, P, KVH, Dh]` shape (the
gathered view has the latter too when N = slots x table width, as in
the benchmark's configuration). PR 27 read from it that the arena is
scattered into and gathered from in place; since PR 29 the gathers are
gone on the TPU and the paged-decode kernel shows up as a
`tpu_custom_call` named `paged_decode_attention` reading the scattered
arena through a bitcast. It also lists the sampler's work over the
vocabulary: every `sort`, and every instruction whose result spans the
grammar tables' `[states, *]` extent, each with the computation that
holds it and whether a conditional's branch reaches that computation
(PR 40).
"""

from __future__ import annotations

import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

cpu = "--cpu" in sys.argv[1:]
if cpu:
    os.environ["JAX_PLATFORMS"] = "cpu"

import json  # noqa: E402

import jax.numpy as jnp  # noqa: E402

from ggrmcp_tpu.core import config as config_mod  # noqa: E402
from ggrmcp_tpu.models import get_model  # noqa: E402
from ggrmcp_tpu.serving.batching import ContinuousBatcher  # noqa: E402
from ggrmcp_tpu.serving.engine import GenerationEngine  # noqa: E402

INSTR = re.compile(
    r"\s*(?:ROOT )?(%?[\w.\-]+) = \(?(\w+\[[\d,]*\])\S* .*?([\w\-]+)\("
)
COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
CALLED = re.compile(
    r"(?:calls|to_apply|body|condition|true_computation"
    r"|false_computation)=%?([\w.\-]+)"
)
BRANCHES = re.compile(
    r"(?:true_computation|false_computation)=%?([\w.\-]+)"
    r"|branch_computations=\{([^}]*)\}"
)


def serving_config(argv):
    """The cell's `stack` (`--config`), else the mistral cell's (tiny-llama
    under `--cpu`)."""
    if "--config" in argv:
        with open(argv[argv.index("--config") + 1]) as f:
            stack = json.load(f)["stack"]
    else:  # benchmark/configs/mistral-7b-int8-1chip.json `stack`
        stack = {"serving": {
            "model": "tiny-llama" if cpu else "mistral-7b",
            "mesh": {"tensor": 1},
            "batching": {
                "paged_kv": "on", "paged_kv_page_size": 16,
                "max_batch_size": 8,
                "kv_cache_max_seq": 256 if cpu else 2048,
                "prefill_chunk": 512, "max_pending": 0,
            },
        }}
        if not cpu:
            stack["serving"].update(
                quantize="int8", synthetic_weights=True)
    return config_mod.load(env=False, overrides=stack).serving


def main() -> int:
    import jax

    serving = serving_config(sys.argv[1:])
    _, cfg = get_model(serving.model)
    engine = GenerationEngine(cfg, serving)
    batcher = ContinuousBatcher(engine, serving.batching)
    b = serving.batching.max_batch_size
    g_allow, g_trans = batcher._grammar_tables()
    compiled = batcher._tick.lower(
        engine.params, jnp.zeros((b,), jnp.int32), batcher.cache,
        jnp.asarray(batcher.seeds), jnp.int32(0),
        jnp.asarray(batcher.temps), jnp.asarray(batcher.top_ks),
        jnp.asarray(batcher.top_ps), jnp.zeros((b,), bool),
        jnp.zeros((b,), jnp.int32), jnp.zeros((b,), jnp.int32),
        g_allow, g_trans,
    ).compile()
    hlo = compiled.as_text()
    out_dir = os.path.join(ROOT, "chiprun_out", "tick_hlo")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"tick_hlo.{serving.model}.txt")
    with open(path, "w") as f:
        f.write(hlo)

    print("device:", jax.devices()[0].device_kind, "model:", serving.model)
    print(compiled.memory_analysis())
    planes = [
        f"{batcher._n_pages},{serving.batching.paged_kv_page_size},"
        + ",".join(map(str, plane)) + "]"
        for plane in dict.fromkeys(getattr(cfg, "kv_planes", None) or (
            (cfg.num_kv_heads, cfg.head_dim),))
    ]
    shapes = tuple(
        lead + plane for plane in planes
        for lead in ("[", f"[{cfg.num_layers},")
    )
    states = f"[{g_trans.shape[0]},"
    # Computations a conditional's branch reaches (itself, its fusions,
    # its loops), so that an operation inside one is told from the same
    # operation in the scan's body proper.
    callees: dict[str, set[str]] = {}
    where = "ENTRY"
    for line in hlo.splitlines():
        m = COMPUTATION.match(line)
        if m:
            where = m.group(1)
        else:
            callees.setdefault(where, set()).update(CALLED.findall(line))
    conditional = set()
    todo = [
        name.strip().lstrip("%")
        for m in BRANCHES.finditer(hlo)
        for name in (m.group(1) or m.group(2)).split(",")
    ]
    while todo:
        name = todo.pop()
        if name not in conditional:
            conditional.add(name)
            todo += callees.get(name, ())
    for line in hlo.splitlines():
        m = COMPUTATION.match(line)
        if m:
            where = m.group(1)
            continue
        m = INSTR.match(line)
        if not m or m.group(3) in (
            "parameter", "get-tuple-element", "bitcast", "tuple",
        ):
            continue
        name, shape, op = m.groups()
        if shape.endswith(shapes) or "paged_decode_attention" in name:
            print(name, shape, op)
        elif op == "sort" or states in shape:
            print(name, shape, op, "in", where,
                  "(under a conditional)" if where in conditional else "")
    return 0


if __name__ == "__main__":
    sys.exit(main())
