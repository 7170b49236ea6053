#!/usr/bin/env python3
"""Compile the batcher's decode tick as the benchmark's configuration
serves it and list what it does to the paged K/V arena.

    python3 scripts/tick_hlo.py            # on the TPU, mistral-7b int8
    python3 scripts/tick_hlo.py --cpu      # rehearsal, tiny-llama

Writes the optimised HLO of `ContinuousBatcher._tick` to
`chiprun_out/tick_hlo/tick_hlo.txt` and prints the compiler's memory
analysis and every instruction whose result has the arena's
`[L, N, P, KVH, Dh]` shape or one layer's `[N, P, KVH, Dh]` shape (the
gathered view has the latter too when N = slots x table width, as in
the benchmark's configuration). PR 27 read from it that the arena is
scattered into and gathered from in place; since PR 29 the gathers are
gone on the TPU and the paged-decode kernel shows up as a
`tpu_custom_call` named `paged_decode_attention` reading the scattered
arena through a bitcast.
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

import jax.numpy as jnp  # noqa: E402

from ggrmcp_tpu.core.config import (  # noqa: E402
    BatchingConfig,
    MeshConfig,
    ServingConfig,
)
from ggrmcp_tpu.models import llama  # noqa: E402
from ggrmcp_tpu.serving.batching import ContinuousBatcher  # noqa: E402
from ggrmcp_tpu.serving.engine import GenerationEngine  # noqa: E402


def main() -> int:
    import jax

    model = "tiny-llama" if cpu else "mistral-7b"
    cfg = llama.CONFIGS[model]
    serving = ServingConfig(model=model, mesh=MeshConfig(tensor=1))
    if not cpu:  # benchmark/configs/mistral-7b-int8-1chip.json `stack`
        serving.quantize, serving.synthetic_weights = "int8", True
    engine = GenerationEngine(cfg, serving)
    b = 8
    batcher = ContinuousBatcher(engine, BatchingConfig(
        paged_kv="on", paged_kv_page_size=16, max_batch_size=b,
        kv_cache_max_seq=256 if cpu else 2048, prefill_chunk=512,
        max_pending=0,
    ))
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
    with open(os.path.join(out_dir, "tick_hlo.txt"), "w") as f:
        f.write(hlo)

    print("device:", jax.devices()[0].device_kind)
    print(compiled.memory_analysis())
    plane = (
        f"[{batcher._n_pages},16,{cfg.num_kv_heads},{cfg.head_dim}]"
    )
    arena = f"[{cfg.num_layers},{plane[1:]}"
    instr = re.compile(
        r"\s*(?:ROOT )?(%?[\w.\-]+) = (\w+\[[\d,]*\])\S* ([\w\-]+)\("
    )
    for line in hlo.splitlines():
        m = instr.match(line)
        if not m:
            continue
        if m.group(2).endswith((plane, arena)) and m.group(3) not in (
            "parameter", "get-tuple-element", "bitcast",
        ) or "paged_decode_attention" in m.group(1):
            print(*m.groups())
    return 0


if __name__ == "__main__":
    sys.exit(main())
