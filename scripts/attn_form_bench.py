#!/usr/bin/env python3
"""Which form of latent attention for how many queries? Times
`models/mla_moe.latent_attention` in both forms (absorbed, expanded)
and the latent-prefill kernel (`ops/attention.latent_prefill_attention`,
the absorbed form with its score block in VMEM) at the published
widths, for a step of S queries a row against a latent past of P
tokens in a contiguous cache, on the chip:

    python3 scripts/attn_form_bench.py            # on the TPU
    python3 scripts/attn_form_bench.py --cpu      # rehearsal, tiny sizes

Prints one line a (rows, S, P, form): milliseconds a call, median of
`--reps` after a warm-up, and for the kernel its largest difference
from the absorbed walk. `ABSORBED_MAX_QUERIES` in models/mla_moe.py
was set from this table (PERF.md, section 4).
"""

from __future__ import annotations

import argparse
import functools
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--reps", type=int, default=10)
    args = ap.parse_args()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp

    from ggrmcp_tpu.models import mla_moe as M
    from ggrmcp_tpu.ops import attention as A

    cfg = M.CONFIGS["tiny-mla-moe" if args.cpu else "kanana-2-30b-a3b-6l"]
    dtype = cfg.jnp_dtype
    h, nope, rope = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    width = cfg.kv_planes[0][0]
    s_max = 256 if args.cpu else 16384
    cases = (
        [(1, 64, 128), (1, 16, 128)] if args.cpu else
        [(1, 512, 12288), (1, 512, 4096), (1, 256, 12288), (1, 128, 12288),
         (1, 64, 12288), (4, 128, 12288), (16, 1, 12288)]
    )
    print(f"device {jax.devices()[0].device_kind}, {cfg.name}")
    for rows, s, past in cases:
        key = jax.random.PRNGKey(0)
        lat = jax.random.normal(key, (rows, s_max, width), dtype)
        q_nope = jax.random.normal(key, (rows, s, h, nope), dtype)
        q_rope = jax.random.normal(key, (rows, s, h, rope), dtype)
        wkv_b = jax.random.normal(
            key, (cfg.kv_lora_rank, h, nope + cfg.v_head_dim), dtype) * 0.04
        q_pos = past + jnp.broadcast_to(jnp.arange(s), (rows, s))
        kv_len = jnp.full((rows,), past + s, jnp.int32)
        block = M._key_block(rows, s, h, s_max, 1)

        def walk(absorbed, q_nope, q_rope, lat, wkv_b, q_pos, kv_len):
            def fetch(i):
                return jax.lax.dynamic_slice_in_dim(lat, i * block, block, 1)

            n_blocks = (jnp.max(kv_len) + block - 1) // block
            return M.latent_attention(
                q_nope, q_rope, fetch, n_blocks, block, wkv_b, q_pos,
                kv_len, cfg, absorbed=absorbed)

        def kernel(q_nope, q_rope, lat, wkv_b, q_pos, kv_len):
            # The folding `mla_moe.attention_block` does around it.
            out = A.latent_prefill_attention(
                M.absorbed_queries(q_nope, q_rope, wkv_b[..., :nope], width),
                lat[None], jnp.int32(0), q_pos[:, 0], kv_len, q_pos[:, -1],
                value_width=cfg.kv_lora_rank,
                scale=(nope + rope) ** -0.5, interpret=args.cpu)
            return jnp.einsum("bshc,chd->bshd", out, wkv_b[..., nope:])

        forms = {
            "absorbed": functools.partial(walk, True),
            "expanded": functools.partial(walk, False),
            "kernel": kernel,
        }
        operands = (q_nope, q_rope, lat, wkv_b, q_pos, kv_len)
        want = None
        for form, run in forms.items():
            fn = jax.jit(run)
            out = jax.block_until_ready(fn(*operands))
            if form == "absorbed":
                want = out.astype(jnp.float32)
            times = []
            for _ in range(args.reps):
                t = time.perf_counter()
                jax.block_until_ready(fn(*operands))
                times.append((time.perf_counter() - t) * 1000.0)
            diff = (
                f"  max|kernel - absorbed| "
                f"{float(jnp.abs(out.astype(jnp.float32) - want).max()):.4f}"
                if form == "kernel" else ""
            )
            print(f"rows {rows:2d} queries {s:4d} past {past:6d} block {block:4d} "
                  f"{form}: {statistics.median(times):8.3f} ms{diff}",
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
