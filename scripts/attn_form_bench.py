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

Then the indexer member's chunk (`deepseek-v3.2-ep16-5l`: 128 heads,
each query its 2,048 selected keys, a 32,768-key plane): the walk
masked by the selection, as `attention_block` runs it where the kernel
is not the call's kind (expanded, to the last real query's key), against
the kernel given the selection, for a suffix of 250 real queries in
the chunk of 512 and for a full chunk. The selection is an input
there (random index scores through `selection_mask`).

Last, making that selection (`ops/indexer.selection_mask`, the cut
found by counting) beside the same four lines with the cut read off
`lax.top_k`, which the chip sorts the whole row for: the cut alone and
the whole mask, float32 index scores of 512 or 256 queries that see
every key of the width.
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

    # (model, plane, then rows, queries, past and real queries a case;
    # 0 real queries: all of them, and no selection)
    plain, sparse = (
        [("tiny-mla-moe", 256, [(1, 64, 128, 0), (1, 16, 128, 0)]),
         ("tiny-dsv32", 256, [(1, 64, 128, 30), (1, 64, 128, 64)])]
        if args.cpu else
        [("kanana-2-30b-a3b-6l", 16384, [
            (1, 512, 12288, 0), (1, 512, 4096, 0), (1, 256, 12288, 0),
            (1, 128, 12288, 0), (1, 64, 12288, 0), (4, 128, 12288, 0),
            (16, 1, 12288, 0)]),
         ("deepseek-v3.2-ep16-5l", 32768, [
            (1, 512, 12288, 250), (1, 512, 12288, 512),
            (1, 512, 18432, 250), (1, 512, 18432, 512)])]
    )
    for name, s_max, cases in (plain, sparse):
        bench(args, M.CONFIGS[name], s_max, cases)
    bench_selection(args, *(
        (16, [(64, 128), (64, 256)]) if args.cpu else
        (2048, [(512, 4096), (512, 16384), (512, 32768), (256, 32768)])))
    return 0


def median_ms(fn, operands, reps: int) -> float:
    """Of a program its caller has already run once (compiled)."""
    import jax

    times = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*operands))
        times.append((time.perf_counter() - t) * 1000.0)
    return statistics.median(times)


def bench_selection(args, topk: int, cases) -> None:
    import jax
    import jax.numpy as jnp

    from ggrmcp_tpu.ops import indexer

    def sorted_cut(scores, k):
        return jax.lax.top_k(scores, k)[0][..., -1:]

    def sorted_mask(scores, k):  # selection_mask as it was with the sort
        thr = sorted_cut(scores, k)
        above = scores > thr
        tied = (scores == thr) & (scores > -jnp.inf)
        need = k - above.sum(-1, keepdims=True)
        return above | (tied & (jnp.cumsum(tied, axis=-1) <= need))

    forms = {
        "cut, lax.top_k": sorted_cut,
        "cut, counted": indexer.kth_largest,
        "mask, lax.top_k": sorted_mask,
        "mask, counted": indexer.selection_mask,
    }
    print(f"device {jax.devices()[0].device_kind}, the selection of "
          f"{topk} keys a query")
    for s, width in cases:
        scores = jnp.round(jax.random.normal(
            jax.random.PRNGKey(width + s), (1, s, width), jnp.float32), 3)
        got = {}
        for form, run in forms.items():
            fn = jax.jit(lambda scores, run=run: run(scores, topk))
            got[form] = jax.block_until_ready(fn(scores))
            print(f"queries {s:4d} keys {width:6d} {form}: "
                  f"{median_ms(fn, (scores,), args.reps):8.3f} ms",
                  flush=True)
        for kind in ("cut", "mask"):
            assert bool((got[f"{kind}, lax.top_k"]
                         == got[f"{kind}, counted"]).all()), (kind, s, width)


def bench(args, cfg, s_max: int, cases) -> None:
    import jax
    import jax.numpy as jnp

    from ggrmcp_tpu.models import mla_moe as M
    from ggrmcp_tpu.ops import attention as A

    dtype = cfg.jnp_dtype
    h, nope, rope = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    width = cfg.kv_planes[0][0]
    print(f"device {jax.devices()[0].device_kind}, {cfg.name}")
    for rows, s, past, n_real in cases:
        key = jax.random.PRNGKey(0)
        lat = jax.random.normal(key, (rows, s_max, width), dtype)
        q_nope = jax.random.normal(key, (rows, s, h, nope), dtype)
        q_rope = jax.random.normal(key, (rows, s, h, rope), dtype)
        wkv_b = jax.random.normal(
            key, (cfg.kv_lora_rank, h, nope + cfg.v_head_dim), dtype) * 0.04
        q_pos = past + jnp.broadcast_to(jnp.arange(s), (rows, s))
        kv_len = jnp.full((rows,), past + s, jnp.int32)
        last = q_pos[:, (n_real or s) - 1]  # a row's last real query
        block = M._key_block(rows, s, h, s_max, 1)
        select = ()
        if n_real:
            k_pos = jnp.arange(s_max)[None, None, :]
            select = (jax.jit(functools.partial(
                M.selection_mask, topk=cfg.index_topk))(jnp.where(
                    k_pos <= q_pos[:, :, None],
                    jax.random.normal(key, (rows, s, s_max)), -jnp.inf)),)

        def walk(absorbed, q_nope, q_rope, lat, wkv_b, q_pos, kv_len, last,
                 *select):
            def fetch(i):
                return jax.lax.dynamic_slice_in_dim(lat, i * block, block, 1)

            return M.latent_attention(
                q_nope, q_rope, fetch, (jnp.max(last) + block) // block,
                block, wkv_b, q_pos, kv_len, cfg, absorbed=absorbed,
                allowed=(lambda i: jax.lax.dynamic_slice_in_dim(
                    select[0], i * block, block, 2)) if select else None)

        def kernel(q_nope, q_rope, lat, wkv_b, q_pos, kv_len, last, *select):
            # The folding `mla_moe.attention_block` does around it.
            out = A.latent_prefill_attention(
                M.absorbed_queries(q_nope, q_rope, wkv_b[..., :nope], width),
                lat[None], jnp.int32(0), q_pos[:, 0], kv_len, last, *select,
                value_width=cfg.kv_lora_rank, scale=cfg.softmax_scale,
                interpret=args.cpu)
            return jnp.einsum("bshc,chd->bshd", out, wkv_b[..., nope:])

        forms = {
            "absorbed": functools.partial(walk, True),
            "expanded": functools.partial(walk, False),
            "kernel": kernel,
        }
        operands = (q_nope, q_rope, lat, wkv_b, q_pos, kv_len, last, *select)
        want = None
        for form, run in forms.items():
            fn = jax.jit(run)
            out = jax.block_until_ready(fn(*operands))[:, :n_real or s]
            if form == "absorbed":
                want = out.astype(jnp.float32)
            ms = median_ms(fn, operands, args.reps)
            diff = (
                f"  max|kernel - absorbed| "
                f"{float(jnp.abs(out.astype(jnp.float32) - want).max()):.4f}"
                if form == "kernel" else ""
            )
            real = f" ({n_real} real, selected)" if n_real else ""
            print(f"rows {rows:2d} queries {s:4d}{real} past {past:6d} "
                  f"block {block:4d} {form}: "
                  f"{ms:8.3f} ms{diff}", flush=True)


if __name__ == "__main__":
    sys.exit(main())
