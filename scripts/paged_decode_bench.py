#!/usr/bin/env python3
"""The two ways a dense-family decode step reads its pages, timed side
by side on the chip: `llama.paged_view` + `attention_xla` over the
full-width view against `ops.attention.paged_decode_attention` walking
each row's block table to its own length.

    python3 scripts/paged_decode_bench.py            # on the TPU
    python3 scripts/paged_decode_bench.py --cpu      # rehearsal, tiny sizes

At mistral-7b's widths (32/8 heads x 128, page 16, 8 slots x 2,048,
every layer's arena resident), one step = a scan over all layers, as a
decode step runs them, and one timed program = `--steps` such steps, so
that the host's launch does not pass for device time. Prints, for each
mix of row lengths and each form, milliseconds a step (median of
`--reps` after a warm-up), the live K/V bytes over that time, and the
largest difference from the gathered form. PAGED_DECODE_BLOCK_PAGES
was set from this table (PERF.md, section 6).
"""

from __future__ import annotations

import argparse
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
    ap.add_argument("--blocks", default="4,8,16")
    ap.add_argument(
        "--steps", type=int, default=8,
        help="decode steps one timed program runs (the host's launch is "
        "then an eighth of a step's share of it)")
    args = ap.parse_args()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ggrmcp_tpu.models import llama
    from ggrmcp_tpu.ops import attention as A

    if args.cpu:
        layers, b, h, kvh, d, page, width = 2, 4, 4, 2, 32, 8, 8
        dtype = jnp.float32
        mixes = {"short": (5, 9, 17, 40), "parked": (0, 64, 0, 33)}
    else:
        layers, b, h, kvh, d, page, width = 32, 8, 32, 8, 128, 16, 128
        dtype = jnp.bfloat16
        mixes = {
            # decode-steady: prompt + output so far, 64-512, mean ~220
            "decode": (64, 120, 150, 200, 230, 280, 350, 512),
            # agent-shared: 1,024-token system prompt + history
            "agent": (1060, 1130, 1250, 1380, 1500, 1620, 1740, 1800),
            "full": (2048,) * 8,
            "one_live": (300, 0, 0, 0, 0, 0, 0, 0),
        }
    n_pages = b * width
    key = jax.random.PRNGKey(0)
    k_arena = jax.random.normal(key, (layers, n_pages, page, kvh, d), dtype)
    v_arena = jax.random.normal(
        jax.random.fold_in(key, 1), (layers, n_pages, page, kvh, d), dtype)
    q = jax.random.normal(
        jax.random.fold_in(key, 2), (layers, b, 1, h, d), dtype)
    table = jnp.asarray(
        np.random.RandomState(0).permutation(n_pages).reshape(b, width),
        jnp.int32,
    )
    idx = jnp.arange(layers)

    def gathered(q, ka, va, table, kv_len):
        def body(_, x):
            ql, layer = x
            return None, A.attention_xla(
                ql, llama.paged_view(ka, table, layer),
                llama.paged_view(va, table, layer),
                causal=True, q_offset=kv_len - 1, kv_len=kv_len,
            )
        return jax.lax.scan(body, None, (q, idx))[1]

    def walked(block_pages):
        def f(q, ka, va, table, kv_len):
            def body(_, x):
                ql, layer = x
                return None, A.paged_decode_attention(
                    ql, ka, va, table, kv_len, layer,
                    block_pages=block_pages, interpret=args.cpu,
                )
            return jax.lax.scan(body, None, (q, idx))[1]
        return f

    def steps(step):  # `--steps` steps, each fed the one before
        def f(q, ka, va, table, kv_len):
            def body(q, _):
                out = step(q, ka, va, table, kv_len)
                return q + (out * 1e-3).astype(q.dtype), out
            return jax.lax.scan(body, q, None, length=args.steps)[1][-1]
        return f

    forms = {"gathered": steps(gathered)}
    for bp in (int(x) for x in args.blocks.split(",")):
        if bp <= width:
            forms[f"kernel bp={bp}"] = steps(walked(bp))

    print(f"device {jax.devices()[0].device_kind}, {layers} layers, "
          f"{b} rows x {width} pages of {page}")
    token_bytes = 2 * kvh * d * jnp.dtype(dtype).itemsize
    for name, lens in mixes.items():
        kv_len = jnp.asarray(lens, jnp.int32)
        live = np.asarray(lens) > 0
        tbl = jnp.where(jnp.asarray(live)[:, None], table, n_pages)
        live_bytes = layers * token_bytes * int(sum(lens))
        ref = None
        for form, fn in forms.items():
            fn = jax.jit(fn)
            out = jax.block_until_ready(fn(q, k_arena, v_arena, tbl, kv_len))
            times = []
            for _ in range(args.reps):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(q, k_arena, v_arena, tbl, kv_len))
                times.append(time.perf_counter() - t0)
            ms = statistics.median(times) * 1e3 / args.steps
            out = np.asarray(out, np.float32)[:, live]
            ref = out if ref is None else ref
            print(f"{name:9s} {form:14s} {ms:8.3f} ms a step  "
                  f"{live_bytes / ms / 1e6:7.1f} GB/s of live K/V  "
                  f"max|d| {np.abs(out - ref).max():.2e}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
