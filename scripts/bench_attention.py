"""On-chip flash-vs-XLA attention benchmark.

Times `flash_attention` (compiled Pallas) against `attention_xla`
across sequence lengths at Llama-1B-like shapes, prints a markdown
table (docs/perf_attention.md) and a suggested FLASH_MIN_SEQ crossover.

Run on the TPU:  python scripts/bench_attention.py
CPU smoke of the script's control flow (the kernel interpreted, so its
timings mean nothing):
  JAX_PLATFORMS=cpu python scripts/bench_attention.py --seqs 256 --interpret
"""

from __future__ import annotations

import argparse
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from ggrmcp_tpu.ops.attention import attention_xla, flash_attention
from ggrmcp_tpu.utils.jaxenv import init_runtime


def _time(fn, *args, iters: int = 20, warmup: int = 3, **kw) -> float:
    """Median wall-clock ms per call, after warmup (compile amortized)."""
    for _ in range(warmup):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        jax.block_until_ready(out)
        samples.append((time.perf_counter() - t0) * 1000)
    return statistics.median(samples)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv-heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument(
        "--seqs", type=int, nargs="*",
        default=[128, 256, 512, 1024, 2048, 4096, 8192],
    )
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument(
        "--interpret", action="store_true",
        help="run the Pallas kernel in the interpreter (CPU smoke only)",
    )
    args = ap.parse_args()

    init_runtime("bench_attention")
    dev = jax.devices()[0]
    print(f"platform={dev.platform} kind={dev.device_kind}")
    print(
        f"B={args.batch} H={args.heads} KVH={args.kv_heads} "
        f"D={args.head_dim} dtype={args.dtype}"
    )
    dtype = jnp.dtype(args.dtype)
    key = jax.random.PRNGKey(0)

    xla_jit = jax.jit(attention_xla, static_argnames=("causal",))

    rows = []
    crossover = None
    win_src = None  # (s, q, kk, vv, t_flash) at the longest seq
    longest = max(args.seqs, default=0)
    for s in args.seqs:
        q = jax.random.normal(
            key, (args.batch, s, args.heads, args.head_dim)
        ).astype(dtype)
        kk = jax.random.normal(
            jax.random.fold_in(key, 1),
            (args.batch, s, args.kv_heads, args.head_dim),
        ).astype(dtype)
        vv = jax.random.normal(
            jax.random.fold_in(key, 2),
            (args.batch, s, args.kv_heads, args.head_dim),
        ).astype(dtype)
        reps = args.heads // args.kv_heads
        k_rep = jnp.repeat(kk, reps, axis=2)
        v_rep = jnp.repeat(vv, reps, axis=2)

        t_xla = _time(xla_jit, q, k_rep, v_rep, causal=True, iters=args.iters)
        t_flash = _time(
            flash_attention, q, kk, vv, causal=True, iters=args.iters,
            interpret=args.interpret,
        )
        speedup = t_xla / t_flash if t_flash else float("inf")
        if crossover is None and speedup >= 1.0:
            crossover = s
        rows.append((s, t_xla, t_flash, speedup))
        if s == longest:
            win_src = (s, q, kk, vv, t_flash)
        print(
            f"S={s:6d}  xla={t_xla:8.3f}ms  flash={t_flash:8.3f}ms  "
            f"flash_speedup={speedup:5.2f}x",
            flush=True,
        )

    print("\n| seq len | XLA (ms) | flash (ms) | flash speedup |")
    print("|---|---|---|---|")
    for s, t_xla, t_flash, speedup in rows:
        print(f"| {s} | {t_xla:.3f} | {t_flash:.3f} | {speedup:.2f}x |")
    if crossover is not None:
        print(f"\nsuggested FLASH_MIN_SEQ: {crossover}")

    # Sliding-window skip win at the longest measured length: the
    # loop's full-causal flash timing vs window = S/2 (the kernel
    # starts each q-block's k-loop at the window floor —
    # docs/perf_attention.md). Reuses the loop's tensors and timing.
    if win_src is not None and win_src[0] >= 512:
        s, q, kk, vv, t_full = win_src
        t_win = _time(flash_attention, q, kk, vv, causal=True,
                      window=s // 2, iters=args.iters,
                      interpret=args.interpret)
        print(
            f"\nwindowed flash @ S={s}, W={s // 2}: full={t_full:.3f}ms "
            f"windowed={t_win:.3f}ms ({t_full / max(t_win, 1e-9):.2f}x)"
        )


if __name__ == "__main__":
    main()
