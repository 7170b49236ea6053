#!/usr/bin/env python3
"""Which block sizes for the dense family's prefill kernel? Times
`ops/attention.flash_attention`, the function alone (its wrapper's
transposes included), at the two shapes the benchmark's cells reach,
mistral-7b's 32 query heads on 8 KV heads of 128, bf16, window 4,096:

    python3 scripts/flash_form_bench.py            # on the TPU
    python3 scripts/flash_form_bench.py --cpu      # rehearsal, tiny sizes

- a chunk of the `[8, 4, 512]` admission grid: 8 rows x 512 queries over
  the 2,048-position mini cache, at each of the scan's four offsets (0,
  512, 1,024, 1,536; `kv_len` = offset + 512), and their sum, which is
  one layer of one admission program;
- one row's 256-token suffix on a reused prefix of 1,024 and of 1,536
  positions in the same mini cache, and 256 fresh tokens alone.

Prints one line a (shape, block sizes): milliseconds a call (`--reps`
calls queued back to back, one wait, so a call's host launch hides
behind the call before it) and the output's rms difference from
`attention_xla` over the same values in float32, relative to that
output's rms. "auto" is what `flash_attention` picks from the shapes
(`_flash_blocks`); `attention_xla` on the bf16 operands is timed beside
it. With `--trace` a profiler capture of five more passes of each shape
at the function's own choice then splits the figure into the kernel
and the wrapper's copies, by the device's own clock (self time a
pass; `benchmark/trace.py` reads it, as it reads a cell's). `_FLASH_ROWS`
/ `_FLASH_BLOCK_K` in ops/attention.py were set from this table
(docs/perf_attention.md).
"""

from __future__ import annotations

import argparse
import functools
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# (block_q, block_k); None: the function's own choice
CANDIDATES = [None, (128, 128), (128, 256), (128, 512), (128, 1024),
              (256, 256), (256, 512), (512, 512)]


def ms_a_call(call, reps: int) -> float:
    """Of a program its caller has already run once (compiled)."""
    import jax

    t = time.perf_counter()
    for _ in range(reps):
        out = call()
    jax.block_until_ready(out)
    return (time.perf_counter() - t) * 1000.0 / reps


def device_ops(calls, passes: int = 5) -> None:
    """Prints the longest device operations of `passes` passes over
    `calls` by self time a pass. Nothing on the CPU: its trace has no
    device plane."""
    import glob
    import tempfile

    import jax

    from benchmark import trace, xplane

    with tempfile.TemporaryDirectory() as path:
        jax.profiler.start_trace(path)
        for _ in range(passes):
            out = [call() for call in calls]
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        reduced = trace.reduce(xplane.load(glob.glob(os.path.join(
            path, "plugins", "profile", "*", "*.xplane.pb"))[0]))
    for name, seconds in (reduced or {}).get("device_ops", []):
        print(f"    {seconds / passes * 1e3:8.3f} ms  {name}", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--trace", action="store_true")
    args = ap.parse_args()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp

    from ggrmcp_tpu.ops import attention as A

    h, kvh, d, window = (4, 2, 32, 96) if args.cpu else (32, 8, 128, 4096)
    cut = 8 if args.cpu else 1  # the rehearsal's sizes are an eighth
    # (label, rows, queries, keys, offsets timed and summed)
    cases = [
        ("chunk grid, a layer of a program", 8 // cut, 512 // cut,
         2048 // cut, [0, 512 // cut, 1024 // cut, 1536 // cut]),
        ("suffix on a reused prefix", 1, 256 // cut, 2048 // cut,
         [1024 // cut, 1536 // cut]),
        ("fresh", 1, 256 // cut, 256 // cut, [0]),
    ]
    print(f"device {jax.devices()[0].device_kind}, {h} heads on {kvh} of {d}, "
          f"window {window}, bf16")
    for label, rows, sq, sk, offsets in cases:
        key = jax.random.PRNGKey(sq + sk)
        q = jax.random.normal(key, (rows, sq, h, d), jnp.bfloat16)
        k = jax.random.normal(
            jax.random.fold_in(key, 1), (rows, sk, kvh, d), jnp.bfloat16)
        v = jax.random.normal(
            jax.random.fold_in(key, 2), (rows, sk, kvh, d), jnp.bfloat16)
        exact = jax.jit(lambda q, k, v, **rows_at: A.attention_xla(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), causal=True, window=window, **rows_at))
        forms = {"attention_xla": jax.jit(functools.partial(
            A.attention_xla, causal=True, window=window))}
        for blocks in CANDIDATES:
            if blocks and (sq % min(blocks[0], sq) or sk % min(blocks[1], sk)):
                continue
            named = dict(block_q=blocks[0], block_k=blocks[1]) if blocks else {}
            forms[f"kernel {blocks or 'auto'}"] = functools.partial(
                A.flash_attention, causal=True, window=window,
                interpret=args.cpu, **named)
        at = [dict(q_offset=jnp.full((rows,), off, jnp.int32),
                   kv_len=jnp.full((rows,), off + sq, jnp.int32))
              for off in offsets]
        for form, fn in forms.items():
            total, worst = 0.0, 0.0
            for rows_at in at:
                call = functools.partial(fn, q, k, v, **rows_at)
                diff = call().astype(jnp.float32) - (
                    want := exact(q, k, v, **rows_at))
                worst = max(worst, float(
                    jnp.sqrt((diff ** 2).mean() / (want ** 2).mean())))
                total += ms_a_call(call, args.reps)
            print(f"{label}: {rows} x {sq} on {sk} at {offsets} {form}: "
                  f"{total:8.3f} ms, rms difference {worst:.2e}", flush=True)
        if args.trace:
            print(f"{label}: device operations of kernel auto, a pass over "
                  f"{offsets}", flush=True)
            device_ops([functools.partial(forms["kernel auto"], q, k, v, **rows_at)
                        for rows_at in at])
    return 0


if __name__ == "__main__":
    sys.exit(main())
