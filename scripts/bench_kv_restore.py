#!/usr/bin/env python3
"""Per-page restore-vs-recompute crossover (docs/paged_kv.md "Host
tier" methodology).

The host tier's bet is that restoring a demoted page — unpack one
KVPagePayload + one H2D `.at[pages].set` — is cheaper than recomputing
it: a prefill forward over page_size tokens. This instrument measures
both sides per page-count on THIS machine and reports the crossover,
so the byte budget and page size can be tuned from data instead of
faith. On CPU the "H2D copy" is a memcpy and prefill is slow, so
restore wins everywhere; the run that means something is on the TPU
(JAX_PLATFORMS unset), where the H2D copy has real cost and the MXU
makes recompute cheap.

Usage:
  JAX_PLATFORMS=cpu python scripts/bench_kv_restore.py
  python scripts/bench_kv_restore.py --model tiny-llama --page-size 16 \
      --pages 1,2,4,8,16 --repeat 5

Writes bench_artifacts/kv_restore_crossover.json and prints a table.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--model", default="tiny-llama")
    parser.add_argument("--page-size", type=int, default=16)
    parser.add_argument("--pages", default="1,2,4,8,16")
    parser.add_argument("--repeat", type=int, default=5)
    parser.add_argument(
        "--out", default="bench_artifacts/kv_restore_crossover.json"
    )
    args = parser.parse_args()

    import jax
    import numpy as np

    from ggrmcp_tpu.utils.jaxenv import init_runtime

    init_runtime("bench_kv_restore")

    from ggrmcp_tpu.core.config import (
        BatchingConfig,
        MeshConfig,
        ObservabilityConfig,
        ServingConfig,
    )
    from ggrmcp_tpu.models import get_model
    from ggrmcp_tpu.serving.batching import ContinuousBatcher
    from ggrmcp_tpu.serving.engine import GenerationEngine

    _, mcfg = get_model(args.model)
    engine = GenerationEngine(mcfg, ServingConfig(
        model=args.model,
        mesh=MeshConfig(tensor=0),
        observability=ObservabilityConfig(enabled=False),
    ))
    page_counts = [int(x) for x in args.pages.split(",") if x]
    max_pages = max(page_counts)
    s_max = 512
    batcher = ContinuousBatcher(engine, BatchingConfig(
        max_batch_size=2,
        kv_cache_max_seq=s_max,
        paged_kv="on",
        paged_kv_page_size=args.page_size,
        paged_kv_host_bytes=1 << 30,
    ))

    # Populate one chain of max_pages indexed pages, then demote them
    # into the host pool so both sides measure REAL page payloads.
    prompt = [(i * 13 + 5) % 199 + 3 for i in range(
        max_pages * args.page_size + 1
    )]
    batcher.pages.admit(0, prompt, need_len=len(prompt) + 2)
    batcher.pages.register(0, prompt)
    chain = batcher.pages.chain_pages(prompt)
    blobs = batcher._demote_fetch(chain)

    def time_restore(n: int) -> float:
        """Median seconds for unpack + H2D write of n pages (first
        sample warms the per-shape scatter program off the clock, like
        the recompute side)."""
        dst = np.asarray(chain[:n], np.int32)
        samples = []
        for _ in range(args.repeat + 1):
            t0 = time.perf_counter()
            batcher._restore_write([int(p) for p in dst], blobs[:n])
            jax.block_until_ready(
                batcher.cache.k.q
                if hasattr(batcher.cache.k, "q") else batcher.cache.k
            )
            samples.append(time.perf_counter() - t0)
        return sorted(samples[1:])[len(samples[1:]) // 2]

    def time_recompute(n: int) -> float:
        """Median seconds to PREFILL n pages' worth of tokens — the
        price of an eviction without a host tier."""
        tokens = prompt[: n * args.page_size]
        samples = []
        for _ in range(args.repeat + 1):  # first sample warms the jit
            t0 = time.perf_counter()
            out, _ = engine.generate(
                [tokens], max_new_tokens=1, seed=0
            )
            samples.append(time.perf_counter() - t0)
        return sorted(samples[1:])[len(samples[1:]) // 2]

    page_bytes = len(blobs[0])
    rows = []
    crossover = None
    for n in page_counts:
        restore_s = time_restore(n)
        recompute_s = time_recompute(n)
        rows.append({
            "pages": n,
            "tokens": n * args.page_size,
            "restore_ms": round(restore_s * 1000, 3),
            "recompute_ms": round(recompute_s * 1000, 3),
            "speedup": round(recompute_s / restore_s, 2)
            if restore_s > 0 else float("inf"),
        })
        if crossover is None and restore_s < recompute_s:
            crossover = n
    result = {
        "model": args.model,
        "platform": jax.devices()[0].platform,
        "page_size": args.page_size,
        "page_payload_bytes": page_bytes,
        "repeat": args.repeat,
        "restore_wins_from_pages": crossover,
        "rows": rows,
        "note": (
            "CPU numbers understate H2D cost and overstate prefill "
            "cost; re-run in a TPU window before tuning budgets "
            "(docs/paged_kv.md 'Host tier')."
        ),
    }
    out_path = Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(result, indent=2) + "\n")
    print(
        f"kv restore crossover ({args.model}, {jax.devices()[0].platform},"
        f" page_size={args.page_size}, payload {page_bytes} B/page)"
    )
    print(f"{'pages':>6} {'restore ms':>11} {'recompute ms':>13} {'x':>6}")
    for row in rows:
        print(
            f"{row['pages']:>6} {row['restore_ms']:>11} "
            f"{row['recompute_ms']:>13} {row['speedup']:>6}"
        )
    print(f"wrote {out_path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
