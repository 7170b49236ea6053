#!/usr/bin/env python3
"""Run one benchmark cell exactly as `benchmark/run.py` does, and then
print one more JSON line with what the sidecar itself counted over the
window: calls it finished and the mean of its `e2e_ms`.

A `--trace 0` run's result line carries end-to-end metrics only, so the
cost of tracing (a `--trace 1` run against a `--trace 0` run of the
same seed) could not be read on the sidecar's own clock. This wrapper
changes nothing in the run: it keeps the dict `run.drive` returns (the
ServingStats at the window's ends, read by the harness in every mode)
and reads two deltas from it. Same arguments as `benchmark/run.py`.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import run, stats  # noqa: E402


def main() -> int:
    kept: dict = {}
    drive = run.drive

    async def keeping(*args, **kwargs):
        kept.update(await drive(*args, **kwargs))
        return kept

    run.drive = keeping
    rc = run.main()
    if kept:
        s0, s1 = kept["stats0"], kept["stats1"]
        print(json.dumps({
            "sidecar_calls": stats.delta(s1, s0, "e2eMsCount"),
            "sidecar_e2e_ms_mean": stats.ratio_of_deltas(
                s1, s0, "e2eMsSum", "e2eMsCount"),
            "window_s": kept["t1"] - kept["t0"],
        }), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
