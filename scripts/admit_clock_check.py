#!/usr/bin/env python3
"""Check the admission round's `device` mark against the device's clock.

While a profile capture runs, every admission program's launch is a
`ggrmcp.admit.program` span and every wait for a program a
`ggrmcp.admit.device` span, which is the round's `device` mark on the
profiler's clock: it ends when `block_until_ready` on the program's
first tokens returns, and starts when the tick in flight had left the
device (a settle that only waits), or when the host came to wait (a
round's second program, whose launch, seat and the next tick's dispatch
the host did while the program ran; since PR 52 the settle follows that
dispatch). This script reads a kept trace (`benchmark/run.py --trace 1
--keep-trace` leaves `benchmark_out/<cell>/trace.xplane.pb`), pairs
every device span with the `jit__admit_*` event on "XLA Modules" that
ENDS inside it (or up to 2 ms before it: the host came late), and
reports how far the span is from the module's duration, and where the
module started against the span (a module that starts before the span
ran while the host still worked, or beside the tick's tail):

    python3 scripts/admit_clock_check.py benchmark_out/<cell>/trace.xplane.pb

It prints one JSON line. Spans the capture cut (no module ends there)
are counted and left out. No JAX: `benchmark/xplane.py` reads the file.
"""

from __future__ import annotations

import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import trace, xplane  # noqa: E402

DEVICE = "ggrmcp.admit.device"
# 5% or 2 ms is agreement (ISSUE 39, item 7).
AGREE_SHARE, AGREE_MS = 0.05, 2.0


def pair(planes: list) -> tuple:
    """([(device span ms, module ms, module start - span start ms,
    module name)], device spans with no module ending in them)."""
    host = [
        e for p in planes if not trace.DEVICE_PLANE.match(p.name)
        for ln in p.lines for e in ln.events
    ]
    devices = sorted(
        (e for e in host if e.name.split("#")[0] == DEVICE),
        key=lambda e: e.start_ps)
    modules = [
        e for p in planes if trace.DEVICE_PLANE.match(p.name)
        for ln in p.lines if ln.name == trace.MODULES_LINE
        for e in ln.events if "_admit_" in e.name
    ]
    late_ps = int(AGREE_MS * 1e9)
    pairs, cut, taken = [], 0, set()
    for d in devices:
        ends = [m for m in modules if id(m) not in taken
                and d.start_ps - late_ps <= m.end_ps <= d.end_ps]
        if not ends:
            cut += 1
            continue
        m = max(ends, key=lambda m: m.end_ps)
        taken.add(id(m))
        pairs.append((d.duration_ps / 1e9, m.duration_ps / 1e9,
                      (m.start_ps - d.start_ps) / 1e9, m.name))
    return pairs, cut


def main() -> int:
    if len(sys.argv) != 2:
        print(__doc__, file=sys.stderr)
        return 2
    pairs, cut = pair(xplane.load(sys.argv[1]))
    if not pairs:
        print(json.dumps({"pairs": 0, "cut": cut}))
        return 1
    diffs = [d - m for d, m, _, _ in pairs]
    agree = [
        abs(d - m) <= max(AGREE_MS, AGREE_SHARE * m) for d, m, _, _ in pairs
    ]
    worst = max(range(len(pairs)), key=lambda i: abs(diffs[i]))
    print(json.dumps({
        "pairs": len(pairs), "cut": cut, "agree": sum(agree),
        "diff_ms_median": statistics.median(diffs),
        "diff_ms_largest": diffs[worst],
        "largest_at": {
            "device_span_ms": pairs[worst][0], "module_ms": pairs[worst][1],
            "module_start_after_span_start_ms": pairs[worst][2],
            "module": trace.short_name(pairs[worst][3]),
        },
        "module_ms_median": statistics.median(m for _, m, _, _ in pairs),
        "device_span_ms_median": statistics.median(d for d, _, _, _ in pairs),
        "module_start_after_span_start_ms_median": statistics.median(
            s for _, _, s, _ in pairs),
        "each": [[round(d, 3), round(m, 3), round(s, 3)]
                 for d, m, s, _ in pairs][:64],
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
