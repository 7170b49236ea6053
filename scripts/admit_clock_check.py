#!/usr/bin/env python3
"""Check the admission round's `device` mark against the device's clock.

While a profile capture runs, every admission program call is a
`ggrmcp.admit.program` span (launch -> first tokens on the host) around
a `ggrmcp.admit.device` span, which is the round's `device` mark on the
profiler's clock: from the tick in flight leaving the device (or the
launch's return) to `np.asarray(first)` returning. This script reads a
kept trace (`benchmark/run.py --trace 1 --keep-trace` leaves
`benchmark_out/<cell>/trace.xplane.pb`), pairs every program span with
the `jit__admit_*` event on "XLA Modules" inside it, and reports how far
the device span is from the module's duration, and where the module
started against the device span (a module that starts before the span
ran beside the tick's tail, or started before the launch returned):

    python3 scripts/admit_clock_check.py benchmark_out/<cell>/trace.xplane.pb

It prints one JSON line. Spans the capture cut (no module wholly
inside) are counted and left out. No JAX: `benchmark/xplane.py` reads
the file.
"""

from __future__ import annotations

import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import trace, xplane  # noqa: E402

PROGRAM, DEVICE = "ggrmcp.admit.program", "ggrmcp.admit.device"
# 5% or 2 ms is agreement (ISSUE 39, item 7).
AGREE_SHARE, AGREE_MS = 0.05, 2.0


def pair(planes: list) -> tuple:
    """([(device span ms, module ms, module start - span start ms,
    module name)], program spans with no whole module inside)."""
    host = [
        e for p in planes if not trace.DEVICE_PLANE.match(p.name)
        for ln in p.lines for e in ln.events
    ]
    programs = sorted(
        (e for e in host if e.name.split("#")[0] == PROGRAM), key=lambda e: e.start_ps)
    devices = [e for e in host if e.name.split("#")[0] == DEVICE]
    modules = [
        e for p in planes if trace.DEVICE_PLANE.match(p.name)
        for ln in p.lines if ln.name == trace.MODULES_LINE
        for e in ln.events if "_admit_" in e.name
    ]
    pairs, cut = [], 0
    for prog in programs:
        inside = [m for m in modules
                  if prog.start_ps <= m.start_ps and m.end_ps <= prog.end_ps]
        span = [d for d in devices
                if prog.start_ps <= d.start_ps and d.end_ps <= prog.end_ps]
        if len(inside) != 1 or len(span) != 1:
            cut += 1
            continue
        (m,), (d,) = inside, span
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
