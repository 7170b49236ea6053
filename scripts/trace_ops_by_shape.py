#!/usr/bin/env python3
"""Device time of the operations whose name matches a pattern, by the
program that ran them.

An operation's name on a trace's "XLA Ops" line is its HLO text, result
shape first (`%fusion.7 = f32[32,16,5120]{..} fusion(..`), so a shape is
a pattern: where no kernel names a piece of work (the state-space
family's chunk scan and decode-step state update are XLA), its shapes
do. From a kept trace (`benchmark/run.py --trace 1 --keep-trace` leaves
`benchmark_out/<cell>/trace.xplane.pb`):

    python3 scripts/trace_ops_by_shape.py <trace.xplane.pb> \\
        h='f32\\[[0-9,]*16,5120\\]' conv='bf16\\[[0-9,]*3,5120\\]'

For each program on "XLA Modules" (`jit__tick_impl`, `jit__admit_*`; the
runs the capture cut short left out) it prints one JSON line: runs, the
program's time, each pattern's self time inside those runs (an
operation's duration less what nests in it, `benchmark/trace.self_times`;
an operation counts under the first pattern it matches) with its share
of the program, and the five longest matching operations. No JAX.
"""

from __future__ import annotations

import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import trace, xplane  # noqa: E402


def family(name: str) -> str:
    """`jit__admit_paged_pfx_impl(123)` -> `_admit_paged_pfx_impl`."""
    return re.sub(r"^jit_|\(.*$", "", name.split("#")[0])


def by_program(planes: list, patterns: dict) -> list:
    plane = next(p for p in planes if trace.DEVICE_PLANE.match(p.name))
    lines = {ln.name: ln for ln in plane.lines}
    ops = trace.self_times(lines[trace.OPS_LINE].events)
    t_lo = min(e.start_ps for e, _ in ops)
    t_hi = max(e.end_ps for e, _ in ops)
    out: dict = {}
    for run in lines[trace.MODULES_LINE].events:
        if run.start_ps < t_lo or run.end_ps > t_hi:
            continue  # cut by the capture
        row = out.setdefault(family(run.name), {
            "runs": 0, "program_ms": 0.0,
            "ms": {k: 0.0 for k in patterns}, "ops": {}})
        row["runs"] += 1
        row["program_ms"] += run.duration_ps / 1e9
        for e, self_ps in ops:
            if not run.start_ps <= e.start_ps < run.end_ps:
                continue
            hit = next((k for k, rx in patterns.items() if rx.search(e.name)),
                       None)
            if hit is not None:
                row["ms"][hit] += self_ps / 1e9
                key = f"{hit}: {e.name[:160]}"
                row["ops"][key] = row["ops"].get(key, 0.0) + self_ps / 1e9
    lines_out = []
    for name, row in sorted(out.items()):
        total = row["program_ms"]
        lines_out.append({
            "program": name, "runs": row["runs"], "program_ms": total,
            "ms": row["ms"],
            "share": {k: v / total for k, v in row["ms"].items()},
            "longest": sorted(
                row["ops"].items(), key=lambda kv: -kv[1])[:5]})
    return lines_out


def main() -> int:
    if len(sys.argv) < 3 or any("=" not in a for a in sys.argv[2:]):
        print(__doc__, file=sys.stderr)
        return 2
    patterns = {k: re.compile(v) for k, v in (
        a.split("=", 1) for a in sys.argv[2:])}
    for line in by_program(xplane.load(sys.argv[1]), patterns):
        print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
