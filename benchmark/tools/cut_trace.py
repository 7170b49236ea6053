#!/usr/bin/env python3
"""Cut a recorded `.xplane.pb` down to a test fixture: keep the device
planes' events and the host events inside [start, start + length) of
the trace (seconds from its first device event), drop stats.

    python3 benchmark/tools/cut_trace.py in.xplane.pb out.xplane.pb 0.5 0.08
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import trace, xplane  # noqa: E402


def main(src: str, dst: str, start_s: str, length_s: str) -> None:
    planes = xplane.load(src)
    first = min(
        e.start_ps for p in planes if trace.DEVICE_PLANE.match(p.name)
        for ln in p.lines for e in ln.events
    )
    lo = first + int(float(start_s) * 1e12)
    hi = lo + int(float(length_s) * 1e12)
    kept = []
    for p in planes:
        device = bool(trace.DEVICE_PLANE.match(p.name))
        lines = []
        for ln in p.lines:
            events = [
                xplane.Event(e.name[:96], e.start_ps, e.duration_ps)
                for e in ln.events if e.start_ps >= lo and e.end_ps <= hi
            ]
            if not device:
                # host lines: the few longest spans are enough to label gaps
                events = sorted(events, key=lambda e: -e.duration_ps)[:40]
                events.sort(key=lambda e: e.start_ps)
            if events:
                lines.append(xplane.Line(ln.name, events))
        if lines:
            kept.append(xplane.Plane(p.name, lines))
    with open(dst, "wb") as f:
        f.write(xplane.dump(kept))
    print(dst, os.path.getsize(dst), "bytes",
          [(p.name, [(ln.name, len(ln.events)) for ln in p.lines]) for p in kept])


if __name__ == "__main__":
    main(*sys.argv[1:5])
