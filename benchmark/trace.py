"""The reduction from a profiler trace to numbers: device busy time,
the longest device operations, the longest idle gaps with what the host
was doing, the time of one named program, the time in collectives.
Input is what `xplane.parse` returns; nothing here touches JAX.
"""

from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
)


# Host spans that only say a thread was parked: a gap is labelled with
# one of them only when no span that does work covers it.
WAITING = re.compile(r"sleep|poll|select|wait|futex|acquire|Condition|queue", re.I)


def union(intervals: list) -> list:
    """Merge (start, end) intervals."""
    merged: list = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def _line(plane, name: str):
    for line in plane.lines:
        if line.name == name:
            return line
    return None


def short_name(name: str, limit: int = 64) -> str:
    """A trace name made safe for a JSON key on one line."""
    return re.sub(r"[^A-Za-z0-9_.:\-]+", "_", name)[:limit]


def reduce(planes: list, program: str = "_tick_impl") -> dict | None:
    """All the benchmark reads from one trace. None when no operation
    ran on a device (a CPU trace, or a capture that missed the work)."""
    devices = [p for p in planes if DEVICE_PLANE.match(p.name)]
    per_device = []
    for plane in devices:
        ops = _line(plane, OPS_LINE) or _line(plane, MODULES_LINE)
        if ops is None or not ops.events:
            continue
        busy = union([(e.start_ps, e.end_ps) for e in ops.events])
        per_device.append((plane, ops, busy))
    if not per_device:
        return None
    # The traced window is the span in which device events were being
    # recorded: host tracing starts earlier and stops later than the
    # device's, and that margin is no idle time.
    t_lo = min(ops.events[0].start_ps for _, ops, _ in per_device)
    t_hi = max(busy[-1][1] for _, _, busy in per_device)
    window_s = (t_hi - t_lo) / 1e12
    n = len(per_device)
    busy_s = sum(sum(b - a for a, b in busy) for _, _, busy in per_device) / n / 1e12

    # One named program on the modules line: its time and its launches.
    # The capture cuts the first and the last run short: leave them out.
    prog_s = prog_runs = 0.0
    for plane, _, _ in per_device:
        modules = _line(plane, MODULES_LINE)
        runs = [e for e in (modules.events if modules else [])
                if program in e.name]
        for e in runs[1:-1] if len(runs) > 2 else runs:
            prog_s += e.duration_ps / 1e12
            prog_runs += 1
    prog_s, prog_runs = prog_s / n, prog_runs / n

    coll_s = sum(
        e.duration_ps for _, ops, _ in per_device for e in ops.events
        if COLLECTIVE.search(e.name)
    ) / n / 1e12

    # Longest operations and gaps: the first device stands for all.
    _, ops0, busy0 = per_device[0]
    by_name: dict = {}
    for e, self_ps in self_times(ops0.events):
        by_name[e.name] = by_name.get(e.name, 0) + self_ps
    device_ops = [
        [short_name(name), ps / 1e12]
        for name, ps in sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    ]
    gaps = sorted(
        ((busy0[i + 1][0] - busy0[i][1], busy0[i][1], busy0[i + 1][0])
         for i in range(len(busy0) - 1)),
        reverse=True,
    )[:5]
    host_events = [
        e for p in planes if not DEVICE_PLANE.match(p.name)
        for ln in p.lines for e in ln.events
    ]
    idle_gaps = [
        [_gap_label(host_events, a, b, t_lo), length / 1e12]
        for length, a, b in gaps
    ]
    return {
        "busy_s": busy_s, "window_s": window_s, "devices": n,
        "program_s": prog_s, "program_runs": prog_runs,
        "collective_s": coll_s,
        "device_ops": device_ops, "idle_gaps": idle_gaps,
    }


def self_times(events: list) -> list:
    """(event, self time): an operation's duration less that of the
    operations nested inside it on the same line (a `while` holds its
    body's operations), so that a container is not counted twice."""
    out, stack = [], []  # stack of [event, child picoseconds]
    for e in sorted(events, key=lambda e: (e.start_ps, -e.duration_ps)):
        while stack and e.start_ps >= stack[-1][0].end_ps:
            done, inner = stack.pop()
            out.append((done, done.duration_ps - inner))
        if stack:
            stack[-1][1] += e.duration_ps
        stack.append([e, 0])
    while stack:
        done, inner = stack.pop()
        out.append((done, done.duration_ps - inner))
    return out


def _gap_label(host_events: list, a: int, b: int, t_lo: int) -> str:
    """What the host was doing in the gap [a, b): the shortest host
    event that covers the whole gap (one that does work, before one
    that only waits), else the one that overlaps it most,
    else `unattributed_<seconds into the trace>s`."""
    covering = [e for e in host_events if e.start_ps <= a and e.end_ps >= b]
    working = [e for e in covering if not WAITING.search(e.name)]
    if working or covering:
        return short_name(
            min(working or covering, key=lambda e: e.duration_ps).name)
    best, best_overlap = None, 0
    for e in host_events:
        overlap = min(e.end_ps, b) - max(e.start_ps, a)
        if overlap > best_overlap:
            best, best_overlap = e, overlap
    if best is not None:
        return short_name(best.name)
    return f"unattributed_{(a - t_lo) / 1e12:.6f}s"
