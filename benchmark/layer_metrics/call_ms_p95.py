"""The 95th percentile of whole-call time over the calls completing in
the window. With 80 to 170 calls a window it is the 4th- to 9th-longest
call: seeds agree on it to 0.1% until one early stop or one late tick
reorders the closed loop, and then it reads 2% off. A number with two
values cannot carry a bound under the check's rules, so it is read here
and the median decides."""

UNIT, LAYER, MOVES, SOURCE = "ms", "tick loop", "call_ms_p50", "host_clock"


def read(ctx):
    from benchmark.stats import percentile

    ms = [c.ms for c in ctx["calls"] if c.ok]
    return percentile(ms, 95) if ms else None
