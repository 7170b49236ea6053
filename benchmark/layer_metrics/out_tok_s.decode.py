"""Output tokens a second of the calls completing inside the window, in
a cell that completes some eighty calls: one call more or fewer at the
window's edge is 1.2% of the total, so the number jumps between two
values from run to run and cannot carry a bound. In a closed loop the
median call time carries the same information (calls in flight = calls
a second x call time) and is continuous; it decides, this is read."""

UNIT, LAYER, MOVES, SOURCE = "tokens/s", "tick loop", "call_ms_p50", "host_clock"


def read(ctx):
    done = sum(c.completion_tokens for c in ctx["calls"] if c.ok)
    return done / ctx["window_s"] if ctx["window_s"] > 0 else None
