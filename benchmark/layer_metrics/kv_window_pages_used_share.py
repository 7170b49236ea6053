"""Mean share of the window layers' page arena that is resident (live
+ kept for reuse), sampled once a second over the window. None where
the program has no such arena."""

UNIT, LAYER, MOVES, SOURCE = "%", "KV manager", "call_ms_p50", "program_counter"


def read(ctx):
    shares = [
        float(s.get("kvWindowPagesInUse", 0)) / float(s["kvWindowPagesTotal"])
        for s in ctx["samples"] if float(s.get("kvWindowPagesTotal", 0)) > 0
    ]
    return 100.0 * sum(shares) / len(shares) if shares else None
