"""Mean share of the KV page arena that is resident (live + kept for
reuse), sampled once a second over the window."""

UNIT, LAYER, MOVES, SOURCE = "%", "KV manager", "call_ms_p50", "program_counter"


def read(ctx):
    shares = [
        float(s.get("kvPagesInUse", 0)) / float(s["kvPagesTotal"])
        for s in ctx["samples"] if float(s.get("kvPagesTotal", 0)) > 0
    ]
    return 100.0 * sum(shares) / len(shares) if shares else None
