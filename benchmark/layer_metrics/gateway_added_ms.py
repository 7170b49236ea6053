"""What the gateway, the transcoding and the gRPC hop add to a call:
the client's mean call time minus the sidecar's own mean end-to-end
time over the same window."""

UNIT, LAYER, MOVES, SOURCE = "ms", "gateway", "call_ms_p50", "program_counter"


def read(ctx):
    from benchmark.stats import ratio_of_deltas

    inner = ratio_of_deltas(ctx["stats1"], ctx["stats0"], "e2eMsSum", "e2eMsCount")
    calls = [c for c in ctx["calls"] if c.ok]
    if inner is None or not calls:
        return None
    return sum(c.ms for c in calls) / len(calls) - inner
