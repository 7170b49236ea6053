"""What the sidecar's Generate handler adds around the batcher: its
own mean time per call (prompt ids, grammar and adapter set-up, submit,
collect, detokenising) minus the batcher's mean end-to-end time over
the same window. The sidecar's part of `gateway_added_ms`. None where
the program has no such counter."""

UNIT, LAYER, MOVES, SOURCE = "ms", "sidecar rpc", "call_ms_p50", "program_counter"


def read(ctx):
    from benchmark.stats import ratio_of_deltas

    rpc = ratio_of_deltas(
        ctx["stats1"], ctx["stats0"], "rpcGenerateMsSum", "rpcGenerateMsCount")
    inner = ratio_of_deltas(ctx["stats1"], ctx["stats0"], "e2eMsSum", "e2eMsCount")
    if rpc is None or inner is None:
        return None
    return rpc - inner
