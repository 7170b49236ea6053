"""Mean time a request lay in the admission queue before `_admit` took
it into a batch (the first half of queue_ms): delta of the sidecar's
pending_ms sum over the delta of its count. None where the program has
no such counter."""

UNIT, LAYER, MOVES, SOURCE = (
    "ms", "scheduler and admission", "call_ms_p50", "program_counter")


def read(ctx):
    from benchmark.stats import ratio_of_deltas

    return ratio_of_deltas(
        ctx["stats1"], ctx["stats0"], "pendingMsSum", "pendingMsCount")
