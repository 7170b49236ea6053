"""Mean time from the pop that put a request into an admission batch
to its slot's activation (the second half of queue_ms: the executor
hand-off plus the admission program): delta of the sidecar's prefill_ms
sum over the delta of its count. None where the program has no such
counter."""

UNIT, LAYER, MOVES, SOURCE = (
    "ms", "scheduler and admission", "call_ms_p50", "program_counter")


def read(ctx):
    from benchmark.stats import ratio_of_deltas

    return ratio_of_deltas(
        ctx["stats1"], ctx["stats0"], "prefillMsSum", "prefillMsCount")
