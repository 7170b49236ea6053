"""Mean wait in the admission queue: delta of the sidecar's
queue_ms sum over the delta of its count."""

UNIT, LAYER, MOVES, SOURCE = (
    "ms", "scheduler and admission", "call_ms_p50", "program_counter")


def read(ctx):
    from benchmark.stats import ratio_of_deltas

    return ratio_of_deltas(ctx["stats1"], ctx["stats0"], "queueMsSum", "queueMsCount")
