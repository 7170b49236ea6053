"""Mean time to the first token on the sidecar's clock (queue wait +
admission prefill), from the ttft_ms sum and count deltas."""

UNIT, LAYER, MOVES, SOURCE = (
    "ms", "scheduler and admission", "call_ms_p50", "program_counter")


def read(ctx):
    from benchmark.stats import ratio_of_deltas

    return ratio_of_deltas(ctx["stats1"], ctx["stats0"], "ttftMsSum", "ttftMsCount")
