"""Mean time between an executor call of the batcher loop ending on
its thread and the loop's coroutine running again: how long the shared
event loop (HTTP server, gRPC server, batcher) makes the batcher wait
for its turn. None where the program has no such counter."""

UNIT, LAYER, MOVES, SOURCE = "ms", "tick loop", "call_ms_p50", "program_counter"


def read(ctx):
    from benchmark.stats import ratio_of_deltas

    return ratio_of_deltas(
        ctx["stats1"], ctx["stats0"], "loopLagMsSum", "loopLagMsCount")
