"""Device time of one decode step: busy time of the decode tick
program in the trace over the steps it ran (launches in the trace x
steps per tick, the latter from the decode_steps and ticks deltas)."""

UNIT, LAYER, MOVES, SOURCE = "ms", "model step", "call_ms_p50", "device_trace"


def read(ctx):
    from benchmark.stats import ratio_of_deltas

    trace = ctx["trace"]
    per_tick = ratio_of_deltas(ctx["stats1"], ctx["stats0"], "decodeSteps", "ticks")
    if not trace or not trace["program_runs"] or not per_tick:
        return None
    return trace["program_s"] * 1000.0 / (trace["program_runs"] * per_tick)
