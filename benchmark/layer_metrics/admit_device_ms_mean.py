"""Mean time one admission program held the device alone: from the tick
in flight leaving the device (or the launch's return, with none in
flight) to the program's first tokens on the host. Delta of the
sidecar's admit_device_ms sum over the delta of its count, one
observation a program call. None where the program has no such
counter."""

UNIT, LAYER, MOVES, SOURCE = (
    "ms", "scheduler and admission", "call_ms_p50", "program_counter")


def read(ctx):
    from benchmark.stats import ratio_of_deltas

    return ratio_of_deltas(
        ctx["stats1"], ctx["stats0"], "admitDeviceMsSum", "admitDeviceMsCount")
