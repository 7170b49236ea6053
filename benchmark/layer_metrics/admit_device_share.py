"""Share of the window the device spent in admission programs alone:
the admit_device_ms sum over the window's length on the host's clock
(the two stats reads bound the window). Not over the tick duration sum
that tick_host_share divides by: on a pipelined loop a tick's duration
holds the one-tick lag, so durations overlap and sum to about twice the
window, and a share of them means something else in every cell. None
where the program has no such counter, or no admission ran."""

UNIT, LAYER, MOVES, SOURCE = (
    "%", "scheduler and admission", "call_ms_p50", "program_counter")


def read(ctx):
    from benchmark.stats import delta

    device = delta(ctx["stats1"], ctx["stats0"], "admitDeviceMsSum")
    window_ms = 1000.0 * ctx["window_s"]
    return 100.0 * device / window_ms if device > 0 and window_ms > 0 else None
