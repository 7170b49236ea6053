"""Share of the tick loop's busy time spent between executor work
items: waiting for the executor thread, waiting for the event loop to
resume the batcher, and the loop-side python, over the whole turn
(those three plus the work inside the executor calls). The part of the
host's time `tick_host_share` cannot see. None where the program has
no such counters."""

UNIT, LAYER, MOVES, SOURCE = "%", "tick loop", "call_ms_p50", "program_counter"


def read(ctx):
    from benchmark.stats import delta

    busy = delta(ctx["stats1"], ctx["stats0"], "loopBusyMsSum")
    if busy <= 0:
        return None
    handoffs = sum(
        delta(ctx["stats1"], ctx["stats0"], f"loop{part}MsSum")
        for part in ("ExecWait", "Lag", "Host")
    )
    return 100.0 * handoffs / busy
