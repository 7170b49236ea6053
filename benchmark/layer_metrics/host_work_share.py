"""Share of the window that was the batcher thread's own work: the
admission rounds' host segments (building, launching, activating:
admit_host_ms) with the ticks' sync, dispatch and host phases, over the
window's length on the host's clock. What tick_host_share meant to be:
that one sums the whole admit phase, which also holds the admission
programs' device time and the wait for the tick in flight, and divides
by the tick duration sum, which on a pipelined loop is about twice the
window (durations overlap by the one-tick lag). All of these are
stretches of one thread's time that do not overlap, as
admit_device_share's are, so the two shares together cannot pass 100.
None where the program has no admit_host_ms counter, or nothing ran."""

UNIT, LAYER, MOVES, SOURCE = "%", "tick loop", "call_ms_p50", "program_counter"


def read(ctx):
    from benchmark.stats import delta

    window_ms = 1000.0 * ctx["window_s"]
    if window_ms <= 0 or "admitHostMsSum" not in ctx["stats1"]:
        return None
    host = delta(ctx["stats1"], ctx["stats0"], "admitHostMsSum") + sum(
        delta(ctx["stats1"], ctx["stats0"], f"tickPhase{p}MsSum")
        for p in ("Sync", "Dispatch", "Host")
    )
    return 100.0 * host / window_ms if host > 0 else None
