"""Share of tick time the host spent outside waiting for the device:
the admit, sync, dispatch and host phases over the tick duration."""

UNIT, LAYER, MOVES, SOURCE = "%", "tick loop", "call_ms_p50", "program_counter"


def read(ctx):
    from benchmark.stats import delta

    total = delta(ctx["stats1"], ctx["stats0"], "tickDurationMsSum")
    if total <= 0:
        return None
    host = sum(
        delta(ctx["stats1"], ctx["stats0"], f"tickPhase{p}MsSum")
        for p in ("Admit", "Sync", "Dispatch", "Host")
    )
    return 100.0 * host / total
