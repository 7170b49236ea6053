"""Share of admitted prompt pages that were reused from the page index
instead of prefilled."""

UNIT, LAYER, MOVES, SOURCE = "%", "KV manager", "call_ms_p50", "program_counter"


def read(ctx):
    from benchmark.stats import ratio_of_deltas

    share = ratio_of_deltas(
        ctx["stats1"], ctx["stats0"], "pagedPagesReused", "pagedPagesAdmitted")
    return None if share is None else 100.0 * share
