"""Share of admissions' snapshot lookups (a prefix of indexed pages was
matched, for a model whose rows keep a recurrent state) that found a
state to restore. None on a program without the counters."""

UNIT, LAYER, MOVES, SOURCE = "%", "KV manager", "call_ms_p50", "program_counter"


def read(ctx):
    from benchmark.stats import ratio_of_deltas

    share = ratio_of_deltas(
        ctx["stats1"], ctx["stats0"], "stateSnapshotHits",
        "stateSnapshotLookups")
    return None if share is None else 100.0 * share
