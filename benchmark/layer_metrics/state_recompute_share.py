"""Share of the tokens of matched pages that admissions ran again for
want of a state snapshot at the match's end. None on a program without
the counters."""

UNIT, LAYER, MOVES, SOURCE = "%", "KV manager", "call_ms_p50", "program_counter"


def read(ctx):
    from benchmark.stats import ratio_of_deltas

    share = ratio_of_deltas(
        ctx["stats1"], ctx["stats0"], "stateTokensRecomputed",
        "stateTokensMatched")
    return None if share is None else 100.0 * share
