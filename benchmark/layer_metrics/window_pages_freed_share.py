"""Share of the window layers' pages mapped in the window (for
admissions' live tails and ahead of decode steps) that rows let go of
again by the position rule: a page's last position more than window - 1
behind the row's next query. Near 100% in steady state: a row holds a
window's worth whatever its context. None where the program has no
such counters."""

UNIT, LAYER, MOVES, SOURCE = "%", "KV manager", "call_ms_p50", "program_counter"


def read(ctx):
    from benchmark.stats import ratio_of_deltas

    share = ratio_of_deltas(
        ctx["stats1"], ctx["stats0"], "pagedWindowPagesFreed",
        "pagedWindowPagesMapped")
    return None if share is None else 100.0 * share
