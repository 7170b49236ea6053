"""Share of the visible context a decode step of a model with a
sparse-attention indexer attends: keys the selecting queries attended
over keys they could see, over the (row, layer) instances in which a
selection ran (a row no longer than index_topk selects nothing and is
not counted). None where the program has no such counters."""

UNIT, LAYER, MOVES, SOURCE = "%", "sparse attention", "call_ms_p50", "program_counter"


def read(ctx):
    from benchmark.stats import ratio_of_deltas

    share = ratio_of_deltas(
        ctx["stats1"], ctx["stats0"], "sparseKeysSelected", "sparseKeysVisible")
    return None if share is None else 100.0 * share
