"""Share of its rows' contexts that the window layers' decode steps
read: keys read (min(context, window) a row, layer and step) over the
keys those rows hold in context. None where the program has no such
counters (a model without window pages counts nothing)."""

UNIT, LAYER, MOVES, SOURCE = "%", "KV manager", "call_ms_p50", "program_counter"


def read(ctx):
    from benchmark.stats import ratio_of_deltas

    share = ratio_of_deltas(
        ctx["stats1"], ctx["stats0"], "windowKeysRead", "windowKeysContext")
    return None if share is None else 100.0 * share
