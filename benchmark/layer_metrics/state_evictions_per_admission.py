"""Row-state snapshots dropped, least recently used first, to make room
for a new one, a snapshot lookup (an admission that matched indexed
pages: every one of a cell whose calls share a system prompt). A pool
with room for its working set reads 0; what it drops is what a later
turn may have to recompute (`state_recompute_share`). None on a program
without the counters."""

UNIT, LAYER, MOVES, SOURCE = (
    "evict/admission", "KV manager", "call_ms_p50", "program_counter")


def read(ctx):
    from benchmark.stats import ratio_of_deltas

    return ratio_of_deltas(
        ctx["stats1"], ctx["stats0"], "stateSnapshotEvictions",
        "stateSnapshotLookups")
