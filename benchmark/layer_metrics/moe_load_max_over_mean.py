"""Imbalance of the routed experts in the decode steps: the largest
load (routed pairs) of one expert over the mean load of all experts,
both summed over (expert layer, step) instances. 1 = even."""

UNIT, LAYER, MOVES, SOURCE = "x", "expert layer", "call_ms_p50", "program_counter"


def read(ctx):
    from benchmark.stats import ratio_of_deltas

    experts = ctx["config"].get("n_routed_experts")
    ratio = ratio_of_deltas(
        ctx["stats1"], ctx["stats0"], "moeLoadMaxSum", "moeRoutedPairs")
    return None if ratio is None or not experts else ratio * experts
