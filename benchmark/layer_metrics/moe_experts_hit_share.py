"""Share of an expert layer's routed experts that a decode step reads:
distinct experts a valid token reached, over (expert layer, step)
instances x experts. 16 rows choosing 6 of 128 at random would hit
1 - (122/128)^16 = 54%."""

UNIT, LAYER, MOVES, SOURCE = "%", "expert layer", "call_ms_p50", "program_counter"


def read(ctx):
    from benchmark.stats import ratio_of_deltas

    experts = ctx["config"].get("n_routed_experts")
    share = ratio_of_deltas(
        ctx["stats1"], ctx["stats0"], "moeExpertsHit", "moeLayerSteps")
    return None if share is None or not experts else 100.0 * share / experts
