"""How close a decode step of `model_type: KeyeVL2` comes to the
memory-bandwidth floor: the weights every step reads, the experts it
hit, each live row's indexer keys and the K and V it selected, over the
chip's peak bytes/s, as a share of the measured step time. None where
the configuration has no `sa_config`, the program no expert counters,
or the capture no tick."""

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "call_ms_p50", "device_trace"


def read(ctx):
    from benchmark import roofline_keye
    from benchmark.plugins import metric
    from benchmark.stats import delta, ratio_of_deltas

    topk = (ctx["config"].get("sa_config") or {}).get("topk")
    step_ms = metric(ctx, "step_ms_mean")
    steps = delta(ctx["stats1"], ctx["stats0"], "decodeSteps")
    hit = ratio_of_deltas(
        ctx["stats1"], ctx["stats0"], "moeExpertsHit", "decodeSteps")
    if not topk or not step_ms or steps <= 0 or not hit:
        return None
    visible, selected = roofline_keye.tokens_per_step(
        [c for c in ctx["calls"] if c.ok], steps, topk)
    floor = roofline_keye.step_floor_ms(
        ctx["config"], ctx["device"]["kind"], hit, visible, selected)
    return 100.0 * floor / step_ms
