"""How close a decode step of `model_name: smallthinker_*` comes to the
memory-bandwidth floor: the weights every step reads, the experts it
hit, and each decoding row's K and V (the whole context in the full
layers, min(context, window) keys in the window layers), over the
chip's peak bytes/s, as a share of the measured step time. None where
the configuration has no `sliding_window_layout`, the program no
expert counters, or the capture no tick."""

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "call_ms_p50", "device_trace"


def read(ctx):
    from benchmark import roofline_smallthinker
    from benchmark.plugins import metric
    from benchmark.stats import delta, ratio_of_deltas

    config = ctx["config"]
    if not config.get("sliding_window_layout"):
        return None
    step_ms = metric(ctx, "step_ms_mean")
    steps = delta(ctx["stats1"], ctx["stats0"], "decodeSteps")
    hit = ratio_of_deltas(
        ctx["stats1"], ctx["stats0"], "moeExpertsHit", "decodeSteps")
    if not step_ms or steps <= 0 or not hit:
        return None
    context, windowed = roofline_smallthinker.keys_per_step(
        [c for c in ctx["calls"] if c.ok], steps,
        int(config["sliding_window_size"]))
    floor = roofline_smallthinker.step_floor_ms(
        config, ctx["device"]["kind"], hit, context, windowed)
    return 100.0 * floor / step_ms
