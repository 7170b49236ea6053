"""How close a decode step comes to the memory-bandwidth floor: the
weight bytes a step streams plus the K/V bytes of the live tokens, over
the chip's peak bytes/s, as a share of the measured step time."""

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "call_ms_p50", "device_trace"


def read(ctx):
    from benchmark import roofline
    from benchmark.plugins import metric
    from benchmark.stats import delta

    step_ms = metric(ctx, "step_ms_mean")
    steps = delta(ctx["stats1"], ctx["stats0"], "decodeSteps")
    if not step_ms or steps <= 0:
        return None
    live = roofline.live_tokens_per_step(
        [c for c in ctx["calls"] if c.ok], steps)
    floor = roofline.decode_step_floor_ms(
        ctx["config"], ctx["device"]["kind"], live, ctx["device"]["count"])
    return 100.0 * floor / step_ms
