"""How close a decode step of the latent-attention + routed-experts
family comes to the memory-bandwidth floor: the weights every step
reads, the routed experts it hit, and the latents of the live tokens,
over the chip's peak bytes/s, as a share of the measured step time."""

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "call_ms_p50", "device_trace"


def read(ctx):
    from benchmark import roofline, roofline_mla_moe
    from benchmark.plugins import metric
    from benchmark.stats import delta, ratio_of_deltas

    step_ms = metric(ctx, "step_ms_mean")
    steps = delta(ctx["stats1"], ctx["stats0"], "decodeSteps")
    hit = ratio_of_deltas(
        ctx["stats1"], ctx["stats0"], "moeExpertsHit", "decodeSteps")
    if not step_ms or steps <= 0 or not hit:  # no counter: not this family
        return None
    live = roofline.live_tokens_per_step(
        [c for c in ctx["calls"] if c.ok], steps)
    floor = roofline_mla_moe.step_floor_ms(
        ctx["config"], ctx["device"]["kind"], hit, live)
    return 100.0 * floor / step_ms
