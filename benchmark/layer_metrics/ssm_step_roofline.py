"""How close a decode step of `model_type: jamba` comes to the
memory-bandwidth floor: every weight once, each decoding row's
recurrent state read and written in every Mamba layer, and the live
tokens' K and V in the attention layers, over the chip's peak bytes/s,
as a share of the measured step time. None where the configuration has
no state-space layers, the program no state pool, or the
capture no tick."""

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "call_ms_p50", "device_trace"


def read(ctx):
    from benchmark import roofline_jamba
    from benchmark.plugins import metric
    from benchmark.stats import delta

    if "mamba_d_state" not in ctx["config"]:
        return None
    if not (ctx["stats1"] or {}).get("statePoolTotal"):
        return None
    step_ms = metric(ctx, "step_ms_mean")
    steps = delta(ctx["stats1"], ctx["stats0"], "decodeSteps")
    if not step_ms or steps <= 0:
        return None
    calls = [c for c in ctx["calls"] if c.ok]
    floor = roofline_jamba.step_floor_ms(
        ctx["config"], ctx["device"]["kind"],
        roofline_jamba.rows_per_step(calls, steps),
        roofline_jamba.live_tokens_per_step(calls, steps))
    return 100.0 * floor / step_ms
