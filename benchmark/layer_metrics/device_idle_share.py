"""Share of the traced window in which no operation ran on the device."""

UNIT, LAYER, MOVES, SOURCE = "%", "device", "call_ms_p50", "device_trace"


def read(ctx):
    trace = ctx["trace"]
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
