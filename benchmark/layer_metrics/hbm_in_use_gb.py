"""Device memory the allocator reports in use on the fullest chip, the
largest of the readings taken over the window."""

UNIT, LAYER, MOVES, SOURCE = "GB", "device memory", "call_ms_p50", "program_counter"


def read(ctx):
    peak = ctx["memory_peak_bytes"]
    return peak / 1e9 if peak else None
