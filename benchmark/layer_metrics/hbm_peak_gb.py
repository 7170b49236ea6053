"""The allocator's high-water mark on the fullest chip, read after the
window: holds what a once-a-second `hbm_in_use_gb` sample misses (the
admission programs' transient mini cache). None where the backend or
the program reports no peak."""

UNIT, LAYER, MOVES, SOURCE = "GB", "device memory", "call_ms_p50", "program_counter"


def read(ctx):
    peaks = [int(x) for x in ctx["memory"].get("devicePeakBytesInUse", [])]
    return max(peaks) / 1e9 if peaks else None
