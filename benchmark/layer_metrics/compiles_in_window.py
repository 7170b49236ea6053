"""Programs compiled after warm-up inside the measured window. Should
be 0; a run with any says so here instead of hiding a slow window."""

UNIT, LAYER, MOVES, SOURCE = "count", "programs", "call_ms_p50", "program_counter"


def read(ctx):
    from benchmark.stats import delta

    return delta(ctx["stats1"], ctx["stats0"], "compilePostWarmup")
