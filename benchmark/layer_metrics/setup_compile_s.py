"""Wall time of every XLA compile (or compile-cache load) the stack
made before the window opened: the part of `setup_s` that the warm-up
ladder and the probes cost. None where the program has no compile
watcher."""

UNIT, LAYER, MOVES, SOURCE = "s", "programs", "setup_s", "program_counter"


def read(ctx):
    ms = ctx["stats0"].get("compileMs")
    return float(ms) / 1000.0 if ms is not None else None
