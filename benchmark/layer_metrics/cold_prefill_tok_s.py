"""Prompt tokens the admission programs computed (cold documents and
the fresh tail of a reused history; tokens taken from shared pages are
not counted) a second of the tick loop's admission phase."""

UNIT, LAYER, MOVES, SOURCE = (
    "tokens/s", "scheduler and admission", "call_ms_p50", "program_counter")


def read(ctx):
    from benchmark.stats import ratio_of_deltas

    per_ms = ratio_of_deltas(
        ctx["stats1"], ctx["stats0"], "prefillTokensComputed", "tickPhaseAdmitMs")
    # No token counted: a program without the counter. Nothing to read.
    return per_ms * 1000.0 if per_ms else None
