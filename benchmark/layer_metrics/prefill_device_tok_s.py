"""Prompt tokens the admission programs computed (cold documents and
the fresh tail of a reused history; tokens taken from shared pages are
not counted) a second of the device's time in those programs
(admit_device_ms): cold_prefill_tok_s without the round's host work and
its wait for the tick in flight. None where the program has no such
counter."""

UNIT, LAYER, MOVES, SOURCE = (
    "tokens/s", "scheduler and admission", "call_ms_p50", "program_counter")


def read(ctx):
    from benchmark.stats import ratio_of_deltas

    per_ms = ratio_of_deltas(
        ctx["stats1"], ctx["stats0"], "prefillTokensComputed", "admitDeviceMsSum")
    return per_ms * 1000.0 if per_ms else None
