"""The pipelined batcher's two tick lengths (serving/batching.py
_tick_steps): a short tick while a request waits or a slot is free, the
full decode_steps_per_tick otherwise. The same step program at another
static count: tokens, step accounting, warm-up and reserves."""

import asyncio

import pytest

from ggrmcp_tpu.core.config import (
    BatchingConfig,
    MeshConfig,
    ServingConfig,
    short_tick_steps,
)
from ggrmcp_tpu.models import llama
from ggrmcp_tpu.ops.sampling import SamplingConfig
from ggrmcp_tpu.serving.batching import ContinuousBatcher
from ggrmcp_tpu.serving.engine import GenerationEngine

FULL = 4
SHORT = short_tick_steps(FULL)
JOIN_AT = 8  # the decode step at which the second request is admitted
FILL = (SHORT,) * (FULL // SHORT)  # short ticks that make up one full one
SAMPLING = {
    "greedy": SamplingConfig(temperature=0.0),
    "seeded": SamplingConfig(temperature=0.9, top_k=16, top_p=0.95),
}


@pytest.fixture(scope="module")
def engine():
    return GenerationEngine(
        llama.CONFIGS["tiny-llama"],
        ServingConfig(
            mesh=MeshConfig(tensor=2, data=0),
            batching=BatchingConfig(max_batch_size=4, kv_cache_max_seq=256),
        ),
    )


def make_batcher(engine, slots=2, steps=FULL, pipeline="on"):
    return ContinuousBatcher(
        engine,
        BatchingConfig(
            max_batch_size=slots, kv_cache_max_seq=256,
            decode_steps_per_tick=steps, pipeline_ticks=pipeline,
        ),
    )


async def collect(batcher, prompt, max_new, sampling, seed):
    out: list[int] = []
    async for ids, _ in batcher.submit(prompt, max_new, sampling, seed=seed):
        out.extend(ids)
    return out


async def two_requests(engine, sampling, script):
    """One request from step 0 and a second admitted by the admission
    turn that precedes the dispatch at decode step JOIN_AT, whatever the
    ticks before it were: `script` is the cycle of lengths the
    dispatches take (None: the batcher's own policy). Returns both
    outputs, the lengths dispatched and the batcher's counters."""
    batcher = make_batcher(engine)
    dispatched: list[int] = []
    policy = batcher._tick_steps

    def tick_steps():
        steps = policy() if script is None else script[
            len(dispatched) % len(script)]
        dispatched.append(steps)
        return steps

    batcher._tick_steps = tick_steps
    second: list = []
    admit = batcher._admit

    async def admit_joining():
        if not second and batcher.step_counter == JOIN_AT:
            second.append(asyncio.ensure_future(collect(
                batcher, [2, 7, 1, 8], 14, sampling, seed=11)))
            while batcher.pending.empty():
                await asyncio.sleep(0)
        return await admit()

    batcher._admit = admit_joining
    batcher.start()
    try:
        first = await collect(batcher, [3, 1, 4, 1, 5], 30, sampling, seed=5)
        assert second, f"no dispatch began at step {JOIN_AT}: {dispatched}"
        return first, await second[0], dispatched, batcher.counter_stats()
    finally:
        await batcher.stop()


_reference: dict = {}


async def fixed_loop(engine, mode):
    """The fixed-length loop's run (every tick FULL), once a mode."""
    if mode not in _reference:
        _reference[mode] = await two_requests(engine, SAMPLING[mode], (FULL,))
    return _reference[mode]


@pytest.mark.parametrize("script", [
    None, (FULL, *FILL), (*FILL, FULL), (SHORT,),
], ids=["policy", "full-then-short", "short-then-full", "all-short"])
@pytest.mark.parametrize("mode", list(SAMPLING))
async def test_any_interleaving_emits_the_fixed_loops_tokens(engine, mode, script):
    """The sampler's draw is tagged with the decode step's index, and
    `step_counter` advances by the steps dispatched: the same tokens
    however the steps are grouped into ticks, for a request from step 0
    and for one admitted mid-run at the same step."""
    want_first, want_second, fixed, _ = await fixed_loop(engine, mode)
    assert set(fixed) == {FULL}
    first, second, dispatched, stats = await two_requests(
        engine, SAMPLING[mode], script)
    assert len(first) <= 30 and len(second) <= 14
    assert (first, second) == (want_first, want_second)
    # The step accounting is whole: decode_steps is the sum of what was
    # dispatched, short_ticks counts the dispatches under the full length.
    assert stats["decode_steps"] == sum(dispatched)
    assert stats["ticks"] == len(dispatched)
    assert stats["short_ticks"] == sum(s < FULL for s in dispatched)
    if script is None:
        assert {SHORT, FULL} == set(dispatched)


async def test_seeded_sampling_draws_by_step(engine):
    """What the seeded cases above rest on: were the two modes' outputs
    equal, they would prove nothing about the step tags."""
    greedy = await fixed_loop(engine, "greedy")
    seeded = await fixed_loop(engine, "seeded")
    assert greedy[:2] != seeded[:2]


async def test_a_full_pool_with_an_empty_queue_dispatches_full_ticks_only(engine):
    """Two requests on two slots: while both decode nobody can be
    admitted and the ticks are full; once one has finished its slot is
    free and the ticks are short. The flight record carries each
    tick's length."""
    batcher = make_batcher(engine)
    batcher.start()
    try:
        greedy = SAMPLING["greedy"]
        await asyncio.gather(
            collect(batcher, [3, 1, 4], 40, greedy, 0),
            collect(batcher, [2, 7, 1], 12, greedy, 1),
        )
        ticks = batcher.recorder.tick_snapshot()
    finally:
        await batcher.stop()
    both = [t.steps for t in ticks if t.active_slots == 2]
    one = [t.steps for t in ticks if t.active_slots == 1]
    assert both and set(both) == {FULL}
    assert one and set(one) == {SHORT}
    assert ticks[0].to_dict()["steps"] == ticks[0].steps


@pytest.mark.parametrize("prompt_len,after", [(3, SHORT), (40, FULL)])
async def test_the_tick_after_a_long_admission_is_full(engine, prompt_len, after):
    """Between two admission rounds the decoding rows advance by one
    tick, the one dispatched after the round. Once the programs since
    the last dispatch ran more chunk tokens than a full tick's steps
    times prefill_chunk (here 4 x 8 = 32) the rows stood still for
    longer than a full tick, and that tick is full although a slot is
    free; after a short admission it is short like the others."""
    batcher = ContinuousBatcher(engine, BatchingConfig(
        max_batch_size=2, kv_cache_max_seq=256, prefill_chunk=8,
        decode_steps_per_tick=FULL, pipeline_ticks="on",
    ))
    batcher.start()
    try:
        prompt = [3 + i % 5 for i in range(prompt_len)]
        await collect(batcher, prompt, 20, SAMPLING["greedy"], 0)
        ticks = batcher.recorder.tick_snapshot()
    finally:
        await batcher.stop()
    after_round = [t.steps for t in ticks if t.phase_admit_ms > 0.0]
    behind_a_tick = [t.steps for t in ticks if t.phase_admit_ms == 0.0]
    assert after_round == [after]
    assert behind_a_tick and set(behind_a_tick) == {SHORT}


def test_warmup_compiles_both_lengths(engine):
    """No compile on the first short tick, nor on the first full one."""
    batcher = make_batcher(engine)
    batcher.warmup()
    assert batcher._tick._cache_size() == 2

    async def run():
        batcher.start()
        try:
            await asyncio.gather(*(
                collect(batcher, [3 + i, 1, 4], 10 + 8 * i, SAMPLING["greedy"], i)
                for i in range(3)
            ))
            return batcher.counter_stats()
        finally:
            await batcher.stop()

    stats = asyncio.run(run())
    assert 0 < stats["short_ticks"] < stats["ticks"]
    assert batcher._tick._cache_size() == 2


@pytest.mark.parametrize("steps,pipeline,short,reserve", [
    (4, "on", 2, 7), (8, "on", 4, 15), (2, "on", 1, 3), (1, "on", 1, 1),
    (4, "off", 4, 3), (1, "off", 1, 0),
])
def test_the_reserve_derives_from_the_full_length(
    engine, steps, pipeline, short, reserve
):
    batcher = make_batcher(engine, steps=steps, pipeline=pipeline)
    # Half the full tick on the pipelined loop, the full tick without.
    assert batcher._short_steps == short
    assert short == (short_tick_steps(steps) if pipeline == "on" else steps)
    assert batcher._reserve == reserve
    # The constrained rows' twin, from the wider of the full tick and
    # the jump window, as before.
    window = max(steps, 1 + batcher._jump_max)
    assert batcher._jump_reserve == (
        2 * window - 1 if pipeline == "on" else window - 1)


@pytest.mark.parametrize("steps,pipeline", [(1, "on"), (4, "off")])
async def test_one_length_where_short_is_full_or_nothing_is_in_flight(
    engine, steps, pipeline
):
    """decode_steps_per_tick 1 (every CPU mesh by default): short equals
    full and nothing changes. Without the pipeline a collect follows its
    own dispatch, so an admission waits behind no tick: full ticks."""
    batcher = make_batcher(engine, steps=steps, pipeline=pipeline)
    batcher.start()
    try:
        out = await collect(batcher, [3, 1, 4], 9, SAMPLING["greedy"], 0)
        stats = batcher.counter_stats()
    finally:
        await batcher.stop()
    assert len(out) <= 9
    assert stats["short_ticks"] == 0
    assert stats["decode_steps"] == steps * stats["ticks"]
