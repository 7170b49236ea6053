"""An admission round reads no device result until everything it has
for the device is queued (serving/batching.py: _seat_slot /
_settle_slot, _settle_round after _tick_step's dispatch). CPU: counts
and identity only.

  * The same arrivals through a pipelined batcher give token for token
    the output of the synchronous reference (`pipeline_ticks` off), in
    each of the four admission families and, for a ROW_STATE family,
    with a snapshot restore in the round.
  * A row whose first token is EOS and a row with `max_new == 1` inside
    a deferred round end cleanly, and their slots are admitted into
    again.
  * A round with a grammar row keeps the synchronous order
    (`admit_rounds_deferred` does not count it) and emits the same
    tokens.
  * `admit_fail`, `tick_fail` and a program that fails mid-round, with
    rows seated and not settled, leave no active slot without a first
    token and give each request exactly one terminal chunk.
  * The round's marks sum to its duration, `device` does not hold the
    tick's dispatch, and `admit_rounds_deferred == admit_rounds` on a
    greedy pipelined run whose rounds each precede a tick.
"""

import asyncio

import numpy as np
import pytest

from ggrmcp_tpu.core.config import BatchingConfig, MeshConfig, ServingConfig
from ggrmcp_tpu.grammar import compile_schema
from ggrmcp_tpu.models import jamba, llama
from ggrmcp_tpu.ops.sampling import SamplingConfig
from ggrmcp_tpu.serving.batching import ContinuousBatcher
from ggrmcp_tpu.serving.engine import GenerationEngine
from ggrmcp_tpu.serving.flight_recorder import ADMIT_HOST_MARKS
from ggrmcp_tpu.utils import failpoints

GREEDY = SamplingConfig(temperature=0.0)
PREAMBLE = list(range(3, 67))  # four pages of 16
LONG = [5, 6, 7]  # the row that keeps a tick in flight
LONG_NEW = 60


@pytest.fixture(scope="module")
def engine():
    return GenerationEngine(
        llama.CONFIGS["tiny-mistral"],
        ServingConfig(mesh=MeshConfig(tensor=1, data=0)),
    )


@pytest.fixture(scope="module")
def jamba_engine():
    return GenerationEngine(
        jamba.CONFIGS["tiny-jamba"],
        ServingConfig(mesh=MeshConfig(tensor=1, data=1)),
    )


@pytest.fixture(autouse=True)
def clean_failpoints():
    failpoints.registry.disarm()
    yield
    failpoints.registry.disarm()


def make_batcher(engine, pipeline="on", **kw) -> ContinuousBatcher:
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("kv_cache_max_seq", 256)
    kw.setdefault("max_queue_delay_ms", 2.0)
    kw.setdefault("decode_steps_per_tick", 4)
    return ContinuousBatcher(
        engine, BatchingConfig(pipeline_ticks=pipeline, **kw))


async def chunks_of(batcher, prompt, max_new, seed=0, **kw) -> list:
    """Every (ids, reason) chunk the request's consumer sees."""
    return [
        (list(ids), reason) async for ids, reason in batcher.submit(
            list(prompt), max_new, GREEDY, seed=seed, **kw)
    ]


def tokens(chunks: list) -> list:
    return [t for ids, _ in chunks for t in ids]


def terminals(chunks: list) -> list:
    return [reason for _, reason in chunks if reason is not None]


async def run_waves(engine, waves, pipeline, **cfg):
    """One long row from the start, so that a tick is in flight whenever
    a wave is admitted; then the waves, one admission round each (a
    wave's requests are queued together), each awaited to its end.
    Returns (the long row's tokens, the waves' tokens, the batcher)."""
    batcher = make_batcher(engine, pipeline, **cfg)
    batcher.start()
    try:
        long_row = asyncio.ensure_future(
            chunks_of(batcher, LONG, LONG_NEW, seed=99))
        while batcher.timing["ticks"] < 2:
            await asyncio.sleep(0.002)
        outs = []
        for wave in waves:
            outs.append([tokens(c) for c in await asyncio.gather(*(
                chunks_of(batcher, p, n, seed=i)
                for i, (p, n) in enumerate(wave)))])
        long_out = tokens(await long_row)
        if batcher._paged:
            batcher.pages.check_invariants()
    finally:
        await batcher.stop()
    return long_out, outs, batcher


FAMILIES = {
    # family the wave must run -> (waves of (prompt, max_new), config)
    "single": ([[([9, 8, 7], 5)], [([4, 4], 3)]], {}),
    "full": ([[([9, 9, i], 4 + i) for i in range(3)]], {}),
    "chunked": (
        [[(list(range(3, 83)), 5), (list(range(40, 73)), 4)]],
        {"prefill_chunk": 32},
    ),
    "paged_pfx": (
        [[(PREAMBLE + [70], 4)],
         [(PREAMBLE + [71 + i], 5) for i in range(2)]],
        {"paged_kv": "on", "paged_kv_page_size": 16},
    ),
}


@pytest.mark.parametrize("family", list(FAMILIES))
async def test_a_pipelined_batcher_emits_the_synchronous_orders_tokens(
    engine, family
):
    waves, cfg = FAMILIES[family]
    want_long, want, sync = await run_waves(engine, waves, "off", **cfg)
    got_long, got, piped = await run_waves(engine, waves, "on", **cfg)
    assert got == want and got_long == want_long
    assert len(want_long) == LONG_NEW
    for batcher in (sync, piped):
        fams = "+".join(
            a.family for a in batcher.recorder.admission_snapshot())
        assert family in fams
    # The reference never defers; the pipelined loop deferred the
    # rounds it could (every round here precedes a tick).
    assert sync.counter_stats()["admit_rounds_deferred"] == 0
    stats = piped.counter_stats()
    assert stats["admit_rounds_deferred"] >= len(waves)
    assert piped._seated is None and not piped._cache_at_risk


async def test_a_state_family_restores_a_snapshot_in_a_deferred_round(
    jamba_engine
):
    """Three sessions on one system prompt at once, beside the long
    row: the first admission is cold and captures, the other two
    restore its snapshot in the same round, in device order, none of it read by the host before the
    tick is queued; every second turn restores its own."""
    rng = np.random.RandomState(3)
    vocab = jamba.CONFIGS["tiny-jamba"].vocab_size

    def ids(n):
        return [int(t) for t in rng.randint(3, vocab, n)]

    system = ids(64)
    firsts = [(system + ids(7 + s), 5) for s in range(3)]
    cfg = {"paged_kv": "on", "paged_kv_page_size": 16, "prefill_chunk": 32}
    _, [outs1], _ = await run_waves(jamba_engine, [firsts], "off", **cfg)
    seconds = [(p + o + ids(9), 5) for (p, _), o in zip(firsts, outs1)]
    want = await run_waves(jamba_engine, [firsts, seconds], "off", **cfg)
    got = await run_waves(jamba_engine, [firsts, seconds], "on", **cfg)
    assert got[:2] == want[:2]
    ref, piped = want[2].counter_stats(), got[2].counter_stats()
    for name in ("state_snapshot_hits", "state_snapshots_taken",
                 "state_tokens_recomputed", "prefill_tokens_reused"):
        assert piped[name] == ref[name], name
    assert piped["state_snapshot_hits"] >= 2 + 3  # first turns, seconds
    assert piped["admit_rounds_deferred"] >= 2
    assert ref["admit_rounds_deferred"] == 0


@pytest.mark.parametrize("ending", ["eos", "max_new_1"])
async def test_a_row_that_ends_at_its_first_token_in_a_deferred_round(
    engine, ending
):
    """The tick queued behind the round was dispatched with the row
    live: the row is the junk row a finish inside a pipelined tick
    already is, its slot is admitted into again, and its neighbours
    read what they always read."""
    probe = [9, 8, 7]
    want_long, [[first, neighbour], [again]], _ = await run_waves(
        engine, [[(probe, 6), ([4, 4, 2], 6)], [(probe, 6)]], "off")
    assert first == again
    batcher = make_batcher(engine, "on", max_batch_size=3)
    if ending == "eos":
        batcher.eos_id = first[0]
    batcher.start()
    try:
        long_row = asyncio.ensure_future(
            chunks_of(batcher, LONG, LONG_NEW, seed=99))
        while batcher.timing["ticks"] < 2:
            await asyncio.sleep(0.002)
        ended, other = await asyncio.gather(
            chunks_of(batcher, probe, 1 if ending == "max_new_1" else 6),
            chunks_of(batcher, [4, 4, 2], 6, seed=1))
        deferred = batcher.counter_stats()["admit_rounds_deferred"]
        # Both slots are free again and the next round takes one.
        later = await chunks_of(batcher, [4, 4, 2], 6, seed=1)
        got_long = tokens(await long_row)
    finally:
        await batcher.stop()
    if ending == "eos":
        assert tokens(ended) == [] and terminals(ended) == ["stop"]
    else:
        assert tokens(ended) == first[:1] and terminals(ended) == ["length"]
    if ending == "max_new_1" or batcher.eos_id not in neighbour:
        assert tokens(other) == tokens(later) == neighbour
    if ending == "max_new_1":
        assert got_long == want_long
    assert deferred >= 1
    assert batcher.counter_stats()["admit_rounds_deferred"] > deferred
    assert not any(s.active for s in batcher.slots)


async def test_a_round_with_a_grammar_row_keeps_the_synchronous_order(engine):
    schema = {"type": "object", "properties": {"ok": {"type": "boolean"}},
              "required": ["ok"], "additionalProperties": False}
    g = compile_schema(
        schema, vocab_size=llama.CONFIGS["tiny-mistral"].vocab_size)

    async def run(pipeline):
        batcher = make_batcher(engine, pipeline)
        batcher.start()
        try:
            long_row = asyncio.ensure_future(
                chunks_of(batcher, LONG, LONG_NEW, seed=99))
            while batcher.timing["ticks"] < 2:
                await asyncio.sleep(0.002)
            before = batcher.counter_stats()
            constrained, free = await asyncio.gather(
                chunks_of(batcher, [3, 1, 4, 1], 64, grammar=g),
                chunks_of(batcher, [9, 8, 7], 5, seed=1))
            after = batcher.counter_stats()
            await long_row
        finally:
            await batcher.stop()
        return tokens(constrained), tokens(free), before, after

    want_c, want_f, _, _ = await run("off")
    got_c, got_f, before, after = await run("on")
    assert (got_c, got_f) == (want_c, want_f) and want_c
    # The round that held the grammar row ran, and was not deferred.
    assert after["admit_rounds"] == before["admit_rounds"] + 1
    assert after["admit_rounds_deferred"] == before["admit_rounds_deferred"]
    assert after["grammar_masked_tokens"] > before["grammar_masked_tokens"]


async def test_a_second_round_of_one_admit_call_settles_the_first(engine):
    """A request that arrives while a round is in the executor is
    admitted by the same `_admit` call, in a second round: the first
    round is settled before it (an executor call of its own, kind
    `settle`), so its `pages.admit` sees what the first round indexed
    (here: the second prompt reuses the first one's pages, as it does
    on the synchronous loop), and only the second round is deferred."""
    import time

    first, second = (PREAMBLE + [70], 5), (PREAMBLE + [71], 5)
    cfg = {"paged_kv": "on", "paged_kv_page_size": 16}
    _, [[want_first], [want_second]], sync = await run_waves(
        engine, [[first], [second]], "off", **cfg)
    # `_admit` starts a further round only inside max_queue_delay_ms of
    # its own start: long enough here to outlast the first round.
    batcher = make_batcher(engine, "on", max_queue_delay_ms=2000.0, **cfg)
    loop = asyncio.get_running_loop()
    in_round = asyncio.Event()
    round_ = batcher._prefill_into_slots

    def slow_round(slots_idx, batch):
        if batch[0].prompt == first[0]:
            loop.call_soon_threadsafe(in_round.set)
            time.sleep(0.05)  # the second request arrives meanwhile
        return round_(slots_idx, batch)

    slow_round.__name__ = round_.__name__  # the hand-off record's kind
    batcher._prefill_into_slots = slow_round
    batcher.start()
    try:
        long_row = asyncio.ensure_future(
            chunks_of(batcher, LONG, LONG_NEW, seed=99))
        while batcher.timing["ticks"] < 2:
            await asyncio.sleep(0.002)
        got_first = asyncio.ensure_future(chunks_of(batcher, *first))
        await in_round.wait()
        got_second = await chunks_of(batcher, *second)
        got_first = await got_first
        await long_row
        batcher.pages.check_invariants()
    finally:
        await batcher.stop()
    assert (tokens(got_first), tokens(got_second)) == (want_first, want_second)
    _, one, two = batcher.recorder.admission_snapshot()
    assert (one.deferred, two.deferred) == (False, True)
    assert one.tick_seq == two.tick_seq  # both precede the same tick
    assert one.dispatch_ms == 0.0 and two.family == "paged_pfx"
    stats = batcher.counter_stats()
    assert stats["admit_rounds"] == 3 and stats["admit_rounds_deferred"] == 2
    assert stats["prefill_tokens_reused"] == (
        sync.counter_stats()["prefill_tokens_reused"]) > 0
    kinds = [h.kind for h in batcher.recorder.handoff_snapshot()]
    assert kinds.count("settle") == 1
    assert kinds[kinds.index("settle") - 1] == kinds[
        kinds.index("settle") + 1] == "admit"


def _no_row_is_seated_without_a_token(batcher) -> bool:
    return batcher._seated is None and not any(
        s.active and s.request is not None and not s.request.t_admit
        for s in batcher.slots)


@pytest.mark.parametrize("fault", ["tick_fail", "admit_fail", "program"])
async def test_a_fault_with_rows_seated_and_not_settled(engine, fault):
    """tick_fail: the tick step after a deferred round fails before its
    dispatch, with the round's rows seated. admit_fail: the round after
    a deferred one fails at its start. program: the second program of a
    round fails at its launch, with the first program's row seated.
    Every request gets exactly one terminal chunk, a replayed row reads
    what it would have read, and nothing stays seated."""
    wave = [([9, 8, 7], 6), ([4, 4, 2], 6)]
    _, [want], _ = await run_waves(engine, [wave], "off")
    batcher = make_batcher(engine, "on")
    seen: list = []  # rows seated when the fault was armed / struck

    if fault == "tick_fail":
        round_ = batcher._prefill_into_slots

        def seat_then_arm(slots_idx, batch):
            round_(slots_idx, batch)
            if batcher._seated is not None and len(batch) == 2:
                seen.append(len(batcher._seated.programs))
                failpoints.registry.arm("tick_fail", every=1, times=1)

        batcher._prefill_into_slots = seat_then_arm
    elif fault == "program":
        program = batcher._admission_program
        calls: list = []

        def second_launch_fails(launch, *args, **kw):
            calls.append(batcher._round)
            if len(calls) >= 3 and calls[-1] is calls[-2] and not seen:
                seen.append(len(batcher._round.programs))
                raise RuntimeError("injected: the launch failed")
            return program(launch, *args, **kw)

        batcher._admission_program = second_launch_fails
    recover = batcher._recover_after_tick_failure
    recovered: list = []

    def recover_and_look():
        recover()
        recovered.append(_no_row_is_seated_without_a_token(batcher))

    batcher._recover_after_tick_failure = recover_and_look
    batcher.start()
    try:
        long_row = asyncio.ensure_future(
            chunks_of(batcher, LONG, LONG_NEW, seed=99))
        while batcher.timing["ticks"] < 2:
            await asyncio.sleep(0.002)
        if fault == "admit_fail":
            # The round after the wave's: armed once the wave is seated
            # or further, struck when the next request is admitted.
            first = await asyncio.gather(*(
                chunks_of(batcher, p, n, seed=i)
                for i, (p, n) in enumerate(wave)))
            failpoints.registry.arm("admit_fail", every=1, times=1)
            failed = await chunks_of(batcher, [1, 2, 3], 4)
            assert terminals(failed) == ["error"] and not tokens(failed)
            seen.append(0)
        else:
            first = await asyncio.gather(*(
                chunks_of(batcher, p, n, seed=i)
                for i, (p, n) in enumerate(wave)))
        later = await chunks_of(batcher, *wave[0])
        long_chunks = await long_row
    finally:
        await batcher.stop()
    assert seen, "the fault never struck"
    for chunks in [*first, later, long_chunks]:
        assert len(terminals(chunks)) == 1
    if fault == "program":
        # The launch that failed took its row with it; the row seated
        # before it was replayed from its prompt.
        assert seen == [1] and terminals(first[1]) == ["error"]
        assert tokens(first[0]) == want[0]
    else:
        assert [tokens(c) for c in first] == want
    assert tokens(later) == want[0]
    assert len(tokens(long_chunks)) == LONG_NEW
    if fault == "tick_fail":
        assert seen[0] >= 1 and recovered and all(recovered)
        assert batcher.counter_stats()["replayed_requests"] >= 3
    assert _no_row_is_seated_without_a_token(batcher)
    assert not batcher._cache_at_risk


async def test_the_marks_close_and_device_leaves_the_dispatch_out(engine):
    _, _, batcher = await run_waves(
        engine, [[([9, 8, 7], 5)], [([4, 4], 3), ([4, 5], 3)]], "on")
    stats = batcher.stats()
    records = batcher.recorder.admission_snapshot()
    # Greedy, pipelined, every round followed by a tick (the long row's
    # own round, into an idle pool, among them): each was settled after
    # that tick's dispatch.
    assert stats["admit_rounds"] == len(records) == 3
    assert stats["admit_rounds_deferred"] == sum(
        r.deferred for r in records) == 3
    ticks = {t.seq: t for t in batcher.recorder.tick_snapshot()}
    for r in records:
        assert r.host_ms + r.tick_wait_ms + r.device_ms + r.dispatch_ms == (
            pytest.approx(r.duration_ms, abs=1e-6))
        assert r.programs >= 1 and r.host_ms > 0
        assert (r.dispatch_ms > 0) == r.deferred
        if r.deferred:
            # The tick the round precedes was dispatched inside the
            # round's `dispatch` stretch: its sync and dispatch phases
            # fit in it, and none of it is `device`.
            tick = ticks[r.tick_seq]
            assert r.dispatch_ms >= (
                tick.phase_sync_ms + tick.phase_dispatch_ms)
    # The last round's own timer: contiguous marks, the gap named.
    timer = batcher._round.timer
    order = [phase for phase, _ in timer.marks]
    assert set(order) <= {*ADMIT_HOST_MARKS, "tick_wait", "device", "dispatch"}
    assert sum(timer.acc.values()) == pytest.approx(
        (timer.last - timer.t0) * 1000.0, abs=1e-9)
    # Two one-row programs: the second was launched when the first had
    # left the device (one `device` wait inside the round), the tick was
    # dispatched behind it, and only then was its first token waited
    # for: no `device` between the last launch and the dispatch.
    assert order.count("device") == order.count("launch") == 2
    gap = order.index("dispatch")
    last_launch = len(order) - 1 - order[::-1].index("launch")
    assert last_launch < gap and "device" not in order[last_launch:gap]
    assert order[gap + 1:] == ["device", "activate"]
    # One device observation a program call, each a program's own time:
    # never less than the host's wait for it.
    assert stats["admit_device_ms_count"] == sum(r.programs for r in records)
    assert stats["admit_device_ms_sum"] >= sum(
        r.device_ms for r in records) - 1e-6


def test_the_counter_and_the_flag_reach_the_proto_and_the_metrics():
    from ggrmcp_tpu.gateway import metrics
    from ggrmcp_tpu.rpc.pb import serving_pb2

    stats = serving_pb2.ServingStatsResponse.DESCRIPTOR.fields_by_name
    assert stats["admit_rounds_deferred"].number == 187
    assert "dispatched" in metrics._SERVING_HELP["admit_rounds_deferred"]
    record = serving_pb2.AdmissionRecord.DESCRIPTOR.fields_by_name
    assert (record["deferred"].number, record["dispatch_ms"].number) == (16, 17)
    wire = serving_pb2.AdmissionRecord(
        deferred=True, dispatch_ms=1.5).SerializeToString()
    back = serving_pb2.AdmissionRecord.FromString(wire)
    assert back.deferred is True and back.dispatch_ms == 1.5
