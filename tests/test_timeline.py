"""Tick-phase attribution + unified Perfetto timeline (ISSUE 9, marker
`obs`).

The load-bearing guarantees:

  * Phase accounting CLOSES — for every collected tick, admit + sync +
    dispatch + wait + host equals the record's duration_ms within a
    small epsilon, across fused/chunked/interleaved/paged
    dispatch paths (no unattributed time). This is what makes "this
    tick lost 3.1 ms to host-side table sync" a trustworthy statement
    before the TPU window spends minutes capturing it.
  * /debug/timeline emits valid Chrome trace-event JSON (Perfetto-
    loadable): ph/ts/dur/pid/tid well-formed, events time-ordered per
    track, spans + ticks + request lifecycles present, and lifecycle
    instants surface an injected failpoint from a chaos run.
  * /debug/ticks and /debug/requests take source=/trace_id=/n= filters
    identically on BOTH HTTP impls, and one inbound trace id agrees
    across /debug/traces, /debug/requests, and a tick's trace_ids.
  * logging.format=json emits parseable one-line JSON records carrying
    the contextvar trace id, joining process logs to the timeline.
"""

import asyncio
import io
import json
import logging

import pytest

from ggrmcp_tpu.core.config import (
    BatchingConfig,
    Config,
    MeshConfig,
    ServingConfig,
)
from ggrmcp_tpu.models import llama
from ggrmcp_tpu.ops.sampling import SamplingConfig
from ggrmcp_tpu.serving.batching import ContinuousBatcher
from ggrmcp_tpu.serving.engine import GenerationEngine
from ggrmcp_tpu.serving.flight_recorder import PHASE_NAMES, PhaseTimer
from ggrmcp_tpu.serving.timeline import build_timeline
from ggrmcp_tpu.utils import failpoints, tracing

pytestmark = pytest.mark.obs

GREEDY = SamplingConfig(temperature=0.0)


def _mesh():
    return MeshConfig(tensor=2, data=0)


@pytest.fixture(scope="module")
def engine():
    return GenerationEngine(
        llama.CONFIGS["tiny-llama"],
        ServingConfig(mesh=_mesh()),
    )


def _batcher(engine, **cfg_kw) -> ContinuousBatcher:
    cfg_kw.setdefault("max_batch_size", 4)
    cfg_kw.setdefault("kv_cache_max_seq", 256)
    cfg_kw.setdefault("max_queue_delay_ms", 2.0)
    return ContinuousBatcher(engine, BatchingConfig(**cfg_kw))


async def _consume(batcher, prompt, max_new, seed=0):
    out = []
    async for ids, _reason in batcher.submit(
        list(prompt), max_new, GREEDY, seed=seed
    ):
        out.extend(ids)
    return out


async def _drive(engine, prompts, max_new=6, **cfg_kw):
    """Run `prompts` through a fresh batcher and return it (stopped;
    recorder rings intact)."""
    batcher = _batcher(engine, **cfg_kw)
    loop = asyncio.get_running_loop()
    await loop.run_in_executor(None, batcher.warmup)
    batcher.start()
    try:
        await asyncio.gather(*(
            _consume(batcher, p, max_new, seed=i)
            for i, p in enumerate(prompts)
        ))
    finally:
        await batcher.stop()
    return batcher


def _phase_sum(rec) -> float:
    return (
        rec.phase_admit_ms + rec.phase_sync_ms + rec.phase_dispatch_ms
        + rec.phase_wait_ms + rec.phase_host_ms
    )


def _assert_closure(batcher):
    """Collected ticks (duration stamped at collect) must attribute
    every millisecond: phase sum == duration_ms within epsilon."""
    ticks = [
        t for t in batcher.recorder.tick_snapshot() if t.duration_ms > 0
    ]
    assert ticks, "no collected tick records"
    for t in ticks:
        assert _phase_sum(t) == pytest.approx(t.duration_ms, abs=0.05), (
            f"tick {t.seq}: phases {_phase_sum(t):.3f} != "
            f"duration {t.duration_ms:.3f}"
        )
        # wait (device compute + transfer) is never literally zero.
        assert t.phase_wait_ms > 0
    # The cumulative ServingStats scalars agree with the records.
    total = sum(batcher.phase_ms.values())
    assert total == pytest.approx(
        sum(t.duration_ms for t in ticks), abs=0.05 * len(ticks) + 0.1
    )
    stats = batcher.counter_stats()
    for phase in PHASE_NAMES:
        assert f"tick_phase_{phase}_ms" in stats
    return ticks


class TestPhaseTimer:
    def test_contiguous_marks_partition_the_interval(self):
        timer = PhaseTimer()
        timer.mark("a")
        timer.mark("b")
        timer.mark("a")  # repeated marks accumulate
        total = (timer.last - timer.t0) * 1000.0
        assert sum(timer.acc.values()) == pytest.approx(total, abs=1e-9)
        assert set(timer.acc) == {"a", "b"}


class TestPhaseClosure:
    async def test_fused_path(self, engine):
        batcher = await _drive(engine, [[5, 6, 7], [9, 10, 11, 12]])
        _assert_closure(batcher)

    async def test_chunked_path(self, engine):
        batcher = await _drive(
            engine, [list(range(3, 83)), list(range(4, 74))],
            prefill_chunk=32,
        )
        _assert_closure(batcher)

    async def test_interleaved_path(self, engine):
        batcher = _batcher(
            engine, prefill_chunk=32, prefill_interleave="on",
            prefill_interleave_rows=2,
        )
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(None, batcher.warmup)
        batcher.start()
        try:
            # A long prompt must land while a slot is decoding to take
            # the fused tick+chunk dispatch (_tick_dispatch_chunk).
            short = asyncio.ensure_future(
                _consume(batcher, [5, 6, 7], 48)
            )
            await asyncio.sleep(0.15)
            await _consume(batcher, list(range(3, 120)), 4, seed=1)
            await short
        finally:
            await batcher.stop()
        ticks = _assert_closure(batcher)
        assert any(t.interleaved_rows > 0 for t in ticks), (
            "interleaved dispatch path was not exercised"
        )

    async def test_paged_path(self, engine):
        preamble = list(range(3, 67))
        batcher = await _drive(
            engine,
            [preamble + [70 + i] for i in range(3)],
            paged_kv="on", paged_kv_page_size=16,
        )
        _assert_closure(batcher)

    async def test_disabled_recorder_attributes_nothing(self, engine):
        from ggrmcp_tpu.core.config import ObservabilityConfig

        eng = GenerationEngine(
            llama.CONFIGS["tiny-llama"],
            ServingConfig(
                mesh=_mesh(),
                observability=ObservabilityConfig(enabled=False),
            ),
        )
        batcher = await _drive(eng, [[5, 6, 7]])
        assert batcher.recorder.tick_snapshot() == []
        assert all(v == 0.0 for v in batcher.phase_ms.values())
        stats = batcher.counter_stats()
        assert stats["tick_phase_wait_ms"] == 0.0


# ---------------------------------------------------------------------------
# The time partition inside the program (ISSUE 26): queue time split at
# the pop, admission rounds as spans, the loop's turn with nothing left
# over, phases as intervals, annotations only while a capture runs.
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def mistral_engine():
    return GenerationEngine(
        llama.CONFIGS["tiny-mistral"],
        ServingConfig(mesh=MeshConfig(tensor=1, data=0)),
    )


def _assert_queue_split(batcher):
    records = batcher.recorder.request_snapshot()
    assert records
    for r in records:
        assert r.pending_ms >= 0.0 and r.prefill_ms >= 0.0
        assert r.pending_ms + r.prefill_ms == pytest.approx(
            r.queue_ms, abs=1e-9
        )
    # The histograms are observed together, so the means add up too.
    h = batcher.recorder.histogram_stats()
    assert h["pending_ms_count"] == h["prefill_ms_count"] == h["queue_ms_count"]
    assert h["pending_ms_sum"] + h["prefill_ms_sum"] == pytest.approx(
        h["queue_ms_sum"], abs=1e-6
    )
    return records


class TestQueueSplit:
    async def test_trickle_and_burst(self, mistral_engine):
        batcher = await _drive(
            mistral_engine, [[5, 6, 7], [9, 10, 11, 12], [3, 4], [8, 8, 8]]
        )
        records = _assert_queue_split(batcher)
        # Admitted requests were popped before they were activated.
        assert all(r.prefill_ms > 0 for r in records if r.first_tick >= 0)

    async def test_requeued_by_the_prefill_budget(self, mistral_engine):
        """A request the Sarathi budget pushed back to the queue head
        is stamped at its LAST pop: the time it waited after the
        deferral is pending, not prefill."""
        from tests.test_scheduler import sched_engine

        batcher = ContinuousBatcher(
            sched_engine(mistral_engine, prefill_budget_tokens=16),
            BatchingConfig(max_batch_size=4, kv_cache_max_seq=256),
        )
        batcher.start()
        try:
            runner = asyncio.ensure_future(
                _consume(batcher, list(range(3, 11)), 16)
            )
            while not batcher._active_count():
                await asyncio.sleep(0.005)
            await asyncio.gather(*(
                _consume(batcher, list(range(20 + i, 32 + i)), 4, seed=i)
                for i in range(3)
            ))
            await runner
        finally:
            await batcher.stop()
        assert batcher.counter_stats()["sched_budget_deferrals"] >= 1
        _assert_queue_split(batcher)

    async def test_replayed_after_a_tick_failure(self, mistral_engine):
        """A replay resets t_submit and pops again: both halves are
        measured on the fresh queue clock and still add up."""
        failpoints.registry.arm("tick_fail", every=3, times=2)
        try:
            batcher = await _drive(
                mistral_engine, [[3, 1, 4, 1], [2, 7, 1]], max_new=8,
                tick_retry_limit=8,
            )
        finally:
            failpoints.registry.disarm()
        assert batcher.replayed > 0, "no fault was actually injected"
        _assert_queue_split(batcher)

    def test_a_record_without_a_pop_puts_all_of_queue_in_prefill(self):
        """Unit: a record that carries no pop stamp, and clamped
        replays, keep the sum."""
        from ggrmcp_tpu.serving.flight_recorder import FlightRecorder

        rec = FlightRecorder()
        rec.record_request("a", 10.0, 10.5, 10.6, 4, 2, "stop", 1, 1)
        rec.record_request(
            "b", 10.0, 10.5, 10.6, 4, 2, "stop", 1, 1, t_pop=10.2
        )
        # t_pop before a reset t_submit, and after t_admit: clamped.
        rec.record_request(
            "c", 10.0, 10.5, 10.6, 4, 2, "stop", 1, 1, t_pop=9.0
        )
        rec.record_request(
            "d", 10.0, 10.5, 10.6, 4, 2, "stop", 1, 1, t_pop=11.0
        )
        a, b, c, d = rec.request_snapshot()
        assert (a.pending_ms, a.prefill_ms) == (0.0, pytest.approx(500.0))
        assert b.pending_ms == pytest.approx(200.0)
        assert b.prefill_ms == pytest.approx(300.0)
        assert (c.pending_ms, c.prefill_ms) == (0.0, pytest.approx(500.0))
        assert (d.pending_ms, d.prefill_ms) == (pytest.approx(500.0), 0.0)


class TestLoopPartition:
    PARTS = ("exec_wait", "work", "lag", "host")

    async def test_parts_sum_to_busy_and_stand_still_while_parked(
        self, mistral_engine
    ):
        batcher = _batcher(mistral_engine)
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(None, batcher.warmup)
        batcher.start()
        try:
            await asyncio.gather(
                _consume(batcher, [5, 6, 7], 12),
                _consume(batcher, [9, 10, 11, 12], 8, seed=1),
            )
            await asyncio.sleep(0.1)  # the loop drains and parks
            parked0 = batcher.counter_stats()
            await asyncio.sleep(0.3)
            parked1 = batcher.counter_stats()
        finally:
            await batcher.stop()
        parts = [f"loop_{p}_ms_sum" for p in self.PARTS]
        assert all(parked1[k] > 0 for k in parts)
        assert sum(parked1[k] for k in parts) == pytest.approx(
            parked1["loop_busy_ms_sum"], abs=1e-9
        )
        # Parked on _wake.wait(): 300 ms passed and no part grew.
        for k in (*parts, "loop_busy_ms_sum", "loop_lag_ms_count"):
            assert parked1[k] == parked0[k], k
        assert (
            parked1["loop_exec_wait_ms_count"]
            == parked1["loop_lag_ms_count"]
            == len(batcher.recorder.handoff_snapshot())
        )
        # work is what the tick phases divide: every tick and every
        # admission ran inside some executor call.
        inside = sum(
            batcher.phase_ms[p] for p in ("admit", "sync", "dispatch", "host")
        )
        assert parked1["loop_work_ms_sum"] >= inside

    async def test_handoff_records_partition_the_turn(self, mistral_engine):
        batcher = await _drive(
            mistral_engine, [[5, 6, 7], [9, 10, 11, 12]], max_new=10
        )
        records = batcher.recorder.handoff_snapshot()
        assert {r.kind for r in records} >= {"admit", "tick"}
        stats = batcher.counter_stats()
        for part in self.PARTS:
            assert sum(
                getattr(r, f"{part}_ms") for r in records
            ) == pytest.approx(stats[f"loop_{part}_ms_sum"], abs=0.5), part
        for prev, rec in zip(records, records[1:]):
            assert min(
                rec.host_ms, rec.exec_wait_ms, rec.work_ms, rec.lag_ms
            ) >= 0.0
            # Contiguous: this call's host part starts where the
            # previous call's lag ended, unless the loop parked between.
            prev_end = prev.t_mono + (
                prev.exec_wait_ms + prev.work_ms + prev.lag_ms
            ) / 1000.0
            assert rec.t_mono - rec.host_ms / 1000.0 >= prev_end - 1e-6
        ticks = {t.seq for t in batcher.recorder.tick_snapshot()}
        assert {r.tick_seq for r in records if r.kind == "tick"} <= ticks


class TestAdmissionRecords:
    PREAMBLE = list(range(3, 67))

    async def _run(self, engine, waves, **cfg_kw):
        """Submit `waves` (lists of prompts) one after the other."""
        batcher = _batcher(engine, **cfg_kw)
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(None, batcher.warmup)
        batcher.start()
        try:
            for wave in waves:
                await asyncio.gather(*(
                    _consume(batcher, p, 4, seed=i)
                    for i, p in enumerate(wave)
                ))
        finally:
            await batcher.stop()
        records = batcher.recorder.admission_snapshot()
        # One record per admission round, numbered like the counter.
        assert len(records) == batcher.timing["admit_rounds"]
        assert [r.seq for r in records] == list(range(1, len(records) + 1))
        ticks = {t.seq: t for t in batcher.recorder.tick_snapshot()}
        for r in records:
            assert r.duration_ms > 0 and r.rows >= 1
            # Its cause link: the tick it precedes carries its time.
            assert ticks[r.tick_seq].phase_admit_ms >= r.duration_ms - 1e-6
        assert sum(r.duration_ms for r in records) == pytest.approx(
            batcher.phase_ms["admit"] + batcher._admit_phase_ms, abs=0.05
        )
        return batcher, records

    @pytest.mark.parametrize("case, family", [
        ("trickle", "single"),
        ("burst", "full"),
        ("page_reuse", "paged_pfx"),
        ("long_prompt", "chunked"),
    ])
    async def test_the_family_that_ran(self, mistral_engine, case, family):
        waves, cfg = {
            "trickle": ([[[5, 6, 7]]], {}),
            "burst": ([[[9, 9, i] for i in range(4)]], {}),
            "page_reuse": (
                [[self.PREAMBLE + [70]],
                 [self.PREAMBLE + [71 + i] for i in range(2)]],
                {"paged_kv": "on", "paged_kv_page_size": 16},
            ),
            "long_prompt": ([[list(range(3, 83))]], {"prefill_chunk": 32}),
        }[case]
        _, records = await self._run(mistral_engine, waves, **cfg)
        last = records[-1]
        assert family in last.family.split("+"), [r.family for r in records]
        assert last.prompt_tokens == sum(len(p) for p in waves[-1][-last.rows:])
        if case == "page_reuse":
            # Four whole pages of the preamble a row came from the index.
            assert last.reused_tokens >= 64
        else:
            assert last.reused_tokens == 0

    async def test_interleave_queued_rows_run_no_program(self, engine):
        batcher = _batcher(
            engine, prefill_chunk=32, prefill_interleave="on",
            prefill_interleave_rows=2,
        )
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(None, batcher.warmup)
        batcher.start()
        try:
            short = asyncio.ensure_future(_consume(batcher, [5, 6, 7], 48))
            await asyncio.sleep(0.15)
            await _consume(batcher, list(range(3, 120)), 4, seed=1)
            await short
        finally:
            await batcher.stop()
        families = [
            r.family for r in batcher.recorder.admission_snapshot()
        ]
        assert families == ["single", "interleave_queued"]

    async def test_trace_ids_join_the_request_records(self, mistral_engine):
        batcher = _batcher(mistral_engine)
        batcher.start()
        try:
            async for _ in batcher.submit(
                [5, 6, 7], 4, GREEDY, seed=0, trace_id="trace-adm",
            ):
                pass
        finally:
            await batcher.stop()
        [adm] = batcher.recorder.admission_snapshot()
        assert adm.trace_ids == ["trace-adm"]
        rec = batcher.recorder.request_record("trace-adm")
        assert rec.first_tick == adm.tick_seq
        # The round is most of the request's prefill half.
        assert adm.duration_ms <= rec.prefill_ms + 1e-6
        admissions, handoffs = batcher.loop_snapshot(trace_id="trace-adm")
        assert admissions == [adm] and handoffs == []


class TestPhaseIntervals:
    @pytest.mark.parametrize("pipeline", ["off", "on"])
    async def test_marks_are_contiguous_and_inside_their_tick(
        self, mistral_engine, pipeline
    ):
        batcher = await _drive(
            mistral_engine, [[5, 6, 7], [9, 10, 11, 12]], max_new=12,
            pipeline_ticks=pipeline,
        )
        ticks = _assert_closure(batcher)
        for t in ticks:
            names = [p for p, _ in t.marks]
            starts = [ms for _, ms in t.marks]
            assert names[0] == "sync" and names[-1] == "host"
            assert set(names) <= set(PHASE_NAMES) - {"admit"}
            # Contiguous from the record's stamp: the first interval
            # opens at t_mono, each next one where the last closed.
            assert starts[0] == 0.0
            assert starts == sorted(starts)
            own = t.duration_ms - t.phase_admit_ms
            assert starts[-1] <= own
            by_phase = dict.fromkeys(names, 0.0)
            for (phase, start), end in zip(t.marks, [*starts[1:], own]):
                by_phase[phase] += end - start
            for phase, ms in by_phase.items():
                assert ms == pytest.approx(
                    getattr(t, f"phase_{phase}_ms"), abs=1e-6
                )

    def test_timer_keeps_where_each_segment_started(self):
        timer = PhaseTimer()
        a = timer.mark("a")
        b = timer.mark("b")
        assert [p for p, _ in timer.marks] == ["a", "b"]
        assert timer.marks[0][1] == timer.t0
        assert timer.marks[1][1] == pytest.approx(timer.t0 + a / 1000.0)
        assert timer.last == pytest.approx(timer.t0 + (a + b) / 1000.0)


class TestTraceAnnotation:
    async def _names_entered(self, engine, monkeypatch) -> list:
        import jax

        entered: list = []

        class Spy:
            def __init__(self, name, **stats):
                self.name, self.stats = name, stats

            def __enter__(self):
                entered.append((self.name, self.stats))

            def __exit__(self, *exc):
                return False

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Spy)
        await _drive(engine, [[5, 6, 7]], max_new=6)
        return entered

    async def test_no_annotation_without_a_capture(
        self, mistral_engine, monkeypatch
    ):
        assert tracing.capture_running is False
        assert tracing.annotation("x", seq=1) is tracing.annotation("y")
        assert await self._names_entered(mistral_engine, monkeypatch) == []

    async def test_work_items_are_annotated_while_a_capture_runs(
        self, mistral_engine, monkeypatch
    ):
        monkeypatch.setattr(tracing, "capture_running", True)
        entered = await self._names_entered(mistral_engine, monkeypatch)
        assert {name for name, _ in entered} == {
            "ggrmcp.admit", "ggrmcp.tick.dispatch", "ggrmcp.tick.collect",
            # The round's children (tests/test_admission_marks.py pins
            # their attributes and nesting).
            "ggrmcp.admit.program", "ggrmcp.admit.device",
            "ggrmcp.admit.activate", "ggrmcp.admit.settle",
        }
        # Dispatch and collect of one tick carry the same seq, the key
        # into the tick ring.
        dispatched = [
            s["seq"] for n, s in entered if n == "ggrmcp.tick.dispatch"
        ]
        collected = [
            s["seq"] for n, s in entered if n == "ggrmcp.tick.collect"
        ]
        assert dispatched == collected == list(
            range(1, len(dispatched) + 1)
        )

    def test_capture_sets_the_flag_for_its_duration_only(self, monkeypatch):
        import jax

        seen: list = []
        monkeypatch.setattr(
            jax.profiler, "start_trace",
            lambda out, profiler_options: seen.append(
                ("start", tracing.capture_running)
                if profiler_options.python_tracer_level == 0 else "python traced"),
        )
        monkeypatch.setattr(
            jax.profiler, "stop_trace",
            lambda: seen.append(("stop", tracing.capture_running)),
        )
        monkeypatch.setattr(
            tracing.time, "sleep",
            lambda s: seen.append(("sleep", tracing.capture_running)),
        )
        tracing.profile_capture(5.0, "/nonexistent")
        assert seen == [("start", False), ("sleep", True), ("stop", False)]
        assert tracing.capture_running is False


# ---------------------------------------------------------------------------
# The unified timeline + debug filters (gateway + real sidecar e2e)
# ---------------------------------------------------------------------------


def _validate_chrome_trace(doc: dict) -> None:
    """Schema-check the trace-event document: well-formed events,
    time-ordered per (pid, tid) track, JSON-serializable."""
    events = doc["traceEvents"]
    assert isinstance(events, list) and events
    per_track: dict = {}
    for ev in events:
        assert ev["ph"] in {"X", "i", "M", "C"}, ev
        assert isinstance(ev["pid"], int)
        assert isinstance(ev["tid"], int)
        assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
        assert isinstance(ev.get("name"), str) and ev["name"]
        if ev["ph"] == "X":
            assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
        if ev["ph"] == "C":
            # Counter tracks (memory ledger / paged occupancy): every
            # series value must be numeric — Perfetto plots args as
            # stacked series.
            assert ev["args"], ev
            assert all(
                isinstance(v, (int, float)) for v in ev["args"].values()
            ), ev
        if ev["ph"] != "M":
            per_track.setdefault((ev["pid"], ev["tid"]), []).append(
                ev["ts"]
            )
    for stamps in per_track.values():
        assert stamps == sorted(stamps), "events not time-ordered per track"
    json.dumps(doc)


class TestTimelineEndpoint:
    async def test_timeline_spans_ticks_requests_and_chaos_instant(self):
        from tests.test_observability import _generate_call, observed_env

        tracing.tracer.clear()
        # Chaos: one injected tick failure → replay → a lifecycle
        # instant must surface on the timeline.
        failpoints.registry.arm("tick_fail", every=4, times=1)
        try:
            async with observed_env("fastlane") as (_side, _gw, client):
                await _generate_call(client, "trace-tl-a", max_new=8)
                await _generate_call(client, "trace-tl-b", max_new=8)
                resp = await client.get("/debug/timeline")
                assert resp.status == 200
                doc = await resp.json()
        finally:
            failpoints.registry.disarm()
        _validate_chrome_trace(doc)
        cats = {e.get("cat") for e in doc["traceEvents"]}
        assert {"span", "tick", "tick.phase", "request"} <= cats
        # Ledger counter tracks ride the same document: per-tick
        # bytes-per-component "C" events (docs/observability.md).
        counters = [e for e in doc["traceEvents"] if e["ph"] == "C"]
        assert any(
            e["name"].startswith("memory_bytes") and "weights" in e["args"]
            for e in counters
        ), "no memory-ledger counter track on the timeline"
        instants = [e for e in doc["traceEvents"] if e["ph"] == "i"]
        assert any(e["name"] == "replay" for e in instants), (
            "injected tick failure left no lifecycle instant"
        )
        # Request rows carry the tick-join keys.
        req = next(
            e for e in doc["traceEvents"] if e.get("cat") == "request"
        )
        assert req["args"]["firstTick"] >= 1
        assert req["args"]["lastTick"] >= req["args"]["firstTick"]
        # Tick slices nest their phase partition: the phase slices of a
        # tick sum to its duration.
        ticks = [
            e for e in doc["traceEvents"]
            if e.get("cat") == "tick" and e["dur"] > 0
        ]
        assert ticks
        phases = [
            e for e in doc["traceEvents"] if e.get("cat") == "tick.phase"
        ]
        t0 = ticks[0]
        nested = [
            p for p in phases
            if p["pid"] == t0["pid"] and p["tid"] == t0["tid"]
            and t0["ts"] <= p["ts"] <= t0["ts"] + t0["dur"]
        ]
        assert nested
        assert sum(p["dur"] for p in nested) <= t0["dur"] + len(nested)

    @pytest.mark.parametrize("impl", ["fastlane", "aiohttp"])
    async def test_timeline_served_on_both_impls(self, impl):
        from tests.test_observability import _generate_call, observed_env

        async with observed_env(impl) as (_side, _gw, client):
            await _generate_call(client, f"trace-tl-{impl}")
            doc = await (await client.get("/debug/timeline")).json()
        _validate_chrome_trace(doc)
        assert any(
            e.get("cat") == "tick" for e in doc["traceEvents"]
        )

    def test_phases_admissions_and_handoffs_are_drawn_where_they_were(self):
        """Hand-made protojson records: phase slices sit at their mark
        offsets inside the tick, admissions and the four hand-off parts
        on their own threads, two pipelined ticks on two lanes."""
        tick = {
            "seq": "7", "tWall": 100.0, "durationMs": 30.0,
            "phaseAdmitMs": 10.0, "phaseSyncMs": 1.0,
            "phaseDispatchMs": 2.0, "phaseWaitMs": 15.0, "phaseHostMs": 2.0,
            "phaseMarks": ["sync", "dispatch", "wait", "host"],
            "phaseMarkStartMs": [0.0, 1.0, 3.0, 18.0],
        }
        tick8 = dict(tick, seq="8", tWall=100.012)
        doc = build_timeline([], [{
            "target": "side:1", "enabled": True,
            "ticks": [tick, tick8],
            "admissions": [{
                "seq": "3", "tWall": 99.989, "durationMs": 10.0,
                "family": "paged_pfx", "rows": 2, "tickSeq": "7",
                "traceIds": ["t"],
            }],
            "handoffs": [{
                "seq": "9", "kind": "tick", "tWall": 99.9995,
                "hostMs": 0.2, "execWaitMs": 0.5, "workMs": 20.0,
                "lagMs": 1.5, "tickSeq": "7",
            }],
        }])
        _validate_chrome_trace(doc)
        events = doc["traceEvents"]
        [t7] = [e for e in events if e["name"] == "tick 7"]
        assert (t7["ts"], t7["dur"]) == (100_000_000, 20_000)
        phases = [
            e for e in events
            if e.get("cat") == "tick.phase" and e["tid"] == t7["tid"]
        ]
        assert [(e["name"], e["ts"] - t7["ts"], e["dur"]) for e in phases] == [
            ("sync", 0, 1_000), ("dispatch", 1_000, 2_000),
            ("wait", 3_000, 15_000), ("host", 18_000, 2_000),
        ]
        [t8] = [e for e in events if e["name"] == "tick 8"]
        assert t8["tid"] != t7["tid"]  # overlapping ticks: two lanes
        [adm] = [e for e in events if e.get("cat") == "admission"]
        assert adm["name"] == "admit paged_pfx"
        assert adm["ts"] + adm["dur"] <= t7["ts"]
        assert adm["args"]["tickSeq"] == "7"
        loop = [e for e in events if e.get("cat") == "loop"]
        assert [e["name"] for e in loop] == [
            "host (tick)", "exec_wait (tick)", "work (tick)", "lag (tick)",
        ]
        for a, b in zip(loop, loop[1:]):
            assert a["ts"] + a["dur"] == b["ts"]  # nothing left over
        assert loop[1]["ts"] == 99_999_500

    def test_build_timeline_tolerates_errors_and_empties(self):
        doc = build_timeline(
            [], [{"target": "dead:1", "error": "unavailable"}]
        )
        assert doc["skippedBackends"] == ["dead:1"]
        _validate_chrome_trace(doc)


class TestDebugFilterParity:
    TIERED = BatchingConfig(
        max_batch_size=4, kv_cache_max_seq=256,
        kv_tiers=[[128, 2], [256, 2]],
    )

    @pytest.mark.parametrize("impl", ["fastlane", "aiohttp"])
    async def test_source_trace_and_n_filters(self, impl):
        """source=/trace_id=/n= behave identically on both HTTP impls:
        the tiered sidecar's records carry tier sources, a matching
        filter returns only them, a non-ticking tier filters to empty,
        and n= bounds the window."""
        from tests.test_observability import _generate_call, observed_env

        trace_id = f"trace-filters-{impl}"
        async with observed_env(
            impl, batching=self.TIERED
        ) as (_side, _gw, client):
            await _generate_call(client, trace_id)

            body = await (await client.get(
                "/debug/ticks", params={"source": "tier-128"}
            )).json()
            ticks = body["backends"][0]["ticks"]
            assert ticks
            assert all(t.get("source") == "tier-128" for t in ticks)
            assert body["source"] == "tier-128"
            # The ticks body is self-describing: the proto-drift-
            # enforced field help table rides along.
            assert body["fields"]["phaseWaitMs"]
            assert body["fields"]["durationMs"]
            # Phase attribution is visible per record.
            assert float(ticks[-1]["phaseWaitMs"]) > 0

            empty = await (await client.get(
                "/debug/ticks", params={"source": "tier-256"}
            )).json()
            assert empty["backends"][0]["ticks"] == []

            one = await (await client.get(
                "/debug/ticks", params={"n": "1"}
            )).json()
            assert len(one["backends"][0]["ticks"]) == 1

            reqs = await (await client.get(
                "/debug/requests",
                params={"source": "tier-128", "trace_id": trace_id},
            )).json()
            [rec] = reqs["backends"][0]["requests"]
            assert rec["traceId"] == trace_id
            none = await (await client.get(
                "/debug/requests", params={"source": "tier-256"}
            )).json()
            assert none["backends"][0]["requests"] == []


class TestTracePropagation:
    async def test_one_trace_id_agrees_across_all_three_surfaces(self):
        """One tools/call with an inbound x-trace-id surfaces the SAME
        id in the span ring (/debug/traces), the request ring
        (/debug/requests), and at least one tick record's trace_ids —
        the three diagnostic surfaces cannot silently disagree."""
        from tests.test_observability import _generate_call, observed_env

        tracing.tracer.clear()
        trace_id = "trace-propagation-e2e"
        async with observed_env("fastlane") as (_side, _gw, client):
            await _generate_call(client, trace_id)

            spans = (await (
                await client.get("/debug/traces")
            ).json())["spans"]
            named = [s for s in spans if s["traceId"] == trace_id]
            assert named, "span ring lost the inbound trace id"
            assert any(
                s["name"] == "sidecar.generate" for s in named
            ), "sidecar span did not continue the gateway trace"

            reqs = await (await client.get(
                "/debug/requests", params={"trace_id": trace_id}
            )).json()
            [rec] = reqs["backends"][0]["requests"]
            assert rec["traceId"] == trace_id

            ticks = (await (await client.get(
                "/debug/ticks", params={"trace_id": trace_id}
            )).json())["backends"][0]["ticks"]
            assert ticks, "no tick record carries the trace id"
            assert all(trace_id in t["traceIds"] for t in ticks)


# ---------------------------------------------------------------------------
# Structured JSON logging
# ---------------------------------------------------------------------------


class TestJsonLogging:
    def _capture(self):
        from ggrmcp_tpu.utils.jsonlog import JsonFormatter

        stream = io.StringIO()
        handler = logging.StreamHandler(stream)
        handler.setFormatter(JsonFormatter())
        logger = logging.getLogger("ggrmcp.test.jsonlog")
        logger.setLevel(logging.INFO)
        logger.addHandler(handler)
        logger.propagate = False
        return logger, handler, stream

    def test_records_are_parseable_and_carry_trace_id(self):
        logger, handler, stream = self._capture()
        try:
            with tracing.tracer.span("test.span", trace_id="tl-log-1"):
                logger.warning("inside %s", "span")
            logger.info("outside")
        finally:
            logger.removeHandler(handler)
        lines = [
            json.loads(line)
            for line in stream.getvalue().splitlines() if line
        ]
        assert lines[0]["msg"] == "inside span"
        assert lines[0]["level"] == "WARNING"
        assert lines[0]["logger"] == "ggrmcp.test.jsonlog"
        assert lines[0]["trace_id"] == "tl-log-1"
        assert lines[0]["ts"] > 0
        # Outside any span there is no trace id key at all.
        assert "trace_id" not in lines[1]

    def test_exceptions_serialize(self):
        logger, handler, stream = self._capture()
        try:
            try:
                raise ValueError("boom \"quoted\"")
            except ValueError:
                logger.exception("failed")
        finally:
            logger.removeHandler(handler)
        rec = json.loads(stream.getvalue().strip())
        assert rec["msg"] == "failed"
        assert "ValueError" in rec["exc"]

    def test_setup_logging_opt_in(self, monkeypatch):
        """logging.format=json (and GGRMCP_LOG_JSON=1) swap the root
        handlers to the JSON formatter; restored after so the test
        process's logging is untouched."""
        from ggrmcp_tpu.gateway.app import setup_logging
        from ggrmcp_tpu.utils.jsonlog import JsonFormatter

        root = logging.getLogger()
        saved_handlers = root.handlers[:]
        saved_level = root.level
        try:
            cfg = Config()
            cfg.logging.format = "json"
            cfg.validate()
            setup_logging(cfg)
            assert any(
                isinstance(h.formatter, JsonFormatter)
                for h in root.handlers
            )
            # Env-var opt-in, config-free.
            root.handlers[:] = []
            monkeypatch.setenv("GGRMCP_LOG_JSON", "1")
            setup_logging(Config())
            assert any(
                isinstance(h.formatter, JsonFormatter)
                for h in root.handlers
            )
        finally:
            root.handlers[:] = saved_handlers
            root.setLevel(saved_level)

    def test_bad_format_rejected(self):
        cfg = Config()
        cfg.logging.format = "logfmt"
        with pytest.raises(ValueError, match="logging.format"):
            cfg.validate()
