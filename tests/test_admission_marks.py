"""The admission round, timed from inside (PR 39, marker `obs`; the
order of the marks since PR 52: tests/test_deferred_first.py has the
deferred order through a whole batcher).

  * The round's PhaseTimer is marked by every admission program call
    (build / launch), seat and settle (activate) and wait (tick_wait /
    device): contiguous, so the marks sum to the round's duration.
  * Nothing is read at the launch: the first tokens come back as the
    device's array, and the settle waits. `tick_wait` ends when the
    tick in flight has left the device, so `device` is the admission
    program alone; it is taken once a round, and the tick's own
    failure is not raised in the round. A second program of a round
    is launched when the first has left the device.
  * `admit_device_ms` is observed once per program call (the program's
    own time) and `admit_host_ms` once per round, through stats(), the
    proto, the /debug ring and /metrics; the profiler's spans carry the
    round's seq and the program's family, rows, chunks and tokens.
"""

import asyncio
import time

import numpy as np
import pytest

from ggrmcp_tpu.core.config import MeshConfig, ServingConfig
from ggrmcp_tpu.models import llama
from ggrmcp_tpu.serving.batching import ContinuousBatcher, _SeatedRound
from ggrmcp_tpu.serving.engine import GenerationEngine
from ggrmcp_tpu.serving.flight_recorder import (
    ADMIT_HOST_MARKS,
    FlightRecorder,
    PhaseTimer,
)
from ggrmcp_tpu.utils import tracing
from tests.test_timeline import _batcher, _consume

pytestmark = pytest.mark.obs

MARKS = {"build", "launch", "tick_wait", "device", "activate"}


class _Tick:
    """A tick's token array still on the device: ready `after` seconds
    from now; `fail` raises from the wait as a failed tick would."""

    def __init__(self, after: float, fail: bool = False):
        self.ready_at = time.perf_counter() + after
        self.fail = fail
        self.waits = 0

    def block_until_ready(self):
        self.waits += 1
        time.sleep(max(0.0, self.ready_at - time.perf_counter()))
        if self.fail:
            raise RuntimeError("the tick in flight failed")
        return self


def _round(inflight=()) -> ContinuousBatcher:
    """Just enough of a batcher for one admission round's program
    calls, seats and settle: the round's state, no tables to sync,
    `inflight` ticks, a four-slot token feedback."""
    import jax.numpy as jnp

    b = ContinuousBatcher.__new__(ContinuousBatcher)
    b._paged = False
    b._cache_at_risk = False
    b.cache = None
    b._inflight = [(t, None, [], None) for t in inflight]
    b._seated = None
    b._round = _SeatedRound(
        PhaseTimer(), {"seq": 1, "tick": 1},
        inflight[-1] if inflight else None)
    b._cur_dev = b._gstate_dev = jnp.zeros((4,), jnp.int32)
    b._adm_chunk_run = b._admit_run = 0
    return b


def _program(b, seen: list, first=(7,)):
    """One program call whose launch notes the at-risk flag, and the
    seat of its (no) rows."""
    import jax.numpy as jnp

    def launch():
        seen.append(b._cache_at_risk)
        return jnp.asarray(first, jnp.int32), "cache"

    got = b._admission_program(
        launch, "single", rows=1, chunks=1, tokens=3, width=4)
    b._activate_rows([], got, np.full((len(first),), 4, np.int32))
    return got


def _marks(b) -> list:
    return [p for p, _ in b._round.timer.marks]


class TestTheRoundsMarks:
    def test_nothing_is_read_at_the_launch(self):
        b, seen = _round(), []
        first = _program(b, seen)
        # The device's array, not a host copy; the round is seated and
        # the cache stays flagged until the settle has waited.
        assert not isinstance(first, np.ndarray) and b.cache == "cache"
        assert seen == [True] and b._cache_at_risk is True
        assert b._seated is b._round
        assert _marks(b) == ["build", "launch", "activate"]
        b._settle_round()
        assert b._seated is None and b._cache_at_risk is False
        # A settled round holds nothing on the device.
        assert b._round.programs[0].first is None
        # No tick in flight: device starts where the seat ended.
        assert _marks(b) == ["build", "launch", "activate", "device", "activate"]

    def test_device_starts_when_the_tick_in_flight_is_ready(self):
        tick = _Tick(after=0.05)
        b = _round([tick])
        _program(b, [])
        assert tick.waits == 0  # the round itself waited for nothing
        b._settle_round()
        segs = {p: (s, e) for p, s, e in b._round.timer.segments()}
        assert list(segs) == [
            "build", "launch", "activate", "tick_wait", "device"]
        # tick_wait ends, and device starts, at the tick's readiness.
        assert segs["tick_wait"][1] == segs["device"][0]
        assert segs["device"][0] >= tick.ready_at
        assert segs["device"][0] - tick.ready_at < 0.02
        assert b._round.timer.acc["tick_wait"] >= 40.0
        # Nothing was consumed: the tick is still in flight.
        assert len(b._inflight) == 1 and tick.waits == 1

    def test_a_second_program_waits_for_the_first_and_not_for_the_tick_again(self):
        tick = _Tick(after=0.0)
        b = _round([tick])
        _program(b, [])
        _program(b, [])
        assert tick.waits == 1
        # The second launch came after the wait for the first program
        # (one admission program's temporaries at a time).
        assert _marks(b) == [
            "build", "launch", "activate",
            "build", "tick_wait", "device", "launch", "activate"]
        assert [p.waited for p in b._round.programs] == [True, False]
        b._settle_round()
        assert tick.waits == 1
        assert _marks(b)[8:] == ["device", "activate"]

    def test_the_newest_tick_in_flight_is_the_one_waited_for(self):
        old, new = _Tick(after=0.0), _Tick(after=0.0)
        b = _round([old, new])
        _program(b, [])
        b._settle_round()
        assert (old.waits, new.waits) == (0, 1)

    def test_the_ticks_failure_is_not_raised_in_the_round(self):
        tick = _Tick(after=0.0, fail=True)
        b = _round([tick])
        _program(b, [])
        b._settle_round()
        assert b._seated is None and b._round.programs[0].waited
        assert tick.waits == 1 and "tick_wait" in b._round.timer.acc
        # The collect still finds the tick, and raises its failure.
        assert b._inflight[0][0] is tick

    def test_the_marks_sum_to_the_rounds_duration(self):
        b = _round([_Tick(after=0.01)])
        _program(b, [])
        _program(b, [])
        b._settle_round()
        timer = b._round.timer
        assert set(timer.acc) == MARKS
        assert sum(timer.acc.values()) == pytest.approx(
            (timer.last - timer.t0) * 1000.0, abs=1e-9)
        rec = FlightRecorder()
        rec.note_admission(timer, "single", [], 2, 6, 0, tick_seq=1, seq=1)
        [adm] = rec.admission_snapshot()
        assert adm.programs == 2 and not adm.deferred
        assert adm.host_ms == pytest.approx(
            sum(timer.acc[p] for p in ADMIT_HOST_MARKS))
        assert adm.host_ms + adm.tick_wait_ms + adm.device_ms == (
            pytest.approx(adm.duration_ms, abs=1e-9))
        assert adm.dispatch_ms == 0.0
        stats = rec.histogram_stats()
        assert stats["admit_device_ms_count"] == 2
        # The first program's own time is the wait for it (the host
        # had nothing else to do); the second's runs from its launch's
        # return, through its seat, to its first tokens.
        segs = timer.segments()
        [seat2] = [e - s for p, s, e in segs[-3:] if p == "activate"][:1]
        assert stats["admit_device_ms_sum"] == pytest.approx(
            adm.device_ms + seat2 * 1000.0)
        assert stats["admit_host_ms_count"] == 1
        assert stats["admit_host_ms_sum"] == pytest.approx(adm.host_ms)

    def test_a_deferred_round_names_the_gap_and_keeps_it_out_of_device(self):
        b = _round([_Tick(after=0.0)])
        _program(b, [])
        b._round.timer.mark("activate")  # the way out
        time.sleep(0.01)  # the loop's hop and the tick's dispatch
        b._round.timer.mark("dispatch")
        b._settle_round()
        timer = b._round.timer
        assert _marks(b) == [
            "build", "launch", "activate", "activate", "dispatch",
            "tick_wait", "device", "activate"]
        rec = FlightRecorder()
        rec.note_admission(
            timer, "single", [], 1, 3, 0, tick_seq=1, seq=1, deferred=True)
        [adm] = rec.admission_snapshot()
        assert adm.deferred and adm.dispatch_ms >= 10.0
        assert (adm.host_ms + adm.tick_wait_ms + adm.device_ms
                + adm.dispatch_ms) == pytest.approx(adm.duration_ms, abs=1e-9)
        # The program's own time starts where the wait before it ended,
        # after the gap: none of the dispatch is in it.
        stats = rec.histogram_stats()
        assert stats["admit_device_ms_sum"] == pytest.approx(adm.device_ms)
        assert adm.to_dict()["deferred"] is True
        assert adm.to_dict()["dispatchMs"] == round(adm.dispatch_ms, 3)


@pytest.fixture(scope="module")
def mistral_engine():
    return GenerationEngine(
        llama.CONFIGS["tiny-mistral"],
        ServingConfig(mesh=MeshConfig(tensor=1, data=0)),
    )


PREAMBLE = list(range(3, 67))


async def _run(engine, waves, **cfg_kw) -> ContinuousBatcher:
    batcher = _batcher(engine, **cfg_kw)
    loop = asyncio.get_running_loop()
    await loop.run_in_executor(None, batcher.warmup)
    batcher.start()
    try:
        for wave in waves:
            await asyncio.gather(*(
                _consume(batcher, p, 4, seed=i) for i, p in enumerate(wave)
            ))
    finally:
        await batcher.stop()
    return batcher


class TestThroughABatcher:
    @pytest.mark.parametrize("case", [
        "trickle", "burst", "page_reuse", "long_prompt",
    ])
    async def test_counts_and_the_partition(self, mistral_engine, case):
        waves, cfg = {
            "trickle": ([[[5, 6, 7]]], {}),
            "burst": ([[[9, 9, i] for i in range(4)]], {}),
            "page_reuse": (
                [[PREAMBLE + [70]], [PREAMBLE + [71 + i] for i in range(2)]],
                {"paged_kv": "on", "paged_kv_page_size": 16},
            ),
            "long_prompt": ([[list(range(3, 83))]], {"prefill_chunk": 32}),
        }[case]
        batcher = await _run(mistral_engine, waves, **cfg)
        self._assert_split(batcher)

    async def test_a_pipelined_loop_waits_for_its_tick_in_flight(
        self, mistral_engine
    ):
        batcher = _batcher(mistral_engine, pipeline_ticks="on")
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(None, batcher.warmup)
        batcher.start()
        try:
            decoding = asyncio.ensure_future(
                _consume(batcher, [5, 6, 7], 96))
            while batcher.timing["ticks"] < 2:
                await asyncio.sleep(0.005)
            # A tick is in flight whenever this one is admitted.
            await _consume(batcher, [8, 9, 10], 4, seed=1)
            await decoding
        finally:
            await batcher.stop()
        first, second = batcher.recorder.admission_snapshot()
        assert first.tick_wait_ms == 0.0 and second.tick_wait_ms > 0.0
        # The second round was settled behind the tick's dispatch.
        assert second.deferred and second.dispatch_ms > 0.0
        self._assert_split(batcher)

    @staticmethod
    def _assert_split(batcher):
        records = batcher.recorder.admission_snapshot()
        stats = batcher.stats()
        # One device observation per admission program call, one host
        # observation per round.
        assert stats["admit_device_ms_count"] == sum(
            r.programs for r in records) >= len(records)
        assert stats["admit_host_ms_count"] == len(records) == (
            stats["admit_rounds"])
        assert stats["admit_rounds_deferred"] == sum(
            r.deferred for r in records)
        # The admit phase holds a round's time up to its return, and
        # its settle where no tick was dispatched in between; a deferred
        # round's settle (its waits among it) lies in that tick's wait
        # phase.
        admit = stats["tick_phase_admit_ms"] + batcher._admit_phase_ms
        whole = sum(r.duration_ms for r in records if not r.deferred)
        assert admit >= whole - 0.05 and admit > 0
        if not any(r.deferred for r in records):
            assert admit == pytest.approx(whole, abs=0.05)
        # A program's own time is never less than the host's wait for
        # it.
        assert stats["admit_device_ms_sum"] >= sum(
            r.device_ms for r in records) - 1e-6
        for r in records:
            assert r.programs >= 1 and r.device_ms > 0 and r.host_ms > 0
            assert (r.host_ms + r.tick_wait_ms + r.device_ms
                    + r.dispatch_ms) == pytest.approx(r.duration_ms, abs=1e-6)

    async def test_the_record_carries_the_split_to_dict_and_proto(
        self, mistral_engine
    ):
        from ggrmcp_tpu.rpc.pb import serving_pb2

        batcher = await _run(mistral_engine, [[[5, 6, 7]]])
        [adm] = batcher.recorder.admission_snapshot()
        d = adm.to_dict()
        assert d["programs"] == 1
        assert d["hostMs"] + d["tickWaitMs"] + d["deviceMs"] == (
            pytest.approx(d["durationMs"], abs=0.005))
        assert d["deferred"] is False and d["dispatchMs"] == 0.0
        wire = serving_pb2.AdmissionRecord(
            seq=adm.seq, duration_ms=adm.duration_ms, family=adm.family,
            host_ms=adm.host_ms, tick_wait_ms=adm.tick_wait_ms,
            device_ms=adm.device_ms, programs=adm.programs,
        ).SerializeToString()
        back = serving_pb2.AdmissionRecord.FromString(wire)
        assert (back.host_ms, back.tick_wait_ms, back.device_ms,
                back.programs) == (
            adm.host_ms, adm.tick_wait_ms, adm.device_ms, 1)
        # stats() feeds the stats proto as is.
        serving_pb2.ServingStatsResponse(**{
            k: v for k, v in batcher.stats().items()
            if k.startswith("admit_")
        })

    async def test_debug_ring_timeline_and_metrics_carry_it(self):
        from tests.test_observability import _generate_call, observed_env

        async with observed_env("fastlane") as (_side, _gw, client):
            await _generate_call(client, "trace-admit-split", max_new=4)
            ring = await (await client.get(
                "/debug/ticks", params={"trace_id": "trace-admit-split"}
            )).json()
            [adm] = ring["backends"][0]["admissions"]
            assert int(adm["programs"]) == 1 and float(adm["deviceMs"]) > 0
            assert (
                float(adm["hostMs"]) + float(adm.get("tickWaitMs", 0))
                + float(adm["deviceMs"])
            ) == pytest.approx(float(adm["durationMs"]), abs=1e-6)
            timeline = await (await client.get("/debug/timeline")).json()
            [slice_] = [
                e for e in timeline["traceEvents"]
                if e.get("cat") == "admission"
            ]
            assert {"hostMs", "deviceMs", "programs"} <= set(slice_["args"])
            # The gateway polls backend stats on an interval.
            for _ in range(100):
                text = await (await client.get("/metrics")).text()
                if "gateway_backend_admit_device_ms_count" in text:
                    break
                await asyncio.sleep(0.1)
        for name in ("admit_device_ms", "admit_host_ms"):
            [help_line] = [
                line for line in text.splitlines()
                if line.startswith(f"# HELP gateway_backend_{name} ")
            ]
            assert "admission" in help_line
            assert f"gateway_backend_{name}_bucket" in text
            assert f"gateway_backend_{name}_sum" in text


class TestSpans:
    async def test_the_rounds_children_while_a_capture_runs(
        self, mistral_engine, monkeypatch
    ):
        import jax

        events: list = []

        class Spy:
            def __init__(self, name, **stats):
                self.name, self.stats = name, stats

            def __enter__(self):
                events.append(("enter", self.name, self.stats))

            def __exit__(self, *exc):
                events.append(("exit", self.name, self.stats))
                return False

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Spy)
        monkeypatch.setattr(tracing, "capture_running", True)
        await _run(mistral_engine, [[list(range(3, 83))]], prefill_chunk=32)
        admit = [e for e in events if e[1].startswith("ggrmcp.admit")]
        # One round, one program call: the launch and the seat are
        # children of the round; the settle (here inside the round: the
        # loop is not pipelined) holds the wait and the activation.
        assert [(kind, name) for kind, name, _ in admit] == [
            ("enter", "ggrmcp.admit"),
            ("enter", "ggrmcp.admit.program"),
            ("exit", "ggrmcp.admit.program"),
            ("enter", "ggrmcp.admit.activate"),
            ("exit", "ggrmcp.admit.activate"),
            ("enter", "ggrmcp.admit.settle"),
            ("enter", "ggrmcp.admit.device"),
            ("exit", "ggrmcp.admit.device"),
            ("enter", "ggrmcp.admit.activate"),
            ("exit", "ggrmcp.admit.activate"),
            ("exit", "ggrmcp.admit.settle"),
            ("exit", "ggrmcp.admit"),
        ]
        by_name = {name: stats for kind, name, stats in admit}
        assert by_name["ggrmcp.admit.program"] == {
            "seq": 1, "tick": 1, "family": "chunked",
            "rows": 1, "chunks": 3, "tokens": 80, "chunk_tokens_run": 96,
        }
        for name in ("ggrmcp.admit.device", "ggrmcp.admit.activate",
                     "ggrmcp.admit.settle"):
            assert by_name[name] == by_name["ggrmcp.admit"] == {
                "seq": 1, "tick": 1}


class TestTheChunksThatRan:
    """`prefill_chunk_tokens_run` (PR 47): the token positions of the
    chunk rows the admission programs ran, beside the prompt tokens
    they computed; the program span's `chunks` and `chunk_tokens_run`
    say the same of each call."""

    @pytest.mark.parametrize("case, want", [
        # A cold chunked round of 80, 33 and 64 tokens at chunk 32:
        # each row's own 3, 2 and 2 chunks, not 3 x the bucket of four.
        ("chunked", {"family": "chunked", "rows": 3, "chunks": 7,
                     "tokens": 177, "chunk_tokens_run": 32 * sum(
                         -(-n // 32) for n in (80, 33, 64))}),
        # One short prompt: the single-row program at its 32 bucket.
        ("single", {"family": "single", "rows": 1, "chunks": 1,
                    "tokens": 9, "chunk_tokens_run": 32}),
        # A burst of three: the full-pool program runs all four rows.
        ("full", {"family": "full", "rows": 3, "chunks": 4,
                  "tokens": 27, "chunk_tokens_run": 4 * 32}),
    ])
    async def test_the_counter_and_the_span_agree(
        self, mistral_engine, monkeypatch, case, want
    ):
        import jax

        spans: list = []

        class Spy:
            def __init__(self, name, **stats):
                if name == "ggrmcp.admit.program":
                    spans.append(stats)

            def __enter__(self):
                pass

            def __exit__(self, *exc):
                return False

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Spy)
        monkeypatch.setattr(tracing, "capture_running", True)
        lens = {"chunked": (80, 33, 64), "single": (9,),
                "full": (9, 9, 9)}[case]
        batcher = _batcher(mistral_engine, prefill_chunk=32)
        # Queued before the loop starts: one admission round.
        tasks = [
            asyncio.ensure_future(
                _consume(batcher, [3 + i] * n, 2, seed=i))
            for i, n in enumerate(lens)
        ]
        await asyncio.sleep(0)
        batcher.start()
        try:
            await asyncio.gather(*tasks)
        finally:
            await batcher.stop()
        [span] = spans
        assert {k: span[k] for k in want} == want
        stats = batcher.counter_stats()
        assert stats["prefill_chunk_tokens_run"] == want["chunk_tokens_run"]
        assert stats["prefill_tokens_computed"] == want["tokens"]

    async def test_a_paged_suffix_group_counts_its_buckets_rows(
        self, mistral_engine
    ):
        """A same-preamble wave of three re-admissions: the suffix grid
        runs the bucket of four rows x one chunk of the suffix's
        16-token bucket, padding row included, as that program does."""
        batcher = await _run(
            mistral_engine,
            [[PREAMBLE + [70]], [PREAMBLE + [71 + i] for i in range(3)]],
            paged_kv="on", paged_kv_page_size=16)
        fams = [a.family for a in batcher.recorder.admission_snapshot()]
        assert fams[0] == "single" and "paged_pfx" in fams[-1]
        stats = batcher.counter_stats()
        assert stats["prefill_tokens_reused"] > 0
        # 65 tokens cold at their 128 bucket, then the wave's grids.
        assert stats["prefill_chunk_tokens_run"] > 128
        assert stats["prefill_chunk_tokens_run"] % 16 == 0
        assert stats["prefill_chunk_tokens_run"] >= (
            stats["prefill_tokens_computed"])

    def test_the_field_reaches_the_proto_and_the_metrics(self):
        from ggrmcp_tpu.gateway import metrics
        from ggrmcp_tpu.rpc.pb import serving_pb2

        field = serving_pb2.ServingStatsResponse.DESCRIPTOR.fields_by_name[
            "prefill_chunk_tokens_run"]
        assert field.number == 177
        assert "chunk rows" in metrics._SERVING_HELP[
            "prefill_chunk_tokens_run"]


def test_the_clock_check_pairs_a_device_span_with_its_module():
    """scripts/admit_clock_check.py on a hand-made trace: a settle whose
    device span starts when the tick's module ends and ends with the
    program's module, a second program the host came to 30 ms into its
    run, and a span the capture cut (no module ends in it)."""
    from benchmark import xplane
    from scripts.admit_clock_check import pair

    ms = 10**9  # picoseconds

    def ev(name, start_ms, dur_ms):
        return xplane.Event(name, int(start_ms * ms), int(dur_ms * ms))

    planes = xplane.parse(xplane.dump([
        xplane.Plane("/host:CPU", [xplane.Line("batcher", [
            ev("ggrmcp.admit", 0, 12), ev("ggrmcp.admit.program", 5, 3),
            ev("ggrmcp.admit.activate", 8, 1),
            ev("ggrmcp.tick.dispatch", 13, 5),
            ev("ggrmcp.admit.settle", 18, 86),
            ev("ggrmcp.admit.device", 20, 80),
            ev("ggrmcp.admit.activate", 100, 4),
            ev("ggrmcp.admit.device", 230, 20),
            ev("ggrmcp.admit.device", 400, 40),
        ])]),
        xplane.Plane("/device:TPU:0", [xplane.Line("XLA Modules", [
            ev("jit__tick_impl(1)", 0, 20),
            ev("jit__admit_chunked_impl(2)", 20.25, 79.5),
            ev("jit__admit_paged_pfx_impl(3)", 200, 49.5),
        ])]),
    ]))
    pairs, cut = pair(planes)
    assert cut == 1
    (device_ms, module_ms, start_ms, name), second = pairs
    assert (device_ms, module_ms) == (80.0, 79.5)
    assert start_ms == pytest.approx(0.25) and "admit_chunked" in name
    # The host waited for the last 20 ms of a 49.5 ms program.
    assert second[:2] == (20.0, 49.5) and second[2] == pytest.approx(-30.0)
