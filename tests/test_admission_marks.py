"""The admission round, timed from inside (PR 39, marker `obs`).

  * The round's PhaseTimer is marked by every admission program call
    (build / launch / tick_wait / device) and activation loop
    (activate): contiguous, so the marks sum to the round's duration.
  * `tick_wait` ends when the tick in flight has left the device, so
    `device` is the admission program alone; it is taken once a round,
    and the tick's own failure is not raised in the round.
  * `admit_device_ms` is observed once per program call and
    `admit_host_ms` once per round, through stats(), the proto, the
    /debug ring and /metrics; the profiler's spans carry the round's
    seq and the program's family, rows, chunks and tokens.
"""

import asyncio
import time

import numpy as np
import pytest

from ggrmcp_tpu.core.config import MeshConfig, ServingConfig
from ggrmcp_tpu.models import llama
from ggrmcp_tpu.serving.batching import ContinuousBatcher
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
    calls: the round's state, no tables to sync, `inflight` ticks."""
    b = ContinuousBatcher.__new__(ContinuousBatcher)
    b._paged = False
    b._cache_at_risk = False
    b.cache = None
    b._inflight = [(t, None, [], None) for t in inflight]
    b._adm_timer = PhaseTimer()
    b._adm_span = {"seq": 1, "tick": 1}
    b._adm_chunk_run = b._admit_run = 0
    return b


def _program(b, seen: list, first=(7,)):
    """One program call whose launch notes the at-risk flag."""
    def launch():
        seen.append(b._cache_at_risk)
        return np.asarray(first, np.int32), "cache"

    return b._admission_program(
        launch, "single", rows=1, chunks=1, tokens=3, width=4)


class TestTheRoundsMarks:
    def test_no_tick_in_flight_device_starts_at_the_launchs_return(self):
        b, seen = _round(), []
        first = _program(b, seen)
        assert first.tolist() == [7] and b.cache == "cache"
        # The donating call ran with the cache flagged, and the flag
        # is cleared once `first` is on the host.
        assert seen == [True] and b._cache_at_risk is False
        assert [p for p, _ in b._adm_timer.marks] == [
            "build", "launch", "device"]

    def test_device_starts_when_the_tick_in_flight_is_ready(self):
        tick = _Tick(after=0.05)
        b = _round([tick])
        _program(b, [])
        segs = {p: (s, e) for p, s, e in b._adm_timer.segments()}
        assert list(segs) == ["build", "launch", "tick_wait", "device"]
        # tick_wait ends, and device starts, at the tick's readiness.
        assert segs["tick_wait"][1] == segs["device"][0]
        assert segs["device"][0] >= tick.ready_at
        assert segs["device"][0] - tick.ready_at < 0.02
        assert b._adm_timer.acc["tick_wait"] >= 40.0
        # Nothing was consumed: the tick is still in flight.
        assert len(b._inflight) == 1 and tick.waits == 1

    def test_a_second_program_call_does_not_wait_for_the_tick_again(self):
        tick = _Tick(after=0.0)
        b = _round([tick])
        _program(b, [])
        b._activate_rows([])
        _program(b, [])
        assert tick.waits == 1
        assert [p for p, _ in b._adm_timer.marks] == [
            "build", "launch", "tick_wait", "device", "activate",
            "build", "launch", "device"]

    def test_the_newest_tick_in_flight_is_the_one_waited_for(self):
        old, new = _Tick(after=0.0), _Tick(after=0.0)
        b = _round([old, new])
        _program(b, [])
        assert (old.waits, new.waits) == (0, 1)

    def test_the_ticks_failure_is_not_raised_in_the_round(self):
        tick = _Tick(after=0.0, fail=True)
        b = _round([tick])
        assert _program(b, []).tolist() == [7]
        assert tick.waits == 1 and "tick_wait" in b._adm_timer.acc
        # The collect still finds the tick, and raises its failure.
        assert b._inflight[0][0] is tick

    def test_the_marks_sum_to_the_rounds_duration(self):
        b = _round([_Tick(after=0.01)])
        _program(b, [])
        b._activate_rows([])
        _program(b, [])
        b._activate_rows([])
        timer = b._adm_timer
        assert set(timer.acc) == MARKS
        assert sum(timer.acc.values()) == pytest.approx(
            (timer.last - timer.t0) * 1000.0, abs=1e-9)
        rec = FlightRecorder()
        rec.note_admission(timer, "single", [], 2, 6, 0, tick_seq=1, seq=1)
        [adm] = rec.admission_snapshot()
        assert adm.programs == 2
        assert adm.host_ms == pytest.approx(
            sum(timer.acc[p] for p in ADMIT_HOST_MARKS))
        assert adm.host_ms + adm.tick_wait_ms + adm.device_ms == (
            pytest.approx(adm.duration_ms, abs=1e-9))
        stats = rec.histogram_stats()
        assert stats["admit_device_ms_count"] == 2
        assert stats["admit_device_ms_sum"] == pytest.approx(adm.device_ms)
        assert stats["admit_host_ms_count"] == 1
        assert stats["admit_host_ms_sum"] == pytest.approx(adm.host_ms)


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
        # admit phase >= host + device: what is left is the wait for
        # the tick in flight (and the rounds no tick has carried yet).
        admit = stats["tick_phase_admit_ms"] + batcher._admit_phase_ms
        split = stats["admit_host_ms_sum"] + stats["admit_device_ms_sum"]
        assert admit >= split - 0.05 > 0
        assert admit - split == pytest.approx(
            sum(r.tick_wait_ms for r in records), abs=0.05)
        for r in records:
            assert r.programs >= 1 and r.device_ms > 0 and r.host_ms > 0
            assert r.host_ms + r.tick_wait_ms + r.device_ms == (
                pytest.approx(r.duration_ms, abs=1e-6))

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
        # One round, one program call: the program and the activation
        # are children of the round, the device span of the program.
        assert [(kind, name) for kind, name, _ in admit] == [
            ("enter", "ggrmcp.admit"),
            ("enter", "ggrmcp.admit.program"),
            ("enter", "ggrmcp.admit.device"),
            ("exit", "ggrmcp.admit.device"),
            ("exit", "ggrmcp.admit.program"),
            ("enter", "ggrmcp.admit.activate"),
            ("exit", "ggrmcp.admit.activate"),
            ("exit", "ggrmcp.admit"),
        ]
        by_name = {name: stats for kind, name, stats in admit}
        assert by_name["ggrmcp.admit.program"] == {
            "seq": 1, "tick": 1, "family": "chunked",
            "rows": 1, "chunks": 3, "tokens": 80, "chunk_tokens_run": 96,
        }
        for name in ("ggrmcp.admit.device", "ggrmcp.admit.activate"):
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
    """scripts/admit_clock_check.py on a hand-made trace: a program
    call whose device span starts when the tick's module ends, and one
    the capture cut (its module is not in the trace)."""
    from benchmark import xplane
    from scripts.admit_clock_check import pair

    ms = 10**9  # picoseconds

    def ev(name, start_ms, dur_ms):
        return xplane.Event(name, int(start_ms * ms), int(dur_ms * ms))

    planes = xplane.parse(xplane.dump([
        xplane.Plane("/host:CPU", [xplane.Line("batcher", [
            ev("ggrmcp.admit", 0, 105), ev("ggrmcp.admit.program", 5, 95),
            ev("ggrmcp.admit.device", 20, 80),
            ev("ggrmcp.admit.activate", 100, 4),
            ev("ggrmcp.admit.program", 200, 50),
            ev("ggrmcp.admit.device", 210, 40),
        ])]),
        xplane.Plane("/device:TPU:0", [xplane.Line("XLA Modules", [
            ev("jit__tick_impl(1)", 0, 20),
            ev("jit__admit_chunked_impl(2)", 20.25, 79.5),
        ])]),
    ]))
    pairs, cut = pair(planes)
    assert cut == 1
    [(device_ms, module_ms, start_ms, name)] = pairs
    assert (device_ms, module_ms) == (80.0, 79.5)
    assert start_ms == pytest.approx(0.25) and "admit_chunked" in name
