"""Latency-SLO machinery in the continuous batcher (SURVEY.md §7 hard
part #2, round-4 verdict #5): p50_budget_ms caps the decode stall any
single admission round may inflict while slots are decoding, and
queue_deadline_ms expires requests the client has abandoned instead of
spending prefill on them. Queue-time vs device-time accounting backs
both (stats()['queue_ms_*'/'service_ms_*'])."""

import asyncio

import pytest

from ggrmcp_tpu.core.config import BatchingConfig, MeshConfig, ServingConfig
from ggrmcp_tpu.models import llama
from ggrmcp_tpu.ops.sampling import SamplingConfig
from ggrmcp_tpu.serving.batching import ContinuousBatcher
from ggrmcp_tpu.serving.engine import GenerationEngine

pytestmark = pytest.mark.slow  # serving-loop integration (JAX compiles)


@pytest.fixture(scope="module")
def engine():
    return GenerationEngine(
        llama.CONFIGS["tiny-llama"],
        ServingConfig(
            mesh=MeshConfig(tensor=2, data=0),
            batching=BatchingConfig(max_batch_size=8, kv_cache_max_seq=128),
        ),
    )


async def _drain(batcher, prompt, max_new, seed=0):
    out, reason = [], None
    async for ids, reason in batcher.submit(
        prompt, max_new, SamplingConfig(), seed=seed
    ):
        out.extend(ids)
    return out, reason


class TestAdmissionStallCap:
    async def test_budget_splits_saturating_burst(self, engine):
        """With p50_budget_ms set and slots decoding, a burst is
        admitted over MULTIPLE capped rounds (decode ticks interleave)
        instead of one big stall; every request still completes, and
        the worst single admission round stays far below the
        uncapped-burst prefill cost. The cap only engages while decode
        is active, so the burst lands behind one running request."""
        cfg = BatchingConfig(
            max_batch_size=8, kv_cache_max_seq=128,
            # EMA starts at 50 ms/row → cap = ceil(100/4 / 50) = 1 row
            # per round until measured costs re-rate it.
            p50_budget_ms=100.0,
        )
        batcher = ContinuousBatcher(engine, cfg)
        batcher.warmup()
        batcher.start()
        try:
            first = asyncio.create_task(
                _drain(batcher, [5, 6, 7], 24, seed=1)
            )
            await asyncio.sleep(0.05)  # first request is decoding
            rounds0 = batcher.timing["admit_rounds"]
            burst = await asyncio.gather(
                *(
                    _drain(batcher, [9, 9, i], 4, seed=i)
                    for i in range(6)
                )
            )
            await first
        finally:
            await batcher.stop()
        assert all(reason in ("stop", "length") for _, reason in burst)
        # The 6-request burst could not have landed in one admission
        # round under the 1-row starting cap.
        assert batcher.timing["admit_rounds"] - rounds0 >= 3
        # Queue accounting recorded every completed request, and both
        # halves of the wait (pending in the queue, then the admission
        # itself) add up to it.
        stats = batcher.stats()
        assert stats["queue_ms_count"] == 7
        assert stats["pending_ms_sum"] + stats["prefill_ms_sum"] == (
            pytest.approx(stats["queue_ms_sum"])
        )
        # Capped rounds make later rows wait in the queue: pending.
        assert stats["pending_ms_sum"] > 0

    async def test_no_budget_admits_burst_in_one_round(self, engine):
        """Control: without an SLO budget the same burst fuses into a
        single admission round (max throughput behavior unchanged)."""
        batcher = ContinuousBatcher(
            engine,
            BatchingConfig(max_batch_size=8, kv_cache_max_seq=128),
        )
        batcher.warmup()
        batcher.start()
        try:
            rounds0 = batcher.timing["admit_rounds"]
            burst = await asyncio.gather(
                *(
                    _drain(batcher, [9, 9, i], 4, seed=i)
                    for i in range(6)
                )
            )
        finally:
            await batcher.stop()
        assert all(reason in ("stop", "length") for _, reason in burst)
        # All six arrived together with no active decode: one fused
        # round (a straggler admitted on a second round is tolerated).
        assert batcher.timing["admit_rounds"] - rounds0 <= 2


class TestQueueDeadline:
    async def test_expired_requests_time_out_without_prefill(self, engine):
        """Requests still queued past queue_deadline_ms fail with
        finish_reason 'timeout' instead of being admitted; requests
        that got slots are unaffected."""
        cfg = BatchingConfig(
            max_batch_size=2, kv_cache_max_seq=128,
            queue_deadline_ms=80.0,
        )
        batcher = ContinuousBatcher(engine, cfg)
        batcher.warmup()
        batcher.start()
        try:
            # Two long-running requests occupy both slots...
            long_tasks = [
                asyncio.create_task(_drain(batcher, [5, i], 48, seed=i))
                for i in range(2)
            ]
            await asyncio.sleep(0.05)
            # ...and two more arrive that will sit in the queue past
            # the deadline (tiny-llama CPU decode of 48 tokens takes
            # far longer than 80 ms).
            late = await asyncio.gather(
                _drain(batcher, [7, 7], 4, seed=9),
                _drain(batcher, [8, 8], 4, seed=10),
            )
            results = await asyncio.gather(*long_tasks)
        finally:
            await batcher.stop()
        assert all(r in ("stop", "length") for _, r in results)
        timed_out = [r for _, r in late if r == "timeout"]
        assert timed_out, f"expected queue timeouts, got {late}"
        assert batcher.timed_out == len(timed_out)
        assert batcher.stats()["timed_out"] == len(timed_out)

    async def test_zero_deadline_waits_forever(self, engine):
        """Default (0) keeps the old semantics: queued requests wait."""
        batcher = ContinuousBatcher(
            engine,
            BatchingConfig(max_batch_size=2, kv_cache_max_seq=128),
        )
        batcher.warmup()
        batcher.start()
        try:
            results = await asyncio.gather(
                *(
                    _drain(batcher, [4, i], 6, seed=i)
                    for i in range(5)  # > slots → real queueing
                )
            )
        finally:
            await batcher.stop()
        assert all(r in ("stop", "length") for _, r in results)
        assert batcher.timed_out == 0
