"""Chaos suite: deterministic fault injection against the hardened
request lifecycle (utils/failpoints.py).

The two load-bearing guarantees, each proven with injected faults:

  * Tick-failure replay — with `tick_fail:every=N` injected, greedy
    outputs are BIT-IDENTICAL to the fault-free run for every request
    within the retry budget (victims requeue with their emitted-token
    prefix; consumers never see a duplicate or missing token).
  * Bounded admission — under a submit storm the pending queue never
    exceeds batching.max_pending; excess submits shed with
    OverloadedError (→ 429 at the gateway) and the shed counters
    increment, instead of unbounded queue growth.

Marked `chaos` (tier-1, like the interleave net): `make test-chaos`
selects it alone; it is deliberately NOT slow-marked so the default
`-m "not slow"` run always exercises the failure paths.
"""

import asyncio
import time

import pytest

from ggrmcp_tpu.core import config as cfgmod
from ggrmcp_tpu.core.config import BatchingConfig, MeshConfig, ServingConfig
from ggrmcp_tpu.models import llama
from ggrmcp_tpu.ops.sampling import SamplingConfig
from ggrmcp_tpu.serving.batching import ContinuousBatcher, OverloadedError
from ggrmcp_tpu.serving.engine import GenerationEngine
from ggrmcp_tpu.serving.tiered import TieredBatcher
from ggrmcp_tpu.utils import failpoints
from ggrmcp_tpu.utils.failpoints import (
    FailpointError,
    FailpointRegistry,
    parse_spec,
)

pytestmark = pytest.mark.chaos

GREEDY = SamplingConfig(temperature=0.0)


@pytest.fixture(scope="module")
def engine():
    return GenerationEngine(
        llama.CONFIGS["tiny-llama"],
        ServingConfig(mesh=MeshConfig(tensor=2, data=0)),
    )


@pytest.fixture(autouse=True)
def clean_failpoints():
    """Every scenario arms the shared registry; nothing may leak into
    the next test (or the rest of the suite)."""
    failpoints.registry.disarm()
    yield
    failpoints.registry.disarm()


async def _drain(batcher, prompt, max_new, seed=0, unary=False):
    out, reason = [], None
    async for ids, reason in batcher.submit(
        prompt, max_new, GREEDY, seed=seed, unary=unary
    ):
        out.extend(ids)
    return out, reason


# ---------------------------------------------------------------------------
# Failpoint registry semantics (pure host-side unit tests)
# ---------------------------------------------------------------------------


class TestFailpointRegistry:
    def test_every_n_fires_deterministically(self):
        reg = FailpointRegistry()
        reg.arm("x", every=3)
        fired = []
        for i in range(1, 10):
            try:
                reg.evaluate("x")
                fired.append(False)
            except FailpointError as exc:
                assert exc.name == "x" and exc.hit == i
                fired.append(True)
        assert fired == [False, False, True] * 3

    def test_times_bounds_fires(self):
        reg = FailpointRegistry()
        reg.arm("x", every=1, times=2)
        fires = 0
        for _ in range(5):
            try:
                reg.evaluate("x")
            except FailpointError:
                fires += 1
        assert fires == 2

    def test_ms_point_sleeps_instead_of_raising(self):
        reg = FailpointRegistry()
        reg.arm("slow", ms=30)
        t0 = time.perf_counter()
        reg.evaluate("slow")  # must NOT raise
        assert (time.perf_counter() - t0) >= 0.025

    def test_unarmed_is_noop(self):
        FailpointRegistry().evaluate("anything")

    def test_spec_parsing(self):
        assert parse_spec("tick_fail:every=7,admit_slow:ms=50") == [
            ("tick_fail", {"every": 7}),
            ("admit_slow", {"ms": 50.0}),
        ]
        assert parse_spec("tick_fail:every=3,times=2") == [
            ("tick_fail", {"every": 3, "times": 2})
        ]
        assert parse_spec("tick_fail") == [("tick_fail", {})]
        with pytest.raises(ValueError):
            parse_spec("tick_fail:bogus=1")
        with pytest.raises(ValueError):
            parse_spec("tick_fail:every")

    def test_config_validates_failpoint_spec(self):
        cfg = cfgmod.default()
        cfg.serving.failpoints = "tick_fail:every=7"
        cfg.validate()  # well-formed spec passes
        cfg.serving.failpoints = "tick_fail:frequency=7"
        with pytest.raises(ValueError, match="failpoints"):
            cfg.validate()


# ---------------------------------------------------------------------------
# Tick-failure replay
# ---------------------------------------------------------------------------


class TestTickFailureReplay:
    async def _run_all(self, engine, prompts, max_new, **cfg_kw):
        cfg = BatchingConfig(
            max_batch_size=4, kv_cache_max_seq=128, **cfg_kw
        )
        batcher = ContinuousBatcher(engine, cfg)
        batcher.start()
        try:
            results = await asyncio.gather(
                *(
                    _drain(batcher, p, max_new, seed=i, unary=(i == 0))
                    for i, p in enumerate(prompts)
                )
            )
            return results, batcher
        finally:
            await batcher.stop()

    async def test_greedy_bit_identical_under_injected_tick_faults(
        self, engine
    ):
        """THE acceptance property: with tick_fail:every=N injected,
        every request within the retry budget streams exactly the
        fault-free tokens — replay rebuilds each victim from its
        prompt + emitted prefix, so greedy continuations are
        bit-identical and no token is duplicated or dropped. One
        request runs unary to pin the single-terminal-chunk contract
        under replay too."""
        prompts = [[3, 1, 4, 1], [2, 7, 1], [5, 5, 5, 5, 5], [9, 9]]
        baseline, base_b = await self._run_all(engine, prompts, 8)
        failpoints.registry.arm("tick_fail", every=3)
        faulted, chaos_b = await self._run_all(
            engine, prompts, 8, tick_retry_limit=32
        )
        failpoints.registry.disarm()
        assert base_b.replayed == 0
        assert chaos_b.replayed > 0, "no fault was actually injected"
        assert chaos_b.replay_exhausted == 0
        assert [r for _, r in faulted] == [r for _, r in baseline]
        assert [o for o, _ in faulted] == [o for o, _ in baseline]
        assert chaos_b.stats()["replayed_requests"] == chaos_b.replayed

    async def test_budget_exhaustion_surfaces_error(self, engine):
        """A PERSISTENT fault (every tick fails) makes progress only
        through replays' admission prefills; once a victim burns
        tick_retry_limit replays it — and only it — sees 'error'."""
        failpoints.registry.arm("tick_fail", every=1)
        cfg = BatchingConfig(
            max_batch_size=2, kv_cache_max_seq=128, tick_retry_limit=1
        )
        batcher = ContinuousBatcher(engine, cfg)
        batcher.start()
        try:
            out, reason = await _drain(batcher, [3, 1, 4], 8)
        finally:
            await batcher.stop()
        assert reason == "error"
        # One token per admission (activation emits the prefill's
        # sample): initial + one replay = 2 tokens before giving up.
        assert len(out) == 2
        assert batcher.replayed == 1
        assert batcher.replay_exhausted == 1

    async def test_zero_retry_limit_restores_fail_fast(self, engine):
        failpoints.registry.arm("tick_fail", every=1, times=1)
        cfg = BatchingConfig(
            max_batch_size=2, kv_cache_max_seq=128, tick_retry_limit=0
        )
        batcher = ContinuousBatcher(engine, cfg)
        batcher.start()
        try:
            _, reason = await _drain(batcher, [3, 1, 4], 6)
            assert reason == "error"
            assert batcher.replayed == 0
            # The fault was times=1: the batcher must have recovered
            # for the next request (fresh cache, clean slots).
            out, reason = await _drain(batcher, [3, 1, 4], 6)
            assert reason in ("stop", "length")
            assert len(out) >= 1
        finally:
            await batcher.stop()

    async def test_admission_fault_contained_to_batch(self, engine):
        """admit_fail kills one admission round; the batch fails but
        the batcher keeps serving (no pool-wide collapse)."""
        failpoints.registry.arm("admit_fail", every=1, times=1)
        batcher = ContinuousBatcher(
            engine, BatchingConfig(max_batch_size=2, kv_cache_max_seq=128)
        )
        batcher.start()
        try:
            _, reason = await _drain(batcher, [4, 2], 4)
            assert reason == "error"
            out, reason = await _drain(batcher, [4, 2], 4)
            assert reason in ("stop", "length") and len(out) >= 1
        finally:
            await batcher.stop()

    async def test_admit_slow_injects_latency_not_failure(self, engine):
        """Latency injection: outputs are unchanged, the admission
        timing visibly absorbs the injected stall."""
        baseline, _ = await self._run_all(engine, [[3, 1, 4]], 6)
        failpoints.registry.arm("admit_slow", ms=30)
        slowed, batcher = await self._run_all(engine, [[3, 1, 4]], 6)
        assert slowed == baseline
        rounds = batcher.recorder.admission_snapshot()
        assert sum(r.duration_ms for r in rounds) >= 30.0


# ---------------------------------------------------------------------------
# Bounded admission / load shedding
# ---------------------------------------------------------------------------


class TestBoundedAdmission:
    async def test_overload_sheds_with_bounded_queue(self, engine):
        """The overload acceptance test: a submit storm against a tiny
        pool keeps the pending queue AT OR UNDER max_pending at every
        observation, sheds the excess with OverloadedError (counted in
        shed_requests), and completes every accepted request."""
        cap = 3
        cfg = BatchingConfig(
            max_batch_size=2, kv_cache_max_seq=128, max_pending=cap
        )
        batcher = ContinuousBatcher(engine, cfg)
        batcher.start()
        max_depth = 0
        tasks: list[asyncio.Task] = []
        shed = 0
        try:
            for i in range(24):
                try:
                    it = batcher.submit([7, 3, i % 11 + 1], 6, GREEDY, seed=i)
                except OverloadedError as exc:
                    assert exc.reason == "requests"
                    shed += 1
                else:
                    async def consume(it=it):
                        out, reason = [], None
                        async for ids, reason in it:
                            out.extend(ids)
                        return out, reason

                    tasks.append(asyncio.create_task(consume()))
                max_depth = max(max_depth, batcher.pending.qsize())
                if i % 3 == 2:
                    await asyncio.sleep(0.01)  # let the loop drain some
                    max_depth = max(max_depth, batcher.pending.qsize())
            results = await asyncio.gather(*tasks)
        finally:
            await batcher.stop()
        assert shed > 0, "storm never hit the cap — not an overload test"
        assert max_depth <= cap, f"queue grew past max_pending: {max_depth}"
        assert batcher.shed == shed
        stats = batcher.stats()
        assert stats["shed_requests"] == shed
        assert stats["queued_tokens"] == 0  # drained by the end
        for out, reason in results:
            assert reason in ("stop", "length")
            assert len(out) >= 1

    async def test_token_cap_sheds_by_queued_tokens(self, engine):
        cfg = BatchingConfig(
            max_batch_size=2, kv_cache_max_seq=128, max_queue_tokens=8
        )
        batcher = ContinuousBatcher(engine, cfg)
        batcher.start()
        try:
            # Occupy both slots with long decodes...
            busy = [
                asyncio.create_task(_drain(batcher, [5, i], 40, seed=i))
                for i in range(2)
            ]
            await asyncio.sleep(0.05)
            # ...then queue five-token prompts back to back. The first
            # is admissible on an empty queue; the second would push
            # the queued total to 10 > 8 and must shed by TOKENS.
            first = batcher.submit([8, 8, 8, 8, 8], 4, GREEDY, seed=7)
            with pytest.raises(OverloadedError) as exc_info:
                batcher.submit([9, 9, 9, 9, 9], 4, GREEDY, seed=8)
            assert exc_info.value.reason == "tokens"
            assert batcher.pending.token_count == 5
            out, reason = [], None
            async for ids, reason in first:
                out.extend(ids)
            assert reason in ("stop", "length")
            for t in busy:
                await t
        finally:
            await batcher.stop()

    async def test_expired_backlog_swept_before_admission(self, engine):
        """Under a saturated pool, queued requests past their deadline
        are dropped by the sweep WHILE the pool is still busy — they
        no longer wait for a free slot just to die on admission."""
        cfg = BatchingConfig(
            max_batch_size=2, kv_cache_max_seq=128, queue_deadline_ms=60.0
        )
        batcher = ContinuousBatcher(engine, cfg)
        batcher.start()
        try:
            busy = [
                asyncio.create_task(_drain(batcher, [5, i], 48, seed=i))
                for i in range(2)
            ]
            await asyncio.sleep(0.05)
            late = await asyncio.gather(
                _drain(batcher, [7, 7], 4, seed=9),
                _drain(batcher, [8, 8], 4, seed=10),
            )
            # The sweep must have expired them while the long decodes
            # still hold both slots — not after.
            assert not all(t.done() for t in busy), (
                "pool drained before the deadline fired; sweep not "
                "exercised"
            )
            results = await asyncio.gather(*busy)
        finally:
            await batcher.stop()
        assert [r for _, r in late] == ["timeout", "timeout"]
        assert all(r in ("stop", "length") for _, r in results)
        assert batcher.timed_out == 2

    async def test_tiered_overflow_before_shed(self, engine):
        """A full small tier spills into the larger tier's queue
        headroom; only when every fitting tier is at cap does the
        facade shed. (The batchers are never started: queues hold.)"""
        tiered = TieredBatcher(
            engine,
            BatchingConfig(
                kv_tiers=[[64, 2], [128, 2]], max_pending=1,
                pipeline_ticks="off",
            ),
        )
        short, long_ = tiered.tiers
        tiered.submit([1, 2], 4, GREEDY)
        assert short.pending.qsize() == 1
        tiered.submit([3, 4], 4, GREEDY)  # overflow → long tier
        assert long_.pending.qsize() == 1
        with pytest.raises(OverloadedError):
            tiered.submit([5, 6], 4, GREEDY)
        assert tiered.stats()["shed_requests"] == 1
        assert tiered.stats()["queued_tokens"] == 4


# ---------------------------------------------------------------------------
# Gateway degraded-health under sustained shed
# ---------------------------------------------------------------------------


class TestDegradedHealth:
    def _handler(self):
        from ggrmcp_tpu.gateway.handler import MCPHandler

        handler = MCPHandler.__new__(MCPHandler)  # shed tracking only
        handler._shed_seen = 0.0
        handler._shed_last_rise = float("-inf")
        return handler

    def test_shed_rise_marks_degraded_for_window(self):
        handler = self._handler()
        assert not handler._sustained_shed([])
        # protojson renders int64 counters as strings.
        stats = [{"target": "t", "shedRequests": "3"}]
        assert handler._sustained_shed(stats)
        # No new sheds, but still inside the window: stays degraded.
        assert handler._sustained_shed(stats)

    def test_window_expiry_clears_degraded(self):
        handler = self._handler()
        stats = [{"target": "t", "shedRequests": "3"}]
        assert handler._sustained_shed(stats)
        handler._shed_last_rise = time.monotonic() - 31.0
        assert not handler._sustained_shed(stats)
        # A FURTHER rise re-degrades.
        assert handler._sustained_shed(
            [{"target": "t", "shedRequests": "4"}]
        )

    def test_error_entries_ignored(self):
        handler = self._handler()
        assert not handler._sustained_shed(
            [{"target": "t", "error": "boom", "shedRequests": "9"}]
        )


# ---------------------------------------------------------------------------
# Client-disconnect cancellation (satellite)
# ---------------------------------------------------------------------------


class TestClientDisconnect:
    async def test_abandoned_iterator_frees_slot_within_a_tick(
        self, engine
    ):
        batcher = ContinuousBatcher(
            engine, BatchingConfig(max_batch_size=2, kv_cache_max_seq=128)
        )
        batcher.start()
        try:
            it = batcher.submit([3, 1, 4], 48, GREEDY)
            async for _ids, _reason in it:
                break  # consumer walks away mid-stream
            await it.aclose()  # deterministic abandonment (no GC race)
            deadline = time.perf_counter() + 5.0
            while (
                batcher._active_count() > 0
                and time.perf_counter() < deadline
            ):
                await asyncio.sleep(0.01)
            assert batcher._active_count() == 0
            assert batcher.pending.empty()
        finally:
            await batcher.stop()

    async def test_disconnected_request_never_enters_replay(self, engine):
        """A cancelled consumer's slot must not ride a tick failure
        back into the queue: the replay path drops cancelled victims
        instead of resurrecting work nobody is reading."""
        batcher = ContinuousBatcher(
            engine,
            BatchingConfig(
                max_batch_size=2, kv_cache_max_seq=128, tick_retry_limit=4
            ),
        )
        batcher.start()
        try:
            it = batcher.submit([3, 1, 4], 48, GREEDY)
            async for _ids, _reason in it:
                break
            await it.aclose()  # cancelled=True; slot may still be live
            failpoints.registry.arm("tick_fail", every=1, times=1)
            deadline = time.perf_counter() + 5.0
            while (
                batcher._active_count() > 0
                and time.perf_counter() < deadline
            ):
                await asyncio.sleep(0.01)
            assert batcher._active_count() == 0
            assert batcher.pending.empty()
            assert batcher.replayed == 0
            # The pool still serves after the fault + disconnect combo.
            out, reason = await _drain(batcher, [9, 9], 4, seed=3)
            assert reason in ("stop", "length") and len(out) >= 1
        finally:
            await batcher.stop()
