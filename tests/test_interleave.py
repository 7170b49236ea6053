"""Stall-free prefill/decode interleaving (batching.prefill_interleave):
greedy token parity against the serialized fused-grid path (flat and
tiered batchers), the one-fused-call stall bound for a 4k-token
admission landing mid-decode, and the new stall/interleave stats.

Deliberately NOT marked slow: this is the tier-1 regression net for the
fused tick+chunk scheduling mode (the configs below are sized so the
whole module stays in the fast-suite budget)."""

import asyncio

import pytest

from ggrmcp_tpu.core.config import BatchingConfig, MeshConfig, ServingConfig
from ggrmcp_tpu.models import llama
from ggrmcp_tpu.ops.sampling import SamplingConfig
from ggrmcp_tpu.serving.batching import ContinuousBatcher
from ggrmcp_tpu.serving.engine import GenerationEngine
from ggrmcp_tpu.serving.tiered import TieredBatcher

pytestmark = pytest.mark.interleave


@pytest.fixture(scope="module")
def engine():
    # tiny dims, 8k context: the 4096-token stall-bound admission runs
    # at a REAL long-prompt length while staying CPU-fast.
    return GenerationEngine(
        llama.CONFIGS["tiny-llama-8k"],
        ServingConfig(
            mesh=MeshConfig(tensor=2, data=0),
            batching=BatchingConfig(max_batch_size=4, kv_cache_max_seq=256),
        ),
    )


# No eos token (2) anywhere: parity must compare full-length streams.
SHORT = [5, 6, 7]
MEDIUM = [3 + (i % 200) for i in range(80)]
LONG = [3 + (i * 7 % 500) for i in range(100)]


async def _drain(batcher, prompt, max_new, seed=0, first_event=None):
    out, reason = [], None
    async for ids, reason in batcher.submit(
        prompt, max_new, SamplingConfig(), seed=seed
    ):
        if first_event is not None and not first_event.is_set():
            first_event.set()
        out.extend(ids)
    return out, reason


def _cfg(mode, **kw):
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("kv_cache_max_seq", 256)
    kw.setdefault("prefill_chunk", 32)
    kw.setdefault("prefill_interleave_rows", 2)
    # One token per tick, synchronous: the emission stream is the
    # per-tick observable the stall bound is stated over.
    kw.setdefault("decode_steps_per_tick", 1)
    kw.setdefault("pipeline_ticks", "off")
    return BatchingConfig(prefill_interleave=mode, **kw)


class TestGreedyParity:
    async def _run_flat(self, engine, mode):
        """One short request decoding, then a long prompt admitted
        mid-decode — the interleave-vs-serialized divergence point."""
        batcher = ContinuousBatcher(engine, _cfg(mode))
        batcher.start()
        try:
            started = asyncio.Event()
            short_task = asyncio.create_task(
                _drain(batcher, SHORT, 24, first_event=started)
            )
            await started.wait()
            long_out = await _drain(batcher, LONG, 8)
            short_out = await short_task
        finally:
            await batcher.stop()
        return batcher, short_out, long_out

    async def test_flat_outputs_bit_identical(self, engine):
        b_off, short_off, long_off = await self._run_flat(engine, "off")
        b_on, short_on, long_on = await self._run_flat(engine, "on")
        # The interleaved path actually engaged (otherwise this test
        # proves nothing): the long prompt rode tick-fused chunks.
        assert b_off.interleaved_admissions == 0
        assert b_on.interleaved_admissions == 1
        assert b_on.interleaved_chunks >= 4  # ceil(100 / 32)
        assert short_on == short_off
        assert long_on == long_off
        assert long_on[1] in ("stop", "length")

    async def _run_tiered(self, engine, mode):
        """Same scenario inside the bigger tier of a TieredBatcher: a
        medium prompt decoding there, a long prompt admitted behind it."""
        batcher = TieredBatcher(
            engine, _cfg(mode, kv_tiers=[[64, 2], [256, 2]])
        )
        batcher.start()
        try:
            started = asyncio.Event()
            med_task = asyncio.create_task(
                _drain(batcher, MEDIUM, 16, first_event=started)
            )
            await started.wait()
            long_out = await _drain(batcher, LONG, 8)
            med_out = await med_task
        finally:
            await batcher.stop()
        return batcher, med_out, long_out

    async def test_tiered_outputs_bit_identical(self, engine):
        b_off, med_off, long_off = await self._run_tiered(engine, "off")
        b_on, med_on, long_on = await self._run_tiered(engine, "on")
        # Both the medium and long prompt route to the 256 tier; the
        # long one must have interleaved behind the medium's decode.
        assert sum(t.interleaved_admissions for t in b_off.tiers) == 0
        assert sum(t.interleaved_admissions for t in b_on.tiers) == 1
        assert med_on == med_off
        assert long_on == long_off

    async def test_idle_pool_uses_serialized_path(self, engine):
        """With nothing decoding, a long prompt keeps today's one-call
        fused grid even under prefill_interleave=on (T round-trips
        would be pure regression on an idle pool)."""
        batcher = ContinuousBatcher(engine, _cfg("on"))
        batcher.start()
        try:
            out, reason = await _drain(batcher, LONG, 4)
        finally:
            await batcher.stop()
        assert reason in ("stop", "length")
        assert batcher.interleaved_admissions == 0


class TestStallBound:
    async def test_4k_admission_gaps_at_most_one_fused_call(self, engine):
        """A 4096-token admission landing mid-decode never gaps an
        active slot's token emission by more than ~one fused call
        (chunk + tick), not the full prompt prefill. Structural bound:
        the prefill split into ceil(4096/512)=8 tick-fused chunks, so
        the worst emission gap must stay well under the admission's
        total duration — the serialized path stalls for all of it."""
        long4k = [3 + (i * 11 % 500) for i in range(4096)]
        batcher = ContinuousBatcher(
            engine,
            _cfg(
                "on", max_batch_size=2, kv_cache_max_seq=8192,
                prefill_chunk=512, prefill_interleave_rows=1,
            ),
        )
        # Steady-state stalls, not compile time: every program a live
        # request would hit compiles here.
        batcher.warmup()
        batcher.start()
        try:
            started = asyncio.Event()
            import time

            short_task = asyncio.create_task(
                _drain(batcher, SHORT, 48, first_event=started)
            )
            await started.wait()
            t0 = time.perf_counter()
            long_task = asyncio.create_task(_drain(batcher, long4k, 4))
            # First chunk of the admission is in flight from the next
            # tick; time to the long request's first emitted token is
            # (a little more than) the whole admission duration.
            long_out = await long_task
            admission_s = time.perf_counter() - t0
            short_out = await short_task
        finally:
            await batcher.stop()
        assert short_out[1] in ("stop", "length")
        assert long_out[1] in ("stop", "length")
        assert batcher.interleaved_admissions == 1
        assert batcher.interleaved_chunks >= 8
        stalls = batcher.stall_snapshot()
        assert stalls, "active slot emitted during the admission"
        worst_ms = max(stalls)
        # One fused call is ~1/8th of the admission; 0.6x leaves wide
        # margin for scheduler noise while still failing hard if the
        # admission serialized (worst gap would be ~1.0x).
        assert worst_ms < 0.6 * admission_s * 1000.0, (
            f"worst emission gap {worst_ms:.0f}ms vs admission "
            f"{admission_s * 1000.0:.0f}ms — decode stalled for the "
            f"full prefill"
        )
        # The long request's prefill rode the ticks: its admission round
        # only queued it, and its wait shows as prefill, not pending.
        assert "interleave_queued" in [
            r.family for r in batcher.recorder.admission_snapshot()
        ]
        long_rec = max(
            batcher.recorder.request_snapshot(),
            key=lambda r: r.prompt_tokens,
        )
        assert long_rec.prefill_ms > long_rec.pending_ms


class TestConfig:
    def test_validation(self):
        from ggrmcp_tpu.core import config as cfgmod

        cfg = cfgmod.default()
        cfg.serving.batching.prefill_interleave = "maybe"
        with pytest.raises(ValueError, match="prefill_interleave"):
            cfg.validate()
        cfg.serving.batching.prefill_interleave = "on"
        cfg.serving.batching.prefill_interleave_rows = 0
        with pytest.raises(ValueError, match="prefill_interleave_rows"):
            cfg.validate()
        cfg.serving.batching.prefill_interleave_rows = 4
        cfg.validate()

    def test_stats_keys_cover_proto(self):
        """The new stall/interleave stats ride the ServingStats proto
        (sidecar constructs the response with **stats — a drifted key
        fails loudly there; this pins it at the unit level)."""
        from ggrmcp_tpu.rpc.pb import serving_pb2

        fields = {
            f.name
            for f in serving_pb2.ServingStatsResponse.DESCRIPTOR.fields
        }
        for key in ("interleaved_chunks", "interleaved_admissions"):
            assert key in fields
        # The stall gauges were lifetime percentiles with no reader
        # (PR 26): the records stay in process (stall_snapshot).
        assert not {k for k in fields if k.startswith("decode_stall")}
