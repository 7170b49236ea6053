"""Device-memory ledger + compile watcher net (ISSUE 13, marker `mem`).

Covers, bottom-up:
- ledger unit behavior: registration, scoped byte accounting, the
  disabled (obs-off) no-op contract, double-registration accounting
- THE closure contract: the component sum reconciles against JAX
  live-buffer totals BY ARRAY IDENTITY — attributed + unattributed ==
  live exactly, and unattributed == 0 for a quiescent serving stack —
  across plain/paged/tiered/grammar configs, all on the
  2-device CPU tensor mesh (the TP stand-in, like tests/test_tp.py)
- compile watcher: a genuine recompile (new shape after the warmup
  mark) increments the counter, emits the WARNING log line, and lands
  a timeline instant; steady-state serving (warmed shapes only) shows
  ZERO post-warmup compiles
- the gateway surface on BOTH HTTP impls: GET /debug/memory
  (per-component bytes + reconciliation + compile ring), POST
  /debug/profile (per-backend capture artifact paths), and /metrics
  carrying the {component}-labeled gateway_backend_memory_bytes family
  plus the gateway_backend_compile_* gauges and the TPOT histogram
"""

import asyncio
import gc

import pytest

from ggrmcp_tpu.core.config import (
    BatchingConfig,
    MeshConfig,
    ObservabilityConfig,
    ServingConfig,
)
from ggrmcp_tpu.models import llama
from ggrmcp_tpu.ops.sampling import SamplingConfig
from ggrmcp_tpu.serving import compile_watcher
from ggrmcp_tpu.serving.batching import ContinuousBatcher
from ggrmcp_tpu.serving.engine import GenerationEngine
from ggrmcp_tpu.serving.memory_ledger import MemoryLedger
from ggrmcp_tpu.serving.tiered import TieredBatcher

pytestmark = pytest.mark.mem

GREEDY = SamplingConfig(temperature=0.0)
TINY = llama.CONFIGS["tiny-llama"]


def _serving(**kw) -> ServingConfig:
    # tensor=2 on the virtual 8-device CPU mesh: every closure test
    # runs tensor-parallel (the TP acceptance config).
    kw.setdefault("mesh", MeshConfig(tensor=2, data=0))
    kw.setdefault(
        "batching",
        BatchingConfig(
            max_batch_size=2, kv_cache_max_seq=128, max_queue_delay_ms=2.0
        ),
    )
    return ServingConfig(**kw)


async def _drive(batcher, prompts, max_new=4, grammar=None):
    loop = asyncio.get_running_loop()
    await loop.run_in_executor(None, batcher.warmup)
    batcher.start()

    async def consume(i, p):
        out = []
        async for ids, _reason in batcher.submit(
            list(p), max_new, GREEDY, seed=i, grammar=grammar
        ):
            out.extend(ids)
        return out

    try:
        return await asyncio.gather(
            *(consume(i, p) for i, p in enumerate(prompts))
        )
    finally:
        await batcher.stop()


async def _closed_stack(serving, prompts, tiered=False, grammar=None):
    """Build a fresh engine + batcher against a live-array BASELINE,
    drive it, and return (engine, batcher, reconcile result). The
    baseline scopes the closure to this stack's own allocations —
    other tests' module-scoped engines stay out of the census."""
    gc.collect()
    base = MemoryLedger.live_ids()
    engine = GenerationEngine(TINY, serving)
    batcher = (
        TieredBatcher(engine, serving.batching)
        if tiered else ContinuousBatcher(engine, serving.batching)
    )
    await _drive(batcher, prompts, grammar=grammar)
    gc.collect()
    rec = engine.ledger.reconcile(baseline_ids=base)
    return engine, batcher, rec


def _assert_closed(rec):
    """The closure invariant: every live byte this stack allocated is
    attributed to exactly one named component."""
    assert rec["attributed_bytes"] + rec["unattributed_bytes"] == (
        rec["live_bytes"]
    )
    assert rec["double_registered"] == 0
    assert rec["unattributed_bytes"] == 0, (
        f"ledger drifted from reality: "
        f"{rec['unattributed_bytes']} unattributed bytes in "
        f"{len(rec['unattributed_arrays'])} arrays — "
        f"{rec['unattributed_arrays'][:5]}"
    )


class TestMemoryLedger:
    def test_register_and_scoped_bytes(self):
        import jax.numpy as jnp

        led = MemoryLedger(enabled=True)
        a = jnp.zeros((4, 4), jnp.float32)
        b = jnp.zeros((8,), jnp.int32)
        led.register("kv_arena", lambda: a)
        led.register("kv_arena", lambda: b, scope="tier-128")
        comp = led.component_bytes()
        assert comp[("", "kv_arena")] == a.nbytes
        assert comp[("tier-128", "kv_arena")] == b.nbytes
        assert led.base_bytes()["kv_arena"] == a.nbytes + b.nbytes
        assert led.total_bytes() == a.nbytes + b.nbytes

    def test_supplier_reads_live_attributes(self):
        """A rebuild reassigns the attribute; the next read must see
        the NEW array — the tick-failure-rebuild contract."""
        import jax.numpy as jnp

        class Holder:
            pass

        h = Holder()
        h.cache = jnp.zeros((2,), jnp.float32)
        led = MemoryLedger(enabled=True)
        led.register("kv_arena", lambda: h.cache)
        before = led.total_bytes()
        h.cache = jnp.zeros((64,), jnp.float32)
        assert led.total_bytes() == 64 * 4 != before

    def test_disabled_ledger_stores_and_computes_nothing(self):
        import jax.numpy as jnp

        led = MemoryLedger(enabled=False)
        led.register("kv_arena", lambda: jnp.zeros((4,)))
        assert led.component_bytes() == {}
        assert led.base_bytes() == {}
        assert led.total_bytes() == 0
        assert led._suppliers == {}

    def test_double_registration_attributes_once(self):
        import jax.numpy as jnp

        led = MemoryLedger(enabled=True)
        arr = jnp.zeros((16,), jnp.float32)
        led.register("weights", lambda: arr)
        led.register("kv_arena", lambda: arr)  # the drift this counts
        rec = led.reconcile()
        assert rec["double_registered"] == 1
        # Attributed once (first registration wins), never summed twice.
        assert rec["components"]["weights"] == arr.nbytes
        assert rec["components"]["kv_arena"] == 0

    def test_none_supplier_and_host_arrays_ignored(self):
        import numpy as np

        led = MemoryLedger(enabled=True)
        led.register("ilv_mini", lambda: None)
        led.register("tick_state", lambda: np.zeros((8,)))  # host RAM
        assert led.component_bytes() == {
            ("", "ilv_mini"): 0, ("", "tick_state"): 0,
        }


class TestClosure:
    """Component sum == JAX live-buffer totals, by identity, across
    the serving configs (acceptance: paged/tiered/grammar/TP —
    every config here runs on the 2-device tensor mesh)."""

    async def test_plain_tp(self):
        _eng, batcher, rec = await _closed_stack(
            _serving(), [[5, 6, 7], [9, 10, 11]]
        )
        _assert_closed(rec)
        comps = rec["components"]
        assert comps["weights"] > 0
        assert comps["kv_arena"] > 0
        assert comps["tick_state"] > 0  # device twins set by real ticks
        assert comps["grammar_arena"] > 0  # accept-all tables uploaded
        # The ServingStats fields mirror the same numbers.
        stats = batcher.stats()
        assert stats["memory_weights_bytes"] == comps["weights"]
        assert stats["memory_kv_arena_bytes"] == comps["kv_arena"]

    async def test_paged(self):
        preamble = list(range(3, 35))
        _eng, batcher, rec = await _closed_stack(
            _serving(batching=BatchingConfig(
                max_batch_size=2, kv_cache_max_seq=128,
                max_queue_delay_ms=2.0,
                paged_kv="on", paged_kv_page_size=16,
            )),
            [preamble + [70 + i] for i in range(2)],
        )
        _assert_closed(rec)
        assert rec["components"]["block_tables"] > 0
        assert batcher.stats()["memory_block_tables_bytes"] > 0

    async def test_grammar_constrained(self):
        from ggrmcp_tpu.grammar import compile_schema

        g = compile_schema(
            {"type": "integer"}, vocab_size=TINY.vocab_size
        )
        _eng, batcher, rec = await _closed_stack(
            _serving(), [[4, 2]], grammar=g
        )
        _assert_closed(rec)
        assert rec["components"]["grammar_arena"] > 0
        assert batcher.stats()["grammar_masked_tokens"] > 0

    async def test_tiered_scopes_sum(self):
        serving = _serving(batching=BatchingConfig(
            max_batch_size=4, kv_cache_max_seq=256,
            max_queue_delay_ms=2.0, kv_tiers=[[128, 2], [256, 2]],
        ))
        _eng, batcher, rec = await _closed_stack(
            serving, [[5, 6, 7], [9, 10, 11]], tiered=True
        )
        _assert_closed(rec)
        comps = rec["components"]
        assert comps["tier-128/kv_arena"] > 0
        assert comps["tier-256/kv_arena"] > 0
        # The facade SUMS per-tier arenas and MAXes the engine-level
        # weight component (one engine, not one per tier).
        stats = batcher.stats()
        assert stats["memory_kv_arena_bytes"] == (
            comps["tier-128/kv_arena"] + comps["tier-256/kv_arena"]
        )
        assert stats["memory_weights_bytes"] == comps["weights"]

    async def test_obs_off_allocates_and_computes_nothing(self):
        serving = _serving(
            observability=ObservabilityConfig(enabled=False)
        )
        engine = GenerationEngine(TINY, serving)
        batcher = ContinuousBatcher(engine, serving.batching)
        await _drive(batcher, [[5, 6, 7]])
        assert engine.ledger.enabled is False
        assert engine.ledger._suppliers == {}
        assert engine.ledger.component_bytes() == {}
        stats = batcher.stats()
        assert stats["memory_weights_bytes"] == 0
        assert stats["memory_kv_arena_bytes"] == 0
        # Tick records (none — recorder off) carry no memory snapshot.
        assert batcher.recorder.tick_snapshot() == []


class TestCompileWatcher:
    def test_compile_counts_names_and_warm_line(self, caplog):
        import jax
        import jax.numpy as jnp

        w = compile_watcher.watcher
        w.install()
        w.mark_cold()
        before = w.stats()

        def fresh_fn(x):
            return x * 3 + 1

        jax.jit(fresh_fn)(jnp.ones((13,)))
        mid = w.stats()
        assert mid["compile_count"] > before["compile_count"]
        assert any(
            "fresh_fn" in c.fn_name for c in w.snapshot()
        ), [c.fn_name for c in w.snapshot()]
        assert mid["compile_post_warmup"] == 0

        # Past the warm mark, a NEW shape is a steady-state recompile:
        # counter + WARNING log line + flagged ring entry.
        w.mark_warm()
        with caplog.at_level("WARNING", logger="ggrmcp.serving.compile"):
            jax.jit(fresh_fn)(jnp.ones((29,)))
        after = w.stats()
        assert after["compile_post_warmup"] >= 1
        assert any(
            "steady-state recompile" in r.message for r in caplog.records
        )
        assert any(c.post_warmup for c in w.snapshot())
        w.mark_cold()

    async def test_steady_state_serving_has_zero_recompiles(self):
        """The serving contract: after warmup, repeated same-shape
        traffic compiles NOTHING."""
        serving = _serving()
        engine = GenerationEngine(TINY, serving)
        batcher = ContinuousBatcher(engine, serving.batching)
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(None, batcher.warmup)
        batcher.start()
        try:
            async def consume(i):
                async for _ids, _r in batcher.submit(
                    [5, 6, 7], 4, GREEDY, seed=i
                ):
                    pass

            # Shakedown calls compile the first-traffic stragglers the
            # warmup ladder can't reach (tiny eager-op programs like
            # the device-twin token patch, which only exists from the
            # SECOND admission on — real compiles, correctly counted),
            # then the line is drawn. Sequential calls keep slot
            # placement deterministic.
            for i in range(3):
                await consume(i)
            compile_watcher.watcher.mark_warm()
            for i in range(4):
                await consume(10 + i)
            stats = compile_watcher.watcher.stats()
            assert stats["compile_post_warmup"] == 0, (
                "steady-state serving recompiled: "
                f"{[c.fn_name for c in compile_watcher.watcher.snapshot() if c.post_warmup]}"
            )
        finally:
            await batcher.stop()
            compile_watcher.watcher.mark_cold()

    def test_compile_instant_renders_on_the_timeline(self):
        from ggrmcp_tpu.serving.compile_watcher import CompileEvent
        from ggrmcp_tpu.serving.timeline import build_timeline
        from tests.test_timeline import _validate_chrome_trace

        rec = CompileEvent(
            fn_name="jit(_tick_impl)", t_wall=1000.0,
            duration_ms=42.0, post_warmup=True,
        )
        doc = build_timeline([], [{
            "target": "side:1", "enabled": True,
            "ticks": [], "requests": [],
            "compiles": [rec.to_dict()],
        }])
        _validate_chrome_trace(doc)
        [ev] = [
            e for e in doc["traceEvents"] if e.get("cat") == "compile"
        ]
        assert ev["ph"] == "i"
        assert ev["name"] == "jit(_tick_impl)"
        assert ev["args"]["postWarmup"] is True
        assert ev["s"] == "g"  # post-warmup instants draw full-height


# ---------------------------------------------------------------------------
# Gateway surface (both HTTP impls, real sidecar)
# ---------------------------------------------------------------------------


class TestMemoryDebugSurface:
    @pytest.mark.parametrize("impl", ["fastlane", "aiohttp"])
    async def test_debug_memory_endpoint(self, impl):
        from tests.test_observability import _generate_call, observed_env

        async with observed_env(impl) as (_side, _gw, client):
            await _generate_call(client, f"trace-mem-{impl}")
            resp = await client.get("/debug/memory")
            assert resp.status == 200
            body = await resp.json()
            assert body["reconcile"] is True
            [backend] = body["backends"]
            assert backend["enabled"] is True
            # protojson omits zero scalars — a 0-byte component has no
            # "bytes" key at all.
            comps = {
                (c.get("scope", ""), c["component"]):
                    int(c.get("bytes", 0))
                for c in backend["components"]
            }
            assert comps[("", "weights")] > 0
            assert comps[("", "kv_arena")] > 0
            total = int(backend["totalBytes"])
            assert total == sum(comps.values()) > 0
            # Reconciliation fields present (process-wide census: other
            # in-process test engines may contribute unattributed
            # bytes, so only structure is pinned here — the closure
            # itself is asserted against baselines in TestClosure).
            assert int(backend["liveBytes"]) >= total
            # Compile watcher rides the same body.
            assert int(backend["compileCount"]) > 0
            assert backend.get("compiles"), "empty compile ring"

            # ?reconcile=0 skips the live-array census.
            body = await (
                await client.get("/debug/memory?reconcile=0")
            ).json()
            assert body["reconcile"] is False
            assert "liveBytes" not in body["backends"][0]  # protojson 0

    @pytest.mark.parametrize("impl", ["fastlane", "aiohttp"])
    async def test_debug_profile_fans_out(self, impl):
        import os

        from tests.test_observability import observed_env

        async with observed_env(impl) as (_side, _gw, client):
            resp = await client.post(
                "/debug/profile?duration_ms=20&label=mem-test"
            )
            assert resp.status == 200
            body = await resp.json()
            assert body["durationMs"] == 20
            [backend] = body["backends"]
            assert "error" not in backend, backend
            assert os.path.isdir(backend["outputPath"])
            # GET is not a capture trigger.
            resp = await client.get("/debug/profile")
            assert resp.status == 405

    async def test_metrics_carry_memory_family_and_compile_gauges(self):
        from prometheus_client.parser import text_string_to_metric_families

        from tests.test_observability import _generate_call, observed_env

        async with observed_env("fastlane") as (_side, _gw, client):
            await _generate_call(client, "trace-mem-metrics", max_new=4)
            text = await (await client.get("/metrics")).text()
        families = {
            f.name: f for f in text_string_to_metric_families(text)
        }
        mem = families["gateway_backend_memory_bytes"]
        by_comp = {
            s.labels["component"]: s.value for s in mem.samples
        }
        assert by_comp["weights"] > 0
        assert by_comp["kv_arena"] > 0
        assert set(by_comp) >= {
            "weights", "lora", "kv_arena", "block_tables", "ilv_mini",
            "grammar_arena", "tick_state",
        }
        assert families["gateway_backend_compile_count"].samples[0].value > 0
        assert "gateway_backend_compile_post_warmup" in families
        # The TPOT histogram (satellite): multi-token requests observe.
        tpot = families["gateway_backend_tpot_ms"]
        count = next(
            s.value for s in tpot.samples if s.name.endswith("_count")
        )
        assert count >= 1.0

    async def test_stats_rpc_carries_memory_and_compile_fields(self):
        from tests.test_observability import _generate_call, observed_env

        async with observed_env("fastlane") as (_side, _gw, client):
            await _generate_call(client, "trace-mem-stats", max_new=4)
            stats = await (await client.get("/stats")).json()
        [serving] = stats["serving"]
        assert int(serving["memoryWeightsBytes"]) > 0
        assert int(serving["memoryKvArenaBytes"]) > 0
        assert int(serving["compileCount"]) > 0
        assert int(serving["tpotMsCount"]) >= 1
