"""Tenant & SLO accounting plane net (serving/slo.py,
docs/observability.md "SLO accounting").

What this file proves:
- goodput-partition CLOSURE: met + violated + unevaluated ==
  total_requests EXACTLY, per class, across plain/paged/tiered/
  grammar batcher configs and under chaos (submit-storm shed, queue
  timeout, tick-failure replay) — a shed or a timeout lands TYPED in
  the partition, never silently dropped from the total
- burn-rate math: multi-window burn from windowed cumulative deltas
  with counter-regression re-baseline (`windowed_delta`), ~1 s
  snapshot coalescing, and EXACT recombination across tiers (summed
  window deltas, never averaged rates)
- the cardinality-bounded tenant table: 10k-tenant churn never grows
  past top_k, evictions fold into the `~overflow` row, counters
  conserve; VTC weighted-token math; LRU eviction order
- obs-off zero-work: disabled, hooks no-op and stats() is empty
- identity precedence: sidecar fallback chain (explicit field >
  x-tenant-id metadata > adapter > x-adapter-id > x-session-id >
  "default") and the gateway's header→argument binding (explicit
  arguments win)
- the HTTP surfaces on BOTH impls: GET /debug/slo shape + closure,
  /debug/requests?tenant= server-side filtering, and the
  class-labeled latency/goodput/burn/target families on /metrics
"""

import asyncio

import pytest

from ggrmcp_tpu.core.config import (
    BatchingConfig,
    MeshConfig,
    ObservabilityConfig,
    ServingConfig,
    SloConfig,
)
from ggrmcp_tpu.grammar import compile_schema
from ggrmcp_tpu.models import llama
from ggrmcp_tpu.ops.sampling import SamplingConfig
from ggrmcp_tpu.rpc.pb import serving_pb2
from ggrmcp_tpu.serving.batching import ContinuousBatcher, OverloadedError
from ggrmcp_tpu.serving.engine import GenerationEngine
from ggrmcp_tpu.serving.slo import (
    ERROR_BUDGET,
    NORMAL_FINISHES,
    OVERFLOW_TENANT,
    SloAccount,
    TenantTable,
    windowed_delta,
)
from ggrmcp_tpu.serving.tiered import TieredBatcher
from ggrmcp_tpu.utils import failpoints

pytestmark = pytest.mark.slo

GREEDY = SamplingConfig(temperature=0.0)
VOCAB = llama.CONFIGS["tiny-llama"].vocab_size

# Two classes that bracket CPU-mesh latency so both partitions fill
# deterministically: "fast" targets are microseconds (every normal
# finish violates), "lax" targets are ~11 days (every normal finish
# meets). default_class exercises the unknown-class degrade.
_CLASSES = {
    "fast": {"ttft_p99_ms": 0.001, "tpot_p99_ms": 0.001},
    "lax": {"ttft_p99_ms": 1e9, "tpot_p99_ms": 1e9},
}


def _slo_cfg(**kw):
    kw.setdefault("default_class", "lax")
    kw.setdefault("classes", {k: dict(v) for k, v in _CLASSES.items()})
    kw.setdefault("burn_windows_s", [60.0, 3600.0])
    return SloConfig(**kw)


def _engine(**kw):
    return GenerationEngine(
        llama.CONFIGS["tiny-llama"],
        ServingConfig(
            mesh=MeshConfig(tensor=2, data=0), slo=_slo_cfg(), **kw
        ),
    )


@pytest.fixture(scope="module")
def engine():
    return _engine()


@pytest.fixture(autouse=True)
def clean_failpoints():
    failpoints.registry.disarm()
    yield
    failpoints.registry.disarm()


async def _drain(batcher, prompt, max_new, seed=0, **kw):
    out, reason = [], None
    async for ids, reason in batcher.submit(
        prompt, max_new, GREEDY, seed=seed, **kw
    ):
        out.extend(ids)
    return out, reason


def _classes_by_name(stats):
    return {e["name"]: e for e in stats["slo_classes"]}


def _assert_closure(stats, expect_total):
    """THE invariant: per class AND across classes, the partition sums
    to the total exactly."""
    total = 0
    for entry in stats["slo_classes"]:
        part = entry["met"] + entry["violated"] + entry["unevaluated"]
        assert part == entry["total_requests"], entry
        total += entry["total_requests"]
    assert total == expect_total
    assert (
        stats["slo_met_total"]
        + stats["slo_violated_total"]
        + stats["slo_unevaluated_total"]
        == expect_total
    )


# ---------------------------------------------------------------------------
# windowed_delta — the shared windowed-histogram primitive
# ---------------------------------------------------------------------------


class TestWindowedDelta:
    def test_elementwise_delta(self):
        assert windowed_delta([1, 2, 3], [4, 4, 10]) == [3, 2, 7]

    def test_missing_prev_is_none(self):
        assert windowed_delta(None, [1, 2]) is None

    def test_shape_change_is_none(self):
        # Bucket-bound config change between snapshots: re-baseline.
        assert windowed_delta([1, 2], [1, 2, 3]) is None

    def test_counter_regression_is_none(self):
        # Process restart: cumulative counters went backwards — a
        # garbage negative delta must never be reported.
        assert windowed_delta([5, 5], [9, 4]) is None

    def test_zero_delta_is_not_none(self):
        assert windowed_delta([3, 3], [3, 3]) == [0, 0]


# ---------------------------------------------------------------------------
# SloAccount units: classification, closure, burn, proto round-trip
# ---------------------------------------------------------------------------


class TestSloClassification:
    def make(self, **kw):
        return SloAccount(_slo_cfg(**kw))

    def test_unadmitted_is_unevaluated(self):
        acct = self.make()
        out = acct.record_terminal("lax", "timeout", admitted=False)
        assert out == "unevaluated"
        c = _classes_by_name(acct.stats())["lax"]
        assert (c["unevaluated"], c["met"], c["violated"]) == (1, 0, 0)
        # No latency to judge: the class histograms stay empty.
        assert c["ttft_ms_count"] == 0 and c["e2e_ms_count"] == 0

    def test_normal_finish_within_targets_is_met(self):
        acct = self.make()
        for reason in sorted(NORMAL_FINISHES):
            out = acct.record_terminal(
                "lax", reason, admitted=True,
                ttft_ms=5.0, tpot_ms=2.0, e2e_ms=20.0,
            )
            assert out == "met", reason
        c = _classes_by_name(acct.stats())["lax"]
        assert c["met"] == len(NORMAL_FINISHES)
        assert c["ttft_ms_count"] == len(NORMAL_FINISHES)

    def test_ttft_over_target_is_violated(self):
        acct = self.make()
        out = acct.record_terminal(
            "fast", "stop", admitted=True,
            ttft_ms=5.0, tpot_ms=0.0005, e2e_ms=10.0,
        )
        assert out == "violated"

    def test_tpot_over_target_is_violated(self):
        acct = self.make()
        out = acct.record_terminal(
            "fast", "stop", admitted=True,
            ttft_ms=0.0005, tpot_ms=5.0, e2e_ms=10.0,
        )
        assert out == "violated"

    def test_abnormal_finish_is_violated_even_when_fast(self):
        # Admitted + died: service was attempted, the tenant got no
        # good answer — typed as violated regardless of latency.
        acct = self.make()
        for reason in ("timeout", "error", "cancelled", "overloaded"):
            out = acct.record_terminal(
                "lax", reason, admitted=True,
                ttft_ms=1.0, tpot_ms=1.0, e2e_ms=5.0,
            )
            assert out == "violated", reason

    def test_missing_latency_judged_on_what_exists(self):
        # One-token unary finish: no decode interval → TPOT not
        # judged; absent TTFT (no first-token stamp) → TTFT not judged.
        acct = self.make()
        assert acct.record_terminal(
            "fast", "stop", admitted=True,
            ttft_ms=None, tpot_ms=None, e2e_ms=3.0,
        ) == "met"

    def test_unknown_class_degrades_to_default(self):
        acct = self.make()
        assert acct.resolve("no-such-class") == "lax"
        acct.record_terminal("no-such-class", "stop", admitted=True,
                             e2e_ms=1.0)
        assert _classes_by_name(acct.stats())["lax"]["met"] == 1

    def test_every_configured_class_always_exported(self):
        # Zero-traffic classes export zeros — stable label sets.
        stats = self.make().stats()
        assert sorted(_classes_by_name(stats)) == ["fast", "lax"]
        _assert_closure(stats, 0)

    def test_shed_and_uncount(self):
        acct = self.make()
        acct.record_shed("lax")
        acct.record_shed("lax")
        acct.uncount_shed("lax")
        c = _classes_by_name(acct.stats())["lax"]
        assert c["unevaluated"] == 1 and c["total_requests"] == 1
        # Never goes negative.
        acct.uncount_shed("lax")
        acct.uncount_shed("lax")
        assert _classes_by_name(acct.stats())["lax"]["unevaluated"] == 0

    def test_mixed_traffic_closure(self):
        acct = self.make()
        for i in range(30):
            if i % 5 == 0:
                acct.record_shed("fast" if i % 2 else "lax")
            else:
                acct.record_terminal(
                    "fast" if i % 2 else "lax",
                    "stop" if i % 3 else "timeout",
                    admitted=i % 7 != 0,
                    ttft_ms=float(i), tpot_ms=1.0, e2e_ms=float(i),
                )
        _assert_closure(acct.stats(), 30)

    def test_stats_round_trip_through_proto(self):
        # The fragment uses proto field names verbatim — the sidecar
        # builds ServingStatsResponse(**stats) from it.
        acct = self.make()
        acct.record_terminal("lax", "stop", admitted=True,
                             ttft_ms=3.0, tpot_ms=1.0, e2e_ms=9.0)
        acct.record_shed("fast")
        msg = serving_pb2.ServingStatsResponse(**acct.stats())
        assert msg.slo_met_total == 1
        assert msg.slo_unevaluated_total == 1
        by_name = {c.name: c for c in msg.slo_classes}
        assert by_name["lax"].met == 1
        assert by_name["lax"].ttft_ms_count == 1
        assert by_name["fast"].unevaluated == 1
        assert list(by_name["lax"].burn_window_s) == [60.0, 3600.0]


class TestBurnRate:
    """Burn = (violated_delta / total_delta) / 0.01 per trailing
    window, from the ~1 s-coalesced snapshot ring — fake clock."""

    def make(self, windows=(60.0,)):
        t = [0.0]
        acct = SloAccount(
            _slo_cfg(burn_windows_s=list(windows)), clock=lambda: t[0]
        )
        return acct, t

    def _record(self, acct, met=0, violated=0):
        for _ in range(met):
            acct.record_terminal("lax", "stop", admitted=True,
                                 ttft_ms=1.0, tpot_ms=1.0, e2e_ms=1.0)
        for _ in range(violated):
            acct.record_terminal("lax", "timeout", admitted=True,
                                 ttft_ms=1.0, tpot_ms=1.0, e2e_ms=1.0)

    def test_burn_inside_window(self):
        acct, t = self.make()
        self._record(acct, met=5, violated=5)
        t[0] = 30.0  # every event inside the 60 s window
        entry = _classes_by_name(acct.stats())["lax"]
        # 5 violated / 10 total = 0.5 violation rate / 0.01 budget.
        assert entry["burn_rate"] == [pytest.approx(0.5 / ERROR_BUDGET)]

    def test_burn_decays_to_zero_when_traffic_ages_out(self):
        acct, t = self.make()
        self._record(acct, met=5, violated=5)
        t[0] = 100.0  # the t=0 snapshot is now the at-edge baseline
        entry = _classes_by_name(acct.stats())["lax"]
        assert entry["burn_rate"] == [0.0]

    def test_zero_traffic_burn_is_zero_not_nan(self):
        acct, _ = self.make()
        assert _classes_by_name(acct.stats())["lax"]["burn_rate"] == [0.0]

    def test_snapshot_coalescing_bounds_the_ring(self):
        acct, t = self.make()
        self._record(acct, violated=50)  # same clock instant: 1 entry
        c = acct.classes["lax"]
        assert len(c.ring) == 1
        t[0] = 2.0
        self._record(acct, violated=1)
        assert len(c.ring) == 2

    def test_ring_prunes_but_keeps_window_baseline(self):
        acct, t = self.make(windows=(60.0,))
        for step in range(0, 200, 2):
            t[0] = float(step)
            self._record(acct, met=1)
        c = acct.classes["lax"]
        # Pruned to ~the window span, and the oldest retained entry is
        # at/before the window edge so the baseline stays available.
        assert len(c.ring) <= 60 / 2 + 2
        assert c.ring[0][0] <= t[0] - 60.0

    def test_multi_window_fast_pages_slow_confirms(self):
        acct, t = self.make(windows=(60.0, 3600.0))
        self._record(acct, met=90)       # old, clean traffic
        t[0] = 1000.0
        self._record(acct, violated=10)  # fresh cliff
        t[0] = 1030.0
        entry = _classes_by_name(acct.stats())["lax"]
        fast, slow = entry["burn_rate"]
        # Fast window sees only the cliff (10/10); the slow window
        # dilutes it with the old traffic (10/100).
        assert fast == pytest.approx(1.0 / ERROR_BUDGET)
        assert slow == pytest.approx(0.1 / ERROR_BUDGET)
        assert fast > slow

    def test_merged_burn_is_weighted_not_averaged(self):
        # One burning quiet tier + one clean busy tier: the merged
        # burn must come from summed (violated, total) deltas —
        # averaging the two rates would report (100 + 0) / 2 = 50.
        t = [0.0]
        cfg = _slo_cfg(burn_windows_s=[60.0])
        a = SloAccount(cfg, clock=lambda: t[0])
        b = SloAccount(cfg, clock=lambda: t[0])
        a.record_terminal("lax", "timeout", admitted=True,
                          ttft_ms=1.0, tpot_ms=1.0, e2e_ms=1.0)
        for _ in range(9):
            b.record_terminal("lax", "stop", admitted=True,
                              ttft_ms=1.0, tpot_ms=1.0, e2e_ms=1.0)
        t[0] = 30.0
        solo = _classes_by_name(a.stats())["lax"]["burn_rate"][0]
        assert solo == pytest.approx(1.0 / ERROR_BUDGET)  # 100x
        merged = SloAccount.merged_stats([a, b])
        entry = _classes_by_name(merged)["lax"]
        assert entry["burn_rate"][0] == pytest.approx(
            (1 / 10) / ERROR_BUDGET  # 10x — exact recombination
        )
        _assert_closure(merged, 10)
        # Histograms merged elementwise too.
        assert entry["ttft_ms_count"] == 10


# ---------------------------------------------------------------------------
# TenantTable units: VTC math, LRU bound, conservation
# ---------------------------------------------------------------------------


class TestTenantTable:
    def make(self, **kw):
        return TenantTable(_slo_cfg(**kw))

    def _rows(self, table):
        return {r["tenant"]: r for r in table.stats()["tenants"]}

    def test_vtc_weighted_token_math(self):
        table = self.make()  # defaults: prompt 1.0, decode 2.0
        table.record_terminal("acme", admitted=True,
                              prompt_tokens=10, decode_tokens=5,
                              queue_ms=3.0)
        row = self._rows(table)["acme"]
        assert row["weighted_tokens"] == pytest.approx(10 * 1.0 + 5 * 2.0)
        assert row["prompt_tokens"] == 10 and row["decode_tokens"] == 5
        assert row["admitted"] == 1 and row["queue_ms_sum"] == 3.0

    def test_unadmitted_prompt_not_charged(self):
        # A queue death never prefilled: its prompt tokens cost no
        # service, only the decode side (zero here) is metered.
        table = self.make()
        table.record_terminal("acme", admitted=False,
                              prompt_tokens=100, decode_tokens=0)
        row = self._rows(table)["acme"]
        assert row["prompt_tokens"] == 0
        assert row["weighted_tokens"] == 0.0
        assert row["requests"] == 1 and row["admitted"] == 0

    def test_custom_weights(self):
        table = self.make(vtc_prompt_weight=0.5, vtc_decode_weight=4.0)
        table.record_terminal("t", admitted=True,
                              prompt_tokens=8, decode_tokens=2)
        assert self._rows(table)["t"]["weighted_tokens"] == (
            pytest.approx(8 * 0.5 + 2 * 4.0)
        )

    def test_empty_tenant_is_default(self):
        table = self.make()
        table.record_terminal("", admitted=True, decode_tokens=1)
        assert "default" in self._rows(table)

    def test_churn_10k_tenants_stays_bounded_and_conserves(self):
        # THE cardinality acceptance: 10k distinct tenants through a
        # top_k=8 table — tracked never exceeds the bound, the
        # overflow row absorbs the evicted tail, and request/token
        # counters CONSERVE exactly across eviction.
        table = self.make(tenant_top_k=8)
        for i in range(10_000):
            table.record_terminal(f"tenant-{i}", admitted=True,
                                  prompt_tokens=2, decode_tokens=1)
        stats = table.stats()
        assert stats["slo_tenants_tracked"] <= 8
        assert stats["slo_tenant_evictions"] == 10_000 - 8
        assert len(stats["tenants"]) <= 8 + 1  # + the overflow row
        rows = self._rows(table)
        assert OVERFLOW_TENANT in rows
        assert sum(r["requests"] for r in rows.values()) == 10_000
        assert sum(r["decode_tokens"] for r in rows.values()) == 10_000
        assert sum(
            r["weighted_tokens"] for r in rows.values()
        ) == pytest.approx(10_000 * (2 * 1.0 + 1 * 2.0))
        # Overflow sorts last despite being heaviest.
        assert stats["tenants"][-1]["tenant"] == OVERFLOW_TENANT

    def test_lru_evicts_least_recently_active(self):
        table = self.make(tenant_top_k=2)
        table.record_terminal("a", admitted=True, decode_tokens=1)
        table.record_terminal("b", admitted=True, decode_tokens=1)
        table.record_terminal("a", admitted=True, decode_tokens=1)
        table.record_terminal("c", admitted=True, decode_tokens=1)  # evicts b
        rows = self._rows(table)
        assert set(rows) == {"a", "c", OVERFLOW_TENANT}
        assert rows[OVERFLOW_TENANT]["requests"] == 1  # b's ledger

    def test_shed_and_uncount(self):
        table = self.make()
        table.record_shed("acme")
        table.record_shed("acme")
        table.uncount_shed("acme")
        row = self._rows(table)["acme"]
        assert row["shed"] == 1 and row["requests"] == 1
        table.uncount_shed("acme")
        table.uncount_shed("acme")  # floor at zero, never negative
        row = self._rows(table)["acme"]
        assert row["shed"] == 0 and row["requests"] == 0

    def test_heaviest_first_ordering(self):
        table = self.make()
        table.record_terminal("light", admitted=True, decode_tokens=1)
        table.record_terminal("heavy", admitted=True, decode_tokens=50)
        names = [r["tenant"] for r in table.stats()["tenants"]]
        assert names == ["heavy", "light"]

    def test_merged_stats_reapplies_bound_and_conserves(self):
        a = self.make(tenant_top_k=4)
        b = self.make(tenant_top_k=4)
        for i in range(4):
            a.record_terminal(f"a{i}", admitted=True, decode_tokens=i + 1)
            b.record_terminal(f"b{i}", admitted=True, decode_tokens=i + 1)
        # Shared tenant sums across tiers.
        a.record_terminal("shared", admitted=True, decode_tokens=10)
        b.record_terminal("shared", admitted=True, decode_tokens=10)
        # (each table evicted one row into its own overflow by now)
        merged = TenantTable.merged_stats([a, b], top_k=4)
        assert len(merged["tenants"]) <= 4 + 1
        rows = {r["tenant"]: r for r in merged["tenants"]}
        assert rows["shared"]["requests"] == 2
        assert rows["shared"]["decode_tokens"] == 20
        assert sum(r["requests"] for r in merged["tenants"]) == 10
        assert merged["tenants"][-1]["tenant"] == OVERFLOW_TENANT

    def test_stats_round_trip_through_proto(self):
        table = self.make()
        table.record_terminal("acme", admitted=True,
                              prompt_tokens=3, decode_tokens=2)
        msg = serving_pb2.ServingStatsResponse(**table.stats())
        assert msg.slo_tenants_tracked == 1
        assert msg.tenants[0].tenant == "acme"
        assert msg.tenants[0].weighted_tokens == pytest.approx(7.0)


# ---------------------------------------------------------------------------
# Obs-off: stores and computes NOTHING
# ---------------------------------------------------------------------------


class TestObsOff:
    def test_slo_disabled_by_config(self):
        acct = SloAccount(_slo_cfg(enabled=False))
        assert not acct.enabled
        assert acct.record_terminal("lax", "stop", admitted=True) == ""
        acct.record_shed("lax")
        assert acct.stats() == {}

    def test_slo_disabled_by_observability(self):
        acct = SloAccount(_slo_cfg(), obs_enabled=False)
        assert not acct.enabled
        assert acct.stats() == {}
        # No ring snapshots, no counters — zero storage.
        assert all(not c.ring for c in acct.classes.values())

    def test_tenant_table_disabled(self):
        for table in (
            TenantTable(_slo_cfg(enabled=False)),
            TenantTable(_slo_cfg(), enabled=False),
        ):
            table.record_terminal("acme", admitted=True, decode_tokens=5)
            table.record_shed("acme")
            assert table.stats() == {}
            assert len(table._rows) == 0

    def test_merged_stats_of_disabled_is_empty(self):
        assert SloAccount.merged_stats(
            [SloAccount(_slo_cfg(enabled=False)), None]
        ) == {}
        assert TenantTable.merged_stats(
            [TenantTable(_slo_cfg(enabled=False)), None]
        ) == {}

    async def test_obs_off_batcher_records_nothing(self, engine):
        import dataclasses

        off = dataclasses.replace(
            engine.serving, observability=ObservabilityConfig(enabled=False)
        )

        class _Shim:
            def __getattr__(self, name):
                return getattr(engine, name)

        shim = _Shim()
        shim.__dict__["serving"] = off
        batcher = ContinuousBatcher(
            shim, BatchingConfig(max_batch_size=2, kv_cache_max_seq=128)
        )
        assert not batcher.slo.enabled and not batcher.tenants.enabled
        batcher.start()
        try:
            await _drain(batcher, [5, 3, 2], 4,
                         tenant="acme", qos_class="fast")
        finally:
            await batcher.stop()
        stats = batcher.stats()
        assert "slo_classes" not in stats and "tenants" not in stats


# ---------------------------------------------------------------------------
# Identity precedence (sidecar fallback chain)
# ---------------------------------------------------------------------------


class _Ctx:
    def __init__(self, md):
        self._md = list(md.items())

    def invocation_metadata(self):
        return self._md


class TestIdentityPrecedence:
    def _resolve(self, req_kw, md):
        from ggrmcp_tpu.serving.sidecar import Sidecar

        req = serving_pb2.GenerateRequest(**req_kw)
        return Sidecar._tenant_identity(None, req, _Ctx(md))

    def test_explicit_fields_win(self):
        tenant, qos = self._resolve(
            {"tenant_id": "explicit", "qos_class": "fast"},
            {"x-tenant-id": "header", "x-qos-class": "lax"},
        )
        assert (tenant, qos) == ("explicit", "fast")

    def test_header_beats_adapter(self):
        tenant, _ = self._resolve(
            {"adapter": "my-lora"}, {"x-tenant-id": "header"}
        )
        assert tenant == "header"

    def test_adapter_beats_adapter_header(self):
        tenant, _ = self._resolve(
            {"adapter": "my-lora"}, {"x-adapter-id": "other"}
        )
        assert tenant == "my-lora"

    def test_adapter_header_beats_session(self):
        tenant, _ = self._resolve(
            {}, {"x-adapter-id": "ad", "x-session-id": "sess"}
        )
        assert tenant == "ad"

    def test_session_fallback_then_default(self):
        tenant, qos = self._resolve({}, {"x-session-id": "sess"})
        assert (tenant, qos) == ("sess", "")
        tenant, _ = self._resolve({}, {})
        assert tenant == "default"


# ---------------------------------------------------------------------------
# Batcher integration: closure across every serving config
# ---------------------------------------------------------------------------


def _make_batcher(engine, mode):
    base = dict(max_batch_size=4, kv_cache_max_seq=256)
    if mode == "paged":
        return ContinuousBatcher(
            engine, BatchingConfig(**base, paged_kv="on")
        )
    if mode == "tiered":
        return TieredBatcher(
            engine, BatchingConfig(kv_tiers=[[64, 2], [128, 2]])
        )
    return ContinuousBatcher(engine, BatchingConfig(**base))


class TestClosureAcrossConfigs:
    @pytest.mark.parametrize(
        "mode", ["plain", "paged", "tiered", "grammar"]
    )
    async def test_goodput_partition_closure(self, engine, mode):
        """The acceptance property, per serving config: every
        submitted request lands in exactly one partition; "fast"
        finishes violate (µs targets), "lax" finishes meet; tenant
        decode attribution reconciles against actually-emitted
        tokens."""
        batcher = _make_batcher(engine, mode)
        grammar = (
            compile_schema({"enum": ["alpha", "beta"]}, vocab_size=VOCAB)
            if mode == "grammar" else None
        )
        batcher.start()
        n = 8
        try:
            tasks = []
            for i in range(n):
                if mode == "tiered" and i % 2:
                    prompt = [5] * 70  # must land in the 128-seq tier
                else:
                    prompt = [7, 3, i % 11 + 1]
                kw = dict(
                    seed=i,
                    tenant=f"acct-{i % 3}",
                    qos_class="fast" if i % 2 else "lax",
                )
                if grammar is not None:
                    kw["grammar"] = grammar
                tasks.append(_drain(batcher, prompt, 48, **kw))
            results = await asyncio.gather(*tasks)
        finally:
            await batcher.stop()
        assert all(r in NORMAL_FINISHES for _, r in results)
        stats = batcher.stats()
        _assert_closure(stats, n)
        classes = _classes_by_name(stats)
        assert classes["fast"]["violated"] == n // 2
        assert classes["fast"]["met"] == 0
        assert classes["lax"]["met"] == n // 2
        # Latency histograms observed every admitted request.
        assert classes["fast"]["e2e_ms_count"] == n // 2
        # Tenant attribution reconciles with what was actually emitted.
        rows = {r["tenant"]: r for r in stats["tenants"]}
        assert sum(r["requests"] for r in rows.values()) == n
        assert sum(r["decode_tokens"] for r in rows.values()) == sum(
            len(out) for out, _ in results
        )
        assert sum(r["prompt_tokens"] for r in rows.values()) == sum(
            3 if (mode != "tiered" or i % 2 == 0) else 70
            for i in range(n)
        )


# ---------------------------------------------------------------------------
# Chaos: shed / timeout / replay land TYPED, closure never breaks
# ---------------------------------------------------------------------------


class TestChaosClosure:
    async def test_submit_storm_sheds_land_unevaluated(self, engine):
        cfg = BatchingConfig(
            max_batch_size=2, kv_cache_max_seq=128, max_pending=2
        )
        batcher = ContinuousBatcher(engine, cfg)
        batcher.start()
        n, shed, tasks = 16, 0, []
        try:
            for i in range(n):
                try:
                    it = batcher.submit(
                        [7, 3, i % 11 + 1], 6, GREEDY, seed=i,
                        tenant=f"storm-{i % 2}", qos_class="lax",
                    )
                except OverloadedError:
                    shed += 1
                else:
                    async def consume(it=it):
                        async for _ in it:
                            pass

                    tasks.append(asyncio.create_task(consume()))
                if i % 4 == 3:
                    await asyncio.sleep(0.02)  # let the loop drain some
            await asyncio.gather(*tasks)
        finally:
            await batcher.stop()
        assert shed > 0, "storm never hit the cap"
        stats = batcher.stats()
        _assert_closure(stats, n)
        lax = _classes_by_name(stats)["lax"]
        # Every shed is typed unevaluated; every accepted finish met.
        assert lax["unevaluated"] == shed
        assert lax["met"] == n - shed
        rows = {r["tenant"]: r for r in stats["tenants"]}
        assert sum(r["shed"] for r in rows.values()) == shed
        assert sum(r["requests"] for r in rows.values()) == n

    async def test_queue_timeouts_land_unevaluated(self, engine):
        cfg = BatchingConfig(
            max_batch_size=2, kv_cache_max_seq=128, queue_deadline_ms=60.0
        )
        batcher = ContinuousBatcher(engine, cfg)
        batcher.start()
        try:
            busy = [
                asyncio.create_task(_drain(
                    batcher, [5, i], 48, seed=i,
                    tenant="busy", qos_class="lax",
                ))
                for i in range(2)
            ]
            await asyncio.sleep(0.05)
            late = await asyncio.gather(
                _drain(batcher, [7, 7], 4, seed=9,
                       tenant="late", qos_class="lax"),
                _drain(batcher, [8, 8], 4, seed=10,
                       tenant="late", qos_class="lax"),
            )
            await asyncio.gather(*busy)
        finally:
            await batcher.stop()
        assert [r for _, r in late] == ["timeout", "timeout"]
        stats = batcher.stats()
        _assert_closure(stats, 4)
        lax = _classes_by_name(stats)["lax"]
        # Queue deaths never prefilled: no latency to judge, typed
        # unevaluated — and they must not pollute the TTFT histogram.
        assert lax["unevaluated"] == 2 and lax["met"] == 2
        assert lax["ttft_ms_count"] == 2
        rows = {r["tenant"]: r for r in stats["tenants"]}
        assert rows["late"]["admitted"] == 0
        assert rows["late"]["requests"] == 2

    async def test_tick_fail_replay_counts_each_request_once(self, engine):
        failpoints.registry.arm("tick_fail", every=3)
        batcher = ContinuousBatcher(
            engine,
            BatchingConfig(max_batch_size=4, kv_cache_max_seq=256,
                           tick_retry_limit=32),
        )
        batcher.start()
        n = 6
        try:
            results = await asyncio.gather(*[
                _drain(batcher, [7, 3, i % 11 + 1], 8, seed=i,
                       tenant="replay", qos_class="fast" if i % 2
                       else "lax")
                for i in range(n)
            ])
        finally:
            await batcher.stop()
        assert all(r in NORMAL_FINISHES for _, r in results)
        stats = batcher.stats()
        # Replayed ticks must not double-count terminals: the totals
        # equal the submit count exactly.
        _assert_closure(stats, n)
        rows = {r["tenant"]: r for r in stats["tenants"]}
        assert rows["replay"]["requests"] == n
        assert rows["replay"]["finished"] == n

    async def test_tiered_probe_sheds_reconcile(self, engine):
        """The overflow-probe un-count: a small tier's refusal that a
        larger sibling absorbed is not a caller-visible shed — the
        facade's class totals must equal accepted + actually-refused,
        with every probe's record_shed reversed."""
        tiered = TieredBatcher(
            engine,
            BatchingConfig(kv_tiers=[[64, 2], [128, 2]], max_pending=1,
                           pipeline_ticks="off"),
        )
        # Never started: queues hold, refusals are deterministic.
        tiered.submit([1, 2], 4, GREEDY, tenant="t", qos_class="lax")
        tiered.submit([3, 4], 4, GREEDY, tenant="t", qos_class="lax")
        with pytest.raises(OverloadedError):
            tiered.submit([5, 6], 4, GREEDY, tenant="t", qos_class="lax")
        stats = tiered.stats()
        lax = _classes_by_name(stats)["lax"]
        # One caller-visible shed (typed unevaluated); the spill that
        # the long tier absorbed was un-counted. The two queued
        # requests have no terminal yet.
        assert lax["unevaluated"] == 1
        assert lax["total_requests"] == 1
        rows = {r["tenant"]: r for r in stats["tenants"]}
        assert rows["t"]["shed"] == 1
        assert rows["t"]["requests"] == 1


# ---------------------------------------------------------------------------
# Gateway e2e: /debug/slo, ?tenant= filter, /metrics families
# ---------------------------------------------------------------------------


def _n(value):
    # protojson renders 64-bit integers as strings and omits zeros.
    return int(float(value or 0))


async def _tenant_call(client, tenant, qos, trace_id, arguments=None):
    args = {"prompt": "slo probe", "maxNewTokens": 4}
    args.update(arguments or {})
    headers = {"X-Trace-Id": trace_id}
    if tenant:
        headers["X-Tenant-Id"] = tenant
    if qos:
        headers["X-QoS-Class"] = qos
    resp = await client.post("/", json={
        "jsonrpc": "2.0", "method": "tools/call", "id": 1,
        "params": {
            "name": "ggrmcp_tpu_generateservice_generate",
            "arguments": args,
        },
    }, headers=headers)
    data = await resp.json()
    assert "error" not in data, data
    return data


class TestGatewaySurfaces:
    @pytest.mark.parametrize("impl", ["fastlane", "aiohttp"])
    async def test_debug_slo_shape_and_closure(self, impl):
        from tests.test_observability import observed_env

        async with observed_env(impl) as (_side, _gw, client):
            await _tenant_call(client, "acme", "interactive",
                               f"t-slo-1-{impl}")
            await _tenant_call(client, "globex", "batch",
                               f"t-slo-2-{impl}")
            body = await (await client.get("/debug/slo")).json()
            [backend] = body["backends"]
            assert backend["target"]
            classes = {c["name"]: c for c in backend["classes"]}
            # The default three-tier class set, every class exported.
            assert set(classes) == {"interactive", "batch", "background"}
            total = 0
            for c in classes.values():
                part = (_n(c.get("met")) + _n(c.get("violated"))
                        + _n(c.get("unevaluated")))
                assert part == _n(c.get("totalRequests")), c
                total += part
                assert c.get("burnWindowS"), c
            assert total == 2
            assert (
                _n(backend.get("metTotal"))
                + _n(backend.get("violatedTotal"))
                + _n(backend.get("unevaluatedTotal"))
            ) == 2
            tenants = {t["tenant"]: t for t in backend["tenants"]}
            assert {"acme", "globex"} <= set(tenants)
            assert _n(tenants["acme"].get("decodeTokens")) >= 1
            assert _n(backend.get("tenantsTracked")) == 2

    @pytest.mark.parametrize("impl", ["fastlane", "aiohttp"])
    async def test_debug_requests_tenant_filter(self, impl):
        from tests.test_observability import observed_env

        async with observed_env(impl) as (_side, _gw, client):
            await _tenant_call(client, "acme", "interactive",
                               f"t-flt-a-{impl}")
            await _tenant_call(client, "globex", "batch",
                               f"t-flt-b-{impl}")
            body = await (await client.get(
                "/debug/requests", params={"tenant": "acme"}
            )).json()
            assert body["tenant"] == "acme"
            [backend] = body["backends"]
            recs = backend["requests"]
            assert len(recs) == 1
            assert recs[0]["tenant"] == "acme"
            assert recs[0]["qosClass"] == "interactive"
            # Unfiltered still shows both.
            body = await (await client.get("/debug/requests")).json()
            [backend] = body["backends"]
            assert {r["tenant"] for r in backend["requests"]} == {
                "acme", "globex"
            }

    async def test_explicit_arguments_beat_headers(self):
        from tests.test_observability import observed_env

        async with observed_env("fastlane") as (_side, _gw, client):
            await _tenant_call(
                client, "header-tenant", "batch", "t-prec",
                arguments={"tenantId": "arg-tenant",
                           "qosClass": "interactive"},
            )
            body = await (await client.get("/debug/requests")).json()
            [backend] = body["backends"]
            [rec] = backend["requests"]
            assert rec["tenant"] == "arg-tenant"
            assert rec["qosClass"] == "interactive"

    @pytest.mark.parametrize("impl", ["fastlane", "aiohttp"])
    async def test_metrics_carry_slo_families(self, impl):
        from prometheus_client.parser import text_string_to_metric_families

        from tests.test_observability import observed_env

        async with observed_env(impl) as (_side, _gw, client):
            await _tenant_call(client, "acme", "interactive",
                               f"t-met-{impl}")
            text = await (await client.get("/metrics")).text()
            families = {
                f.name: f for f in text_string_to_metric_families(text)
            }
            hist = families["gateway_backend_class_latency_ms"]
            labels = {
                (s.labels.get("class"), s.labels.get("metric"))
                for s in hist.samples
            }
            assert ("interactive", "ttft") in labels
            assert ("interactive", "e2e") in labels
            req = families["gateway_backend_slo_requests"]
            by_outcome = {
                (s.labels["class"], s.labels["outcome"]): s.value
                for s in req.samples
            }
            # The one finished call landed in exactly one partition.
            assert sum(
                v for (cls, _), v in by_outcome.items()
                if cls == "interactive"
            ) == 1.0
            burn = families["gateway_backend_slo_burn_rate"]
            assert {s.labels["window"] for s in burn.samples} >= {
                "300", "3600"
            }
            target = families["gateway_backend_slo_target_ms"]
            targets = {
                (s.labels["class"], s.labels["metric"]): s.value
                for s in target.samples
            }
            # Objectives ride the same scrape the latencies do.
            assert targets[("interactive", "ttft")] == 500.0
            assert targets[("interactive", "tpot")] == 100.0
            # No tenant LABEL anywhere on the exposition (the
            # unbounded axis lives on /debug/slo only; the bounded
            # tracked/evictions gauges are fine).
            assert not any(
                "tenant" in s.labels
                for f in families.values() for s in f.samples
            )
