"""Flight recorder + latency attribution net (ISSUE 3, marker `obs`).

Covers, bottom-up:
- histogram bucket math and ring bounding (serving/flight_recorder.py)
- proto↔pb2 drift (scripts/regen_serving_pb2.py --check as a test)
- proto↔metrics drift: EVERY scalar ServingStatsResponse field exports
  a gateway_backend_* gauge, every *_bucket triplet a real histogram
- scrape validity: the rendered /metrics exposition parses with
  prometheus_client.parser (malformed series never ship)
- end-to-end trace linkage on BOTH HTTP impls: one tool call's
  X-Trace-Id walks /debug/traces → /debug/requests → /debug/ticks,
  and /metrics carries the backend ttft/e2e/queue/tick histograms
- near-zero-overhead off switch: observability.enabled=false records
  nothing while serving stays correct
"""

import contextlib
import json

import aiohttp
import pytest

from ggrmcp_tpu.core.config import ObservabilityConfig
from ggrmcp_tpu.serving.flight_recorder import (
    HISTOGRAM_NAMES,
    FlightRecorder,
    LatencyHistogram,
)

pytestmark = pytest.mark.obs


class TestLatencyHistogram:
    def test_bucket_boundaries_are_le_inclusive(self):
        h = LatencyHistogram((1.0, 10.0, 100.0))
        for v in (0.5, 1.0, 9.9, 10.0, 100.0, 5000.0):
            h.observe(v)
        # le-inclusive: 1.0 lands in the le=1 bucket, 10.0 in le=10.
        assert h.counts == [2, 2, 1, 1]
        assert h.total == 6
        assert h.sum == pytest.approx(0.5 + 1 + 9.9 + 10 + 100 + 5000)

    def test_merge_is_elementwise(self):
        a = FlightRecorder(ObservabilityConfig(bucket_bounds_ms=[1, 10]))
        b = FlightRecorder(ObservabilityConfig(bucket_bounds_ms=[1, 10]))
        a.record_request("x", 0.0, 0.001, 0.002, 4, 2, "stop", 1, 1)
        b.record_request("y", 0.0, 0.001, 0.005, 4, 2, "stop", 1, 1)
        merged = FlightRecorder.merge_histogram_stats(
            [a.histogram_stats(), b.histogram_stats()]
        )
        assert merged["ttft_ms_count"] == 2
        assert merged["e2e_ms_count"] == 2
        assert sum(merged["ttft_ms_bucket"]) == 2
        assert merged["latency_bucket_bounds_ms"] == [1.0, 10.0]

    def test_rings_are_bounded(self):
        rec = FlightRecorder(
            ObservabilityConfig(tick_ring=4, request_ring=4)
        )
        for i in range(10):
            rec.tick_start(i, 1, 0, [], 0, 0, 0)
            rec.record_request(f"t{i}", 0.0, 0.0, 0.0, 1, 1, "stop", -1, -1)
        assert len(rec.tick_snapshot()) == 4
        assert len(rec.request_snapshot()) == 4
        assert rec.tick_snapshot()[-1].seq == 9

    def test_disabled_records_nothing(self):
        rec = FlightRecorder(ObservabilityConfig(enabled=False))
        assert rec.tick_start(1, 1, 0, [], 0, 0, 0) is None
        rec.record_request("x", 0.0, 0.0, 0.001, 1, 1, "stop", -1, -1)
        assert rec.request_snapshot() == []
        assert rec.histogram_stats()["e2e_ms_count"] == 0

    def test_request_record_lookup_newest_first(self):
        rec = FlightRecorder()
        rec.record_request("dup", 0.0, 0.0, 0.001, 1, 1, "stop", -1, -1)
        rec.record_request("dup", 0.0, 0.0, 0.002, 1, 2, "stop", -1, -1)
        assert rec.request_record("dup").tokens == 2
        assert rec.request_record("missing") is None
        assert rec.request_record("") is None


class TestProtoDrift:
    def test_pb2_matches_proto(self):
        """serving_pb2.py must be regenerated whenever serving.proto
        changes (scripts/regen_serving_pb2.py; no protoc on the image)."""
        import scripts.regen_serving_pb2 as regen

        assert regen.check() == 0

    def test_every_scalar_stats_field_is_exported(self):
        """The drift guard the hand-synced gauge list needed: every
        scalar ServingStatsResponse field must flow to a
        gateway_backend_* gauge, and every *_bucket repeated field to a
        real histogram family — a new proto field without an export is
        a red test, not a silent dashboard gap."""
        from ggrmcp_tpu.gateway.metrics import (
            GatewayMetrics,
            serving_gauge_names,
            serving_histogram_names,
            serving_info_names,
            serving_memory_component_names,
        )
        from ggrmcp_tpu.rpc.pb import serving_pb2

        desc = serving_pb2.ServingStatsResponse.DESCRIPTOR
        gauges = set(serving_gauge_names())
        hists = set(serving_histogram_names())
        infos = set(serving_info_names())
        memory = set(serving_memory_component_names())
        assert hists == {
            "ttft_ms", "e2e_ms", "queue_ms", "tick_duration_ms",
            # Inter-token latency (fields 106-108).
            "tpot_ms",
            # queue_ms split at the admission pop (fields 144-149).
            "pending_ms", "prefill_ms",
            # The admission round timed from inside (fields 169-174).
            "admit_device_ms", "admit_host_ms",
            # Tick-phase attribution: one histogram per phase, rendered
            # as ONE gateway_backend_tick_phase_ms{phase} family.
            *(f"tick_phase_{p}_ms"
              for p in ("admit", "sync", "dispatch", "wait", "host")),
        }
        # String fields export info-style (labels carry the value) —
        # mesh_shape was the first, the serving role rides beside it; a
        # new string field lands there by construction.
        assert infos == {"mesh_shape", "role"}
        # Memory-ledger fields render as the component label of ONE
        # gateway_backend_memory_bytes family (never per-field gauges).
        assert memory == {
            "weights", "lora", "kv_arena", "block_tables",
            "ilv_mini", "grammar_arena", "tick_state",
        }
        assert not (gauges & infos)
        # Repeated MESSAGE fields carry structured per-class/per-tenant
        # tables: the SLO classes export through the class-labeled
        # _SloCollector families, the tenant table through /debug/slo
        # ONLY (tenant is an unbounded Prometheus label). A NEW message
        # field must be named here with its export surface — the
        # covered-loop below rejects it otherwise.
        structured = {"slo_classes", "tenants"}
        for field in desc.fields:
            covered = (
                field.name in gauges
                or field.name in infos
                or field.name in structured
                or field.name in {
                    f"memory_{m}_bytes" for m in memory
                }
                or any(
                    field.name in
                    (f"{h}_bucket", f"{h}_sum", f"{h}_count")
                    for h in hists
                )
                or field.name == "latency_bucket_bounds_ms"
            )
            assert covered, f"ServingStats field {field.name} not exported"
        assert structured == {
            f.name for f in desc.fields
            if f.cpp_type == f.CPPTYPE_MESSAGE
        }
        # The SLO cross-class totals export as plain gauges.
        assert {
            "slo_met_total", "slo_violated_total",
            "slo_unevaluated_total", "slo_tenants_tracked",
            "slo_tenant_evictions",
        } <= gauges
        # The TP-serving identity fields must stay exported as gauges —
        # the anti-masquerade contract (docs/tensor_parallel_serving.md).
        assert {"tp_chips", "mesh_devices", "mesh_spec_downgrades"} <= gauges
        # The compile watcher's fields export as plain gauges
        # (gateway_backend_compile_*).
        assert {
            "compile_count", "compile_ms", "compile_cache_hits",
            "compile_cache_misses", "compile_post_warmup",
        } <= gauges
        # The loop-turn partition and the sidecar's own Generate time
        # are monotone sum/count pairs without buckets: plain gauges.
        assert {
            "loop_exec_wait_ms_sum", "loop_exec_wait_ms_count",
            "loop_work_ms_sum", "loop_lag_ms_sum", "loop_lag_ms_count",
            "loop_host_ms_sum", "loop_busy_ms_sum",
            "rpc_generate_ms_sum", "rpc_generate_ms_count",
        } <= gauges

        metrics = GatewayMetrics()
        if metrics.registry is None:
            pytest.skip("prometheus_client unavailable")
        # The registry actually carries a gauge per scalar field, and
        # the info series carries one label per string field.
        assert set(metrics.serving_gauges) == gauges
        metrics.set_serving_stats([{
            "target": "t1", "tpChips": 2, "meshShape": "tensor=2",
            "memoryWeightsBytes": "1024", "compilePostWarmup": 3,
        }])
        rendered = metrics.render()[0].decode()
        assert 'gateway_backend_serving_mesh_info{' in rendered
        assert 'mesh_shape="tensor=2"' in rendered
        assert 'gateway_backend_tp_chips{target="t1"} 2.0' in rendered
        # The {component}-labeled memory family and the compile gauges.
        assert (
            'gateway_backend_memory_bytes{component="weights",'
            'target="t1"} 1024.0' in rendered
        )
        assert (
            'gateway_backend_memory_bytes{component="kv_arena",'
            'target="t1"} 0.0' in rendered
        )
        assert (
            'gateway_backend_compile_post_warmup{target="t1"} 3.0'
            in rendered
        )
        # Target disappears → info series AND memory family retire.
        metrics.set_serving_stats([])
        rendered = metrics.render()[0].decode()
        assert 'mesh_shape="tensor=2"' not in rendered
        assert 'target="t1"' not in rendered

    @pytest.mark.parametrize("name", [
        "tick_dispatch_ms", "tick_collect_ms", "admit_ms", "admit_ms_max",
        "queue_ms_p50", "queue_ms_p99", "service_ms_p50",
        "service_ms_p99", "decode_stall_ms_p50", "decode_stall_ms_p99",
        "decode_stall_ms_max",
        # PR 39: without a writer since PR 30.
        "speculative_calls", "speculative_requests",
        "speculative_drafted", "speculative_accepted",
        "memory_prefix_pool_bytes",
        # PR 48: went with the in-batcher speculative tick.
        "spec_ticks", "spec_drafted", "spec_accepted",
        "memory_draft_cache_bytes",
    ])
    def test_retired_fields_are_gone_and_their_numbers_reserved(self, name):
        """PR 26's inventory: the second tick clocks and the lifetime
        percentile gauges had no reader; PR 39's: five fields had no
        writer; PR 48's: four fields went with the path that wrote
        them. Their numbers stay reserved, so no later field can take
        one and be misread by an old peer."""
        from ggrmcp_tpu.gateway.metrics import serving_gauge_names
        from ggrmcp_tpu.rpc.pb import serving_pb2

        desc = serving_pb2.ServingStatsResponse.DESCRIPTOR
        assert name not in desc.fields_by_name
        assert name not in serving_gauge_names()
        from google.protobuf import descriptor_pb2

        proto = descriptor_pb2.DescriptorProto()
        desc.CopyToProto(proto)
        reserved = {
            n for r in proto.reserved_range for n in range(r.start, r.end)
        }
        assert reserved == {
            8, 9, 14, 15, 16, 17, 18, 19, 20, 21, 22, 23, 26, 27, 28,
            50, 51, 52, 96, 97,
        }
        assert not reserved & {f.number for f in desc.fields}
        tick = descriptor_pb2.DescriptorProto()
        serving_pb2.TickRecord.DESCRIPTOR.CopyToProto(tick)
        assert {
            n for r in tick.reserved_range for n in range(r.start, r.end)
        } == {14, 15}

    def test_flight_recorder_stats_match_proto_fields(self):
        """histogram_stats() keys must be exact proto field names —
        ServingStatsResponse(**stats) is the loud-drift contract."""
        from ggrmcp_tpu.rpc.pb import serving_pb2

        stats = FlightRecorder().histogram_stats()
        serving_pb2.ServingStatsResponse(**stats)  # raises on drift
        assert set(stats) == {
            "latency_bucket_bounds_ms",
            *(f"{n}_{suffix}" for n in HISTOGRAM_NAMES
              for suffix in ("bucket", "sum", "count")),
        }


class TestScrapeValidity:
    def _populated_metrics(self):
        from ggrmcp_tpu.gateway.metrics import GatewayMetrics

        metrics = GatewayMetrics()
        if metrics.registry is None:
            pytest.skip("prometheus_client unavailable")
        rec = FlightRecorder()
        rec.record_request("t", 0.0, 0.001, 0.002, 4, 8, "stop", 1, 3)
        entry = {
            "target": "side:1",
            "activeSlots": 2,
            "queuedTokens": "37",
            **{
                # protojson shape: camelCase keys, int64 lists as
                # strings, doubles as numbers.
                "latencyBucketBoundsMs": list(
                    rec.histogram_stats()["latency_bucket_bounds_ms"]
                ),
                "ttftMsBucket": [
                    str(c) for c in rec.histogram_stats()["ttft_ms_bucket"]
                ],
                "ttftMsSum": rec.histogram_stats()["ttft_ms_sum"],
                "ttftMsCount": str(rec.histogram_stats()["ttft_ms_count"]),
            },
        }
        metrics.observe_http("POST", "/", 200, 0.01)
        metrics.observe_tool_call("tool_x", "ok", 0.02)
        metrics.set_serving_stats([entry])
        return metrics

    def test_exposition_parses_and_carries_histograms(self):
        from prometheus_client.parser import text_string_to_metric_families

        metrics = self._populated_metrics()
        text = metrics.render()[0].decode()
        families = {
            f.name: f for f in text_string_to_metric_families(text)
        }
        # Genuine histogram: _bucket/_sum/_count samples with le labels.
        ttft = families["gateway_backend_ttft_ms"]
        assert ttft.type == "histogram"
        samples = {
            (s.name, s.labels.get("le")): s.value for s in ttft.samples
        }
        assert samples[("gateway_backend_ttft_ms_count", None)] == 1.0
        assert samples[("gateway_backend_ttft_ms_bucket", "+Inf")] == 1.0
        # cumulative le semantics: every bucket ≤ +Inf count, ascending.
        bucket_vals = [
            s.value for s in ttft.samples
            if s.name.endswith("_bucket")
        ]
        assert bucket_vals == sorted(bucket_vals)
        # Descriptor-driven gauges rendered too.
        assert families["gateway_backend_active_slots"].samples
        assert families["gateway_backend_tick_phase_dispatch_ms"].samples
        assert families["gateway_backend_loop_busy_ms_sum"].samples

    def test_stale_target_drops_histograms(self):
        metrics = self._populated_metrics()
        metrics.set_serving_stats([])  # backend disappeared
        text = metrics.render()[0].decode()
        assert 'target="side:1"' not in text


# ---------------------------------------------------------------------------
# End-to-end: gateway + real sidecar, both HTTP impls
# ---------------------------------------------------------------------------


@contextlib.asynccontextmanager
async def observed_env(impl: str, **serving_kw):
    from ggrmcp_tpu.gateway.app import Gateway
    from tests.test_gateway_http import gateway_config
    from tests.test_serving import Sidecar, serving_cfg

    side = Sidecar(serving_cfg(**serving_kw))
    port = await side.start(0)
    gw = Gateway(gateway_config(impl), targets=[f"localhost:{port}"])
    await gw.start()
    base = f"http://127.0.0.1:{gw.port}"
    client = aiohttp.ClientSession(base_url=base)
    try:
        yield side, gw, client
    finally:
        await client.close()
        await gw.stop()
        await side.stop()


async def _generate_call(client, trace_id: str, max_new: int = 4):
    resp = await client.post("/", json={
        "jsonrpc": "2.0", "method": "tools/call", "id": 1,
        "params": {
            "name": "ggrmcp_tpu_generateservice_generate",
            "arguments": {"prompt": "observe me", "maxNewTokens": max_new},
        },
    }, headers={"X-Trace-Id": trace_id})
    data = await resp.json()
    assert "error" not in data, data
    assert resp.headers["X-Trace-Id"] == trace_id
    return data


class TestTraceLinkedPostmortems:
    @pytest.mark.parametrize("impl", ["fastlane", "aiohttp"])
    async def test_debug_endpoints_link_one_trace(self, impl):
        """The acceptance walk: one completed tool call's trace id
        resolves to a request record (/debug/requests?trace_id=) and to
        the tick records it decoded in (/debug/ticks?trace_id=), on
        both HTTP server implementations."""
        trace_id = f"trace-obs-{impl}"
        async with observed_env(impl) as (_side, _gw, client):
            await _generate_call(client, trace_id)

            resp = await client.get(
                "/debug/requests", params={"trace_id": trace_id}
            )
            body = await resp.json()
            assert body["traceId"] == trace_id
            [backend] = body["backends"]
            assert backend["enabled"] is True
            [rec] = backend["requests"]
            assert rec["traceId"] == trace_id
            assert rec["finishReason"] in ("stop", "length")
            assert float(rec["ttftMs"]) > 0
            assert float(rec["e2eMs"]) >= float(rec["ttftMs"])
            assert int(rec["tokens"]) >= 1

            resp = await client.get(
                "/debug/ticks", params={"trace_id": trace_id}
            )
            ticks = (await resp.json())["backends"][0]["ticks"]
            assert ticks, "no tick records linked to the trace"
            assert all(trace_id in t["traceIds"] for t in ticks)
            # The request record's tick range brackets the linked ticks.
            seqs = [int(t["seq"]) for t in ticks]
            assert min(seqs) >= int(rec["firstTick"]) >= 1
            assert float(ticks[0]["durationMs"]) > 0

            # Unfiltered listing also serves (the "what just happened"
            # operator view), newest last.
            resp = await client.get("/debug/ticks")
            assert (await resp.json())["backends"][0]["ticks"]

    @pytest.mark.parametrize("impl", ["fastlane", "aiohttp"])
    async def test_metrics_expose_backend_histograms(self, impl):
        async with observed_env(impl) as (_side, _gw, client):
            await _generate_call(client, "trace-metrics")
            text = await (await client.get("/metrics")).text()
            for base in ("ttft_ms", "e2e_ms", "queue_ms",
                         "tick_duration_ms"):
                assert f"gateway_backend_{base}_bucket" in text
                assert f"gateway_backend_{base}_count" in text
            # Parses as a valid exposition end-to-end too.
            from prometheus_client.parser import (
                text_string_to_metric_families,
            )

            families = {
                f.name: f for f in text_string_to_metric_families(text)
            }
            ttft = families["gateway_backend_ttft_ms"]
            count = next(
                s.value for s in ttft.samples
                if s.name.endswith("_count")
            )
            assert count >= 1.0

    async def test_span_carries_ttft_and_tick_attrs(self):
        from ggrmcp_tpu.utils import tracing

        tracing.tracer.clear()
        async with observed_env("fastlane") as (_side, _gw, client):
            await _generate_call(client, "trace-span-attrs")
        spans = [
            s for s in tracing.tracer.recent()
            if s["name"] == "sidecar.generate"
            and s["traceId"] == "trace-span-attrs"
        ]
        assert spans
        attrs = spans[0]["attrs"]
        assert attrs["ttft_ms"] > 0
        assert attrs["first_tick"] >= 1
        assert attrs["last_tick"] >= attrs["first_tick"]

    async def test_disabled_recorder_serves_with_empty_rings(self):
        async with observed_env(
            "fastlane",
            observability=ObservabilityConfig(enabled=False),
        ) as (_side, _gw, client):
            await _generate_call(client, "trace-disabled")
            body = await (await client.get("/debug/requests")).json()
            [backend] = body["backends"]
            assert backend["enabled"] is False
            assert backend["requests"] == []
            # Histograms export as zero-count, still valid exposition.
            text = await (await client.get("/metrics")).text()
            from prometheus_client.parser import (
                text_string_to_metric_families,
            )

            list(text_string_to_metric_families(text))


class TestTimePartitionSurfaces:
    """ISSUE 26 end to end through the gateway: the rings behind
    /debug/ticks, the new ServingStats pairs, the allocator's peak."""

    @pytest.mark.parametrize("impl", ["fastlane", "aiohttp"])
    async def test_debug_ticks_carries_admissions_and_handoffs(self, impl):
        trace_id = f"trace-adm-{impl}"
        async with observed_env(impl) as (_side, _gw, client):
            await _generate_call(client, trace_id, max_new=6)
            body = await (await client.get("/debug/ticks")).json()
            [backend] = body["backends"]
            [adm] = backend["admissions"]
            assert adm["family"] == "single" and adm["traceIds"] == [trace_id]
            assert float(adm["durationMs"]) > 0
            kinds = {h["kind"] for h in backend["handoffs"]}
            assert {"admit", "tick"} <= kinds
            tick = backend["ticks"][0]
            assert tick["phaseMarks"][0] == "sync"
            assert len(tick["phaseMarks"]) == len(tick["phaseMarkStartMs"])
            # Filtered to the trace: its admission stays, hand-offs
            # (which carry no trace id) are left out.
            body = await (await client.get(
                "/debug/ticks", params={"trace_id": trace_id}
            )).json()
            [backend] = body["backends"]
            assert [a["seq"] for a in backend["admissions"]] == [adm["seq"]]
            assert backend["handoffs"] == []
            # The request record carries both halves of its queue time.
            body = await (await client.get(
                "/debug/requests", params={"trace_id": trace_id}
            )).json()
            [rec] = body["backends"][0]["requests"]
            assert float(rec["pendingMs"]) + float(
                rec["prefillMs"]
            ) == pytest.approx(float(rec["queueMs"]), abs=2e-3)
            # And the timeline draws all of it.
            doc = await (await client.get("/debug/timeline")).json()
            cats = {e.get("cat") for e in doc["traceEvents"]}
            assert {"admission", "loop", "tick.phase"} <= cats

    async def test_stats_carry_the_loop_partition_and_rpc_time(self):
        async with observed_env("fastlane") as (_side, _gw, client):
            for i in range(2):
                await _generate_call(client, f"trace-loop-{i}", max_new=6)
            [s] = (await (await client.get("/stats")).json())["serving"]
            parts = sum(float(s[k]) for k in (
                "loopExecWaitMsSum", "loopWorkMsSum", "loopLagMsSum",
                "loopHostMsSum",
            ))
            assert parts == pytest.approx(float(s["loopBusyMsSum"]))
            assert s["loopLagMsCount"] == s["loopExecWaitMsCount"]
            assert s["pendingMsCount"] == s["prefillMsCount"] == "2"
            assert float(s["pendingMsSum"]) + float(
                s["prefillMsSum"]
            ) == pytest.approx(float(s["queueMsSum"]))
            # The sidecar's handler contains the batcher's e2e.
            assert s["rpcGenerateMsCount"] == "2"
            assert float(s["rpcGenerateMsSum"]) >= float(s["e2eMsSum"])

    async def test_memory_reports_a_peak_only_where_the_backend_has_one(self):
        """The CPU allocator reports no memory stats: both per-device
        lists are absent, never zero-filled."""
        async with observed_env("fastlane") as (_side, _gw, client):
            body = await (await client.get(
                "/debug/memory", params={"reconcile": "0"}
            )).json()
            [backend] = body["backends"]
            assert "devicePeakBytesInUse" not in backend
            assert "deviceBytesInUse" not in backend

    def test_peak_rides_beside_bytes_in_use(self):
        """Unit for the chip's shape: the handler copies both allocator
        figures per device, in mesh order."""
        from ggrmcp_tpu.rpc.pb import serving_pb2

        msg = serving_pb2.MemoryResponse(
            device_bytes_in_use=[10, 11],
            device_peak_bytes_in_use=[12, 13],
        )
        from google.protobuf import json_format

        body = json_format.MessageToDict(msg)
        assert body["devicePeakBytesInUse"] == ["12", "13"]


class TestServingStatsHistogramFlow:
    async def test_stats_rpc_carries_and_merges_histograms(self):
        """ServingStats now carries the bucket fields (tiered: merged
        elementwise across tiers) — asserted through the real RPC via
        /stats so the kwargs construction contract is exercised."""
        from ggrmcp_tpu.core.config import BatchingConfig

        async with observed_env(
            "fastlane",
            batching=BatchingConfig(
                max_batch_size=4, kv_cache_max_seq=256,
                kv_tiers=[[128, 2], [256, 2]],
            ),
        ) as (_side, _gw, client):
            await _generate_call(client, "trace-tiered")
            stats = await (await client.get("/stats")).json()
            [serving] = stats["serving"]
            assert serving["e2eMsCount"] == "1"
            counts = [int(c) for c in serving["e2eMsBucket"]]
            bounds = serving["latencyBucketBoundsMs"]
            assert len(counts) == len(bounds) + 1
            assert sum(counts) == 1
