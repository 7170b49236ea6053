"""Serving-plane tests: mesh construction, engines, continuous batching,
the sidecar over real gRPC, and gateway→sidecar integration — all on the
virtual 8-device CPU mesh."""

import asyncio
import contextlib
import json

import grpc
import grpc.aio
import jax
import numpy as np
import pytest

from ggrmcp_tpu.core.config import (
    BatchingConfig,
    MeshConfig,
    ServingConfig,
)
from ggrmcp_tpu.models import bert, llama
from ggrmcp_tpu.ops.sampling import SamplingConfig
from ggrmcp_tpu.parallel import mesh as mesh_mod
from ggrmcp_tpu.rpc.pb import serving_pb2
from ggrmcp_tpu.serving import tensors
from ggrmcp_tpu.serving.engine import (
    EmbeddingEngine,
    GenerationEngine,
    bucket_len,
)
from ggrmcp_tpu.serving.sidecar import Sidecar
from ggrmcp_tpu.serving.tokenizer import ByteTokenizer


def serving_cfg(**kw) -> ServingConfig:
    kw.setdefault("mesh", MeshConfig(tensor=2, data=0))
    kw.setdefault(
        "batching", BatchingConfig(max_batch_size=4, kv_cache_max_seq=256)
    )
    return ServingConfig(**kw)


@pytest.fixture(scope="module")
def gen_engine():
    return GenerationEngine(llama.CONFIGS["tiny-llama"], serving_cfg())


@pytest.fixture(scope="module")
def embed_engine():
    return EmbeddingEngine(bert.CONFIGS["bert-tiny"], serving_cfg())


class TestMesh:
    def test_resolve_infers_free_axis(self):
        sizes = mesh_mod.resolve_axis_sizes(MeshConfig(tensor=0), 8)
        assert sizes["tensor"] == 8

    def test_resolve_fixed_plus_free(self):
        sizes = mesh_mod.resolve_axis_sizes(MeshConfig(tensor=2, data=0), 8)
        assert sizes == {
            "data": 4, "fsdp": 1, "tensor": 2,
            "sequence": 1, "expert": 1, "stage": 1,
        }

    def test_resolve_rejects_mismatch(self):
        with pytest.raises(ValueError):
            mesh_mod.resolve_axis_sizes(MeshConfig(tensor=3, data=1), 8)

    def test_build_mesh_axes(self):
        mesh = mesh_mod.build_mesh(MeshConfig(tensor=4, data=0))
        assert mesh.axis_names == mesh_mod.AXES
        assert mesh.devices.size == len(jax.devices())

    def test_compatible_spec_drops_nondividing(self):
        from jax.sharding import PartitionSpec as P

        mesh = mesh_mod.build_mesh(MeshConfig(tensor=4, data=0))
        spec = mesh_mod.compatible_spec(P("tensor", None), (30522, 16), mesh)
        assert spec == P(None, None)
        spec2 = mesh_mod.compatible_spec(P("tensor", None), (128, 16), mesh)
        assert spec2 == P("tensor", None)

    def test_bucket_len(self):
        assert bucket_len(1) == 32
        assert bucket_len(33) == 64
        assert bucket_len(64) == 64
        assert bucket_len(5000, maximum=4096) == 4096


class TestGenerationEngine:
    def test_batch_generate(self, gen_engine):
        outs, reasons = gen_engine.generate(
            [[5, 6, 7], [9, 10, 11, 12]], max_new_tokens=8
        )
        assert [len(o) for o in outs] == [8, 8]
        assert reasons == ["length", "length"]

    def test_stream_matches_batch_greedy(self, gen_engine):
        streamed = list(gen_engine.generate_stream([5, 6, 7], max_new_tokens=8))
        batched, _ = gen_engine.generate([[5, 6, 7]], max_new_tokens=8)
        assert streamed == batched[0]

    def test_sampling_determinism_by_seed(self, gen_engine):
        cfg = SamplingConfig(temperature=0.8, top_k=16)
        a, _ = gen_engine.generate([[5, 6, 7]], 8, cfg, seed=42)
        b, _ = gen_engine.generate([[5, 6, 7]], 8, cfg, seed=42)
        c, _ = gen_engine.generate([[5, 6, 7]], 8, cfg, seed=43)
        assert a == b
        assert a != c  # overwhelmingly likely for 8 tokens over 512 vocab

    def test_model_info(self, gen_engine):
        info = gen_engine.model_info()
        assert info["family"] == "llama"
        assert info["num_devices"] == 8
        assert info["mesh"] == {"data": 4, "tensor": 2}

    def test_weights_never_lowered_as_constants(self, gen_engine,
                                                embed_engine):
        """Weights must ride as jit ARGUMENTS, not closure captures: a
        captured param tree is embedded into the lowered module as
        constants (llama3-8b int8 = 8 GB of HLO — found on the chip:
        every big-model warmup blew its compile budget) and keys the
        persistent compile cache on weight values. tiny-llama is 6.4 MB bf16, so a 1 MB warn threshold
        trips on any regression."""
        import warnings

        if not hasattr(jax.config, "jax_captured_constants_warn_bytes"):
            # This image's jax predates the captured-constants warning
            # knob; the property under test (weights as jit arguments)
            # is structural and covered by the engine design either way.
            pytest.skip("jax lacks jax_captured_constants_warn_bytes")
        prior = jax.config.jax_captured_constants_warn_bytes
        jax.config.update("jax_captured_constants_warn_bytes", 1_000_000)
        try:
            with warnings.catch_warnings():
                warnings.filterwarnings(
                    "error", message=".*constants were captured.*"
                )
                # Shapes/static-args no earlier test compiled, so each
                # call really lowers (module-scoped fixtures share jit
                # caches; a cache hit would make this test vacuous).
                gen_engine.generate([[5, 6, 7]], max_new_tokens=3)
                list(gen_engine.generate_stream(
                    [5] * 40, max_new_tokens=2
                ))
                embed_engine.embed([[101, 5, 102]], pooling="cls")
        finally:
            jax.config.update("jax_captured_constants_warn_bytes", prior)


class TestEmbeddingEngine:
    def test_embed_batch(self, embed_engine):
        out = embed_engine.embed([[101, 5, 102], [101, 6, 7, 8, 102]])
        assert out.shape == (2, 128)
        np.testing.assert_allclose(
            np.linalg.norm(out, axis=-1), 1.0, atol=1e-5
        )

    def test_bucket_stability(self, embed_engine):
        # same inputs, different surrounding batch → same vectors
        a = embed_engine.embed([[101, 5, 102]])
        b = embed_engine.embed([[101, 5, 102], [101, 9, 9, 9, 9, 102]])
        np.testing.assert_allclose(a[0], b[0], atol=1e-4)


class TestTensors:
    def test_roundtrip_float32(self):
        arr = np.random.rand(3, 4).astype(np.float32)
        back = tensors.from_proto(tensors.to_proto(arr))
        np.testing.assert_array_equal(arr, back)

    def test_roundtrip_int(self):
        arr = np.arange(12, dtype=np.int32).reshape(3, 4)
        back = tensors.from_proto(tensors.to_proto(arr))
        np.testing.assert_array_equal(arr, back)

    def test_bfloat16_roundtrip(self):
        import ml_dtypes

        arr = np.array([1.5, -2.25], dtype=ml_dtypes.bfloat16)
        back = tensors.from_proto(tensors.to_proto(arr))
        np.testing.assert_array_equal(
            arr.astype(np.float32), back.astype(np.float32)
        )

    def test_int_values_path(self):
        proto = serving_pb2.Tensor(dtype="int32", shape=[3], int_values=[1, 2, 3])
        np.testing.assert_array_equal(
            tensors.from_proto(proto), np.array([1, 2, 3], np.int32)
        )


class TestFitRequest:
    def test_fit_noop_when_within_limit(self):
        from ggrmcp_tpu.serving.engine import fit_request

        assert fit_request([1, 2, 3], 4, 100) == ([1, 2, 3], 4)

    def test_fit_truncates_prompt_tail(self):
        from ggrmcp_tpu.serving.engine import fit_request

        prompt, max_new = fit_request(list(range(100)), 20, 64)
        assert len(prompt) + max_new + 1 <= 64
        assert prompt[-1] == 99  # tail kept

    def test_fit_caps_max_new(self):
        from ggrmcp_tpu.serving.engine import fit_request

        prompt, max_new = fit_request(list(range(60)), 200, 64)
        assert len(prompt) + max_new + 1 <= 64
        assert max_new >= 1

    def test_long_prompt_generate_does_not_crash(self, gen_engine):
        long_prompt = list(range(1, 200)) * 10  # 1990 tokens > max_seq 1024
        outs, _ = gen_engine.generate([long_prompt], max_new_tokens=4)
        assert len(outs[0]) <= 4


class TestStreamingUTF8:
    def test_stable_prefix_holds_back_partial(self):
        from ggrmcp_tpu.serving.sidecar import _stable_prefix

        assert _stable_prefix("héllo") == "héllo"
        assert _stable_prefix("h�") == "h"
        assert _stable_prefix("ok��") == "ok"

    def test_strip_trailing_pads_keeps_interior_zeros(self):
        from ggrmcp_tpu.serving.sidecar import _strip_trailing_pads

        assert _strip_trailing_pads(np.array([5, 0, 7, 0, 0])) == [5, 0, 7]
        assert _strip_trailing_pads(np.array([0, 0])) == []


class TestTokenizer:
    def test_byte_roundtrip(self):
        tok = ByteTokenizer()
        text = "Hello, Grüße 世界 🚀"
        assert tok.decode(tok.encode(text)) == text

    def test_specials_filtered(self):
        tok = ByteTokenizer()
        ids = [tok.bos_id] + tok.encode("hi") + [tok.eos_id]
        assert tok.decode(ids) == "hi"


# ---------------------------------------------------------------------------
# Sidecar over real gRPC + gateway integration
# ---------------------------------------------------------------------------


@contextlib.asynccontextmanager
async def sidecar_env(model="tiny-llama"):
    side = Sidecar(serving_cfg(model=model))
    port = await side.start(0)
    channel = grpc.aio.insecure_channel(f"localhost:{port}")
    try:
        yield side, channel, port
    finally:
        await channel.close()
        await side.stop()


class TestFusedDecodeTicks:
    """decode_steps_per_tick > 1: same tokens as the per-step loop for
    greedy decoding, correct truncation at non-multiple max_new."""

    async def _collect(self, batcher, prompt, max_new, seed=0):
        out: list[int] = []
        reason = None
        async for ids, reason in batcher.submit(
            prompt, max_new, SamplingConfig(temperature=0.0), seed=seed
        ):
            out.extend(ids)
        return out, reason

    async def test_greedy_matches_per_step_loop(self, gen_engine):
        from ggrmcp_tpu.serving.batching import ContinuousBatcher

        prompt = [3, 1, 4, 1, 5]
        results = {}
        for steps in (1, 4):
            batcher = ContinuousBatcher(
                gen_engine,
                BatchingConfig(
                    max_batch_size=4, kv_cache_max_seq=256,
                    decode_steps_per_tick=steps,
                ),
            )
            batcher.start()
            try:
                results[steps] = await self._collect(batcher, prompt, 8)
            finally:
                await batcher.stop()
        assert results[1] == results[4]

    async def test_max_new_not_multiple_of_tick(self, gen_engine):
        from ggrmcp_tpu.serving.batching import ContinuousBatcher

        batcher = ContinuousBatcher(
            gen_engine,
            BatchingConfig(
                max_batch_size=4, kv_cache_max_seq=256,
                decode_steps_per_tick=4,
            ),
        )
        batcher.start()
        try:
            out, reason = await self._collect(batcher, [3, 1, 4], 5)
            assert reason in ("length", "stop")
            if reason == "length":
                assert len(out) == 5
            else:
                assert len(out) <= 5
        finally:
            await batcher.stop()

    async def test_concurrent_requests_chunked(self, gen_engine):
        from ggrmcp_tpu.serving.batching import ContinuousBatcher

        batcher = ContinuousBatcher(
            gen_engine,
            BatchingConfig(
                max_batch_size=4, kv_cache_max_seq=256,
                decode_steps_per_tick=4,
            ),
        )
        batcher.start()
        try:
            outs = await asyncio.gather(
                *(
                    self._collect(batcher, [2 + i, 7, 1], 6, seed=i)
                    for i in range(6)  # > max_batch_size → queueing
                )
            )
            for out, reason in outs:
                assert reason in ("length", "stop")
                assert len(out) <= 6
        finally:
            await batcher.stop()


class TestPipelinedTicks:
    """pipeline_ticks: dispatch tick N+1 before collecting tick N.
    Token values must equal the synchronous loop's (same programs,
    same device-side feedback); the owner snapshot must keep re-used
    slots from crediting a predecessor's junk tokens."""

    async def _run_all(self, engine, pipeline, prompts, max_new, batch=2):
        from ggrmcp_tpu.serving.batching import ContinuousBatcher

        batcher = ContinuousBatcher(
            engine,
            BatchingConfig(
                max_batch_size=batch, kv_cache_max_seq=256,
                decode_steps_per_tick=4, pipeline_ticks=pipeline,
            ),
        )
        batcher.start()

        async def one(p, seed):
            out: list[int] = []
            reason = None
            async for ids, reason in batcher.submit(
                p, max_new, SamplingConfig(temperature=0.0), seed=seed
            ):
                out.extend(ids)
            return out, reason

        try:
            return await asyncio.gather(
                *(one(p, i) for i, p in enumerate(prompts))
            )
        finally:
            await batcher.stop()

    async def test_pipelined_matches_synchronous(self, gen_engine):
        prompts = [[3, 1, 4, 1], [2, 7, 1], [5, 5, 5, 5, 5], [9, 9]]
        on = await self._run_all(gen_engine, "on", prompts, 8)
        off = await self._run_all(gen_engine, "off", prompts, 8)
        # Greedy decode of independent rows: outputs are a function of
        # the prompt alone, whatever the batching/pipelining timing.
        assert [o for o, _ in on] == [o for o, _ in off]
        for _, reason in on:
            assert reason in ("length", "stop")

    async def test_slot_churn_over_pipeline_lag(self, gen_engine):
        """12 short requests through 2 slots: every slot is re-admitted
        several times while a stale tick for its previous owner is in
        flight — each request still gets exactly its own tokens."""
        prompts = [[3 + (i % 5), 1, 4] for i in range(12)]
        churned = await self._run_all(gen_engine, "on", prompts, 3, batch=2)
        solo = await self._run_all(
            gen_engine, "on", [prompts[0]], 3, batch=2
        )
        for (out, reason), p in zip(churned, prompts):
            assert reason in ("length", "stop")
            if reason == "length":
                assert len(out) == 3
            if p == prompts[0] and reason == solo[0][1]:
                assert out == solo[0][0]

    async def test_unary_over_pipeline(self, gen_engine):
        from ggrmcp_tpu.serving.batching import ContinuousBatcher

        batcher = ContinuousBatcher(
            gen_engine,
            BatchingConfig(
                max_batch_size=2, kv_cache_max_seq=256,
                decode_steps_per_tick=4, pipeline_ticks="on",
            ),
        )
        batcher.start()
        try:
            chunks = [
                (ids, r) async for ids, r in batcher.submit(
                    [3, 1, 4], 6, SamplingConfig(temperature=0.0),
                    unary=True,
                )
            ]
            assert len(chunks) == 1  # one terminal chunk
            ids, reason = chunks[0]
            assert reason in ("length", "stop")
            if reason == "length":
                assert len(ids) == 6
        finally:
            await batcher.stop()


class TestChunkedPrefill:
    """Prompts longer than cfg.prefill_chunk are prefilled in fixed
    chunks; greedy output must equal the engine's whole-prompt path."""

    async def test_long_prompt_matches_fused_prefill(self, gen_engine):
        from ggrmcp_tpu.serving.batching import ContinuousBatcher

        prompt = [(i * 7 + 3) % 500 + 1 for i in range(40)]
        expected, _ = gen_engine.generate([prompt], max_new_tokens=6, seed=0)

        batcher = ContinuousBatcher(
            gen_engine,
            BatchingConfig(
                max_batch_size=4, kv_cache_max_seq=256, prefill_chunk=16
            ),
        )
        batcher.start()
        try:
            out: list[int] = []
            async for ids, reason in batcher.submit(
                prompt, 6, SamplingConfig(temperature=0.0)
            ):
                out.extend(ids)
            assert out == expected[0]
        finally:
            await batcher.stop()

    async def test_mixed_burst_short_and_long(self, gen_engine):
        from ggrmcp_tpu.serving.batching import ContinuousBatcher

        batcher = ContinuousBatcher(
            gen_engine,
            BatchingConfig(
                max_batch_size=4, kv_cache_max_seq=256, prefill_chunk=16
            ),
        )
        batcher.start()

        async def one(prompt, seed):
            out: list[int] = []
            reason = None
            async for ids, reason in batcher.submit(
                prompt, 5, SamplingConfig(temperature=0.0), seed=seed
            ):
                out.extend(ids)
            return out, reason

        try:
            long_p = [(i * 3 + 1) % 500 + 1 for i in range(30)]
            outs = await asyncio.gather(
                one([4, 2], 0), one(long_p, 1), one([9, 9, 9], 2)
            )
            for out, reason in outs:
                assert reason in ("length", "stop")
                assert 1 <= len(out) <= 5
        finally:
            await batcher.stop()


class TestBatcherRecovery:
    async def test_tick_failure_fails_request_then_recovers(self, gen_engine):
        """A decode-tick crash fails in-flight requests with 'error' but
        the batcher (whose tick donated the shared KV cache) rebuilds it
        and serves subsequent requests normally."""
        from ggrmcp_tpu.serving.batching import ContinuousBatcher

        batcher = ContinuousBatcher(
            gen_engine, BatchingConfig(max_batch_size=4, kv_cache_max_seq=256)
        )
        batcher.start()
        try:
            real_tick = batcher._tick_step
            calls = {"n": 0}

            def flaky_tick():
                calls["n"] += 1
                raise RuntimeError("injected device failure")

            batcher._tick_step = flaky_tick
            chunks = [
                r async for _, r in batcher.submit(
                    [3, 1, 4], 4, SamplingConfig(temperature=0.0)
                )
            ]
            assert chunks[-1] == "error" and calls["n"] >= 1

            batcher._tick_step = real_tick
            out: list[int] = []
            reason = None
            async for ids, reason in batcher.submit(
                [3, 1, 4], 4, SamplingConfig(temperature=0.0)
            ):
                out.extend(ids)
            assert reason in ("length", "stop")
            assert len(out) >= 1
        finally:
            await batcher.stop()


def _unary(channel, path, req_cls, resp_cls):
    return channel.unary_unary(
        path,
        request_serializer=req_cls.SerializeToString,
        response_deserializer=resp_cls.FromString,
    )


class TestSidecarGeneration:
    async def test_generate_unary(self):
        async with sidecar_env() as (_, channel, _port):
            gen = _unary(
                channel, "/ggrmcp.tpu.GenerateService/Generate",
                serving_pb2.GenerateRequest, serving_pb2.GenerateResponse,
            )
            resp = await gen(
                serving_pb2.GenerateRequest(
                    prompt="hi", max_new_tokens=6, return_tokens=True
                )
            )
            assert resp.completion_tokens == len(resp.token_ids) <= 6
            assert resp.finish_reason in ("length", "stop")
            assert resp.model_id == "tiny-llama"

    async def test_generate_concurrent_batching(self):
        async with sidecar_env() as (side, channel, _port):
            gen = _unary(
                channel, "/ggrmcp.tpu.GenerateService/Generate",
                serving_pb2.GenerateRequest, serving_pb2.GenerateResponse,
            )
            resps = await asyncio.gather(
                *(
                    gen(serving_pb2.GenerateRequest(
                        prompt=f"req {i}", max_new_tokens=5
                    ))
                    for i in range(6)  # > max_batch_size=4 → queueing
                )
            )
            assert all(r.completion_tokens <= 5 for r in resps)

    async def test_generate_stream(self):
        async with sidecar_env() as (_, channel, _port):
            stream = channel.unary_stream(
                "/ggrmcp.tpu.GenerateService/GenerateStream",
                request_serializer=serving_pb2.GenerateRequest.SerializeToString,
                response_deserializer=serving_pb2.GenerateChunk.FromString,
            )
            chunks = [
                c async for c in stream(
                    serving_pb2.GenerateRequest(prompt="s", max_new_tokens=5)
                )
            ]
            assert chunks[-1].done
            assert chunks[-1].finish_reason in ("length", "stop")

    async def test_model_info(self):
        async with sidecar_env() as (_, channel, _port):
            info = _unary(
                channel, "/ggrmcp.tpu.ModelInfoService/GetModelInfo",
                serving_pb2.ModelInfoRequest, serving_pb2.ModelInfoResponse,
            )
            resp = await info(serving_pb2.ModelInfoRequest())
            assert resp.family == "llama"
            assert resp.num_devices == 8
            assert resp.platform == "cpu"

    async def test_serving_stats(self):
        async with sidecar_env() as (_, channel, _port):
            gen = _unary(
                channel, "/ggrmcp.tpu.GenerateService/Generate",
                serving_pb2.GenerateRequest, serving_pb2.GenerateResponse,
            )
            await gen(serving_pb2.GenerateRequest(
                prompt="count me", max_new_tokens=4
            ))
            stats_rpc = _unary(
                channel, "/ggrmcp.tpu.ModelInfoService/GetServingStats",
                serving_pb2.ServingStatsRequest,
                serving_pb2.ServingStatsResponse,
            )
            stats = await stats_rpc(serving_pb2.ServingStatsRequest())
            assert stats.total_slots >= 1
            assert stats.kv_cache_bytes > 0
            assert stats.decode_steps >= 1
            assert stats.active_slots == 0  # request finished

    async def test_embed_not_registered_on_llama(self):
        # A generation sidecar does not even expose EmbedService —
        # family-scoped registration keeps pooled tool names collision-free.
        async with sidecar_env() as (_, channel, _port):
            embed = _unary(
                channel, "/ggrmcp.tpu.EmbedService/Embed",
                serving_pb2.EmbedRequest, serving_pb2.EmbedResponse,
            )
            with pytest.raises(grpc.aio.AioRpcError) as exc:
                await embed(serving_pb2.EmbedRequest(texts=["x"]))
            assert exc.value.code() == grpc.StatusCode.UNIMPLEMENTED


class TestSidecarEmbedding:
    async def test_embed_texts(self):
        async with sidecar_env(model="bert-tiny") as (_, channel, _port):
            embed = _unary(
                channel, "/ggrmcp.tpu.EmbedService/Embed",
                serving_pb2.EmbedRequest, serving_pb2.EmbedResponse,
            )
            resp = await embed(
                serving_pb2.EmbedRequest(texts=["hello tpu", "second"])
            )
            vecs = tensors.from_proto(resp.embeddings)
            assert vecs.shape == (2, 128)
            assert resp.model_id == "bert-tiny"
            assert resp.compute_ms > 0


class TestCentralizedGateway:
    """BASELINE.md config #5: one gateway, embed + generate backends
    (two sidecars standing in for two TPU slices)."""

    async def test_two_model_backends_one_gateway(self):
        import aiohttp

        from ggrmcp_tpu.core import config as cfgmod
        from ggrmcp_tpu.gateway.app import Gateway

        gen_side = Sidecar(serving_cfg(model="tiny-llama"))
        gen_port = await gen_side.start(0)
        emb_side = Sidecar(serving_cfg(model="bert-tiny"))
        emb_port = await emb_side.start(0)

        cfg = cfgmod.default()
        cfg.server.host = "127.0.0.1"
        cfg.server.port = 0
        cfg.grpc.reconnect.enabled = False
        gw = Gateway(
            cfg, targets=[f"localhost:{gen_port}", f"localhost:{emb_port}"]
        )
        await gw.start()
        try:
            async with aiohttp.ClientSession(
                base_url=f"http://127.0.0.1:{gw.port}"
            ) as client:
                resp = await client.post("/", json={
                    "jsonrpc": "2.0", "method": "tools/call", "id": 1,
                    "params": {
                        "name": "ggrmcp_tpu_generateservice_generate",
                        "arguments": {"prompt": "x", "maxNewTokens": 3},
                    },
                })
                gen_data = await resp.json()
                assert "error" not in gen_data, gen_data
                gen_payload = json.loads(
                    gen_data["result"]["content"][0]["text"]
                )
                assert gen_payload["modelId"] == "tiny-llama"

                resp = await client.post("/", json={
                    "jsonrpc": "2.0", "method": "tools/call", "id": 2,
                    "params": {
                        "name": "ggrmcp_tpu_embedservice_embed",
                        "arguments": {"texts": ["hello"]},
                    },
                })
                emb_data = await resp.json()
                assert "error" not in emb_data, emb_data
                emb_payload = json.loads(
                    emb_data["result"]["content"][0]["text"]
                )
                assert emb_payload["modelId"] == "bert-tiny"

                # stats report both backends healthy
                resp = await client.get("/stats")
                stats = await resp.json()
                assert len(stats["backends"]) == 2
                assert all(b["healthy"] for b in stats["backends"])
        finally:
            await gw.stop()
            await gen_side.stop()
            await emb_side.stop()


class TestGatewayToSidecar:
    """The zero→aha flow: MCP tool call → gateway → sidecar → model."""

    async def test_tpu_model_as_mcp_tool(self):
        import aiohttp

        from ggrmcp_tpu.core import config as cfgmod
        from ggrmcp_tpu.gateway.app import Gateway

        side = Sidecar(serving_cfg())
        port = await side.start(0)
        cfg = cfgmod.default()
        cfg.server.host = "127.0.0.1"
        cfg.server.port = 0
        cfg.grpc.reconnect.enabled = False
        gw = Gateway(cfg, targets=[f"localhost:{port}"])
        await gw.start()
        try:
            async with aiohttp.ClientSession(
                base_url=f"http://127.0.0.1:{gw.port}"
            ) as client:
                resp = await client.post("/", json={
                    "jsonrpc": "2.0", "method": "tools/list", "id": 1
                })
                tools = {t["name"] for t in (await resp.json())["result"]["tools"]}
                assert "ggrmcp_tpu_generateservice_generate" in tools
                assert "ggrmcp_tpu_generateservice_generatestream" in tools
                assert "ggrmcp_tpu_modelinfoservice_getmodelinfo" in tools
                # family-scoped: a llama sidecar exposes no embed tool
                assert "ggrmcp_tpu_embedservice_embed" not in tools

                resp = await client.post("/", json={
                    "jsonrpc": "2.0", "method": "tools/call", "id": 2,
                    "params": {
                        "name": "ggrmcp_tpu_generateservice_generate",
                        "arguments": {"prompt": "hello tpu", "maxNewTokens": 5},
                    },
                })
                data = await resp.json()
                assert "error" not in data, data
                payload = json.loads(data["result"]["content"][0]["text"])
                assert payload["modelId"] == "tiny-llama"
                assert payload["completionTokens"] <= 5

                # /stats surfaces the model plane's live counters
                # (ServingStats fan-out to every sidecar backend).
                resp = await client.get("/stats")
                stats = await resp.json()
                serving = stats["serving"]
                assert len(serving) == 1
                assert serving[0]["target"] == f"localhost:{port}"
                assert int(serving[0]["totalSlots"]) >= 1
                assert int(serving[0]["kvCacheBytes"]) > 0

                # ...and /metrics exports them as per-target gauges.
                resp = await client.get("/metrics")
                text = await resp.text()
                assert "gateway_backend_kv_cache_bytes{" in text
                assert f'target="localhost:{port}"' in text
        finally:
            await gw.stop()
            await side.stop()
