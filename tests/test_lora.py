"""Multi-LoRA serving (ops/lora.py): per-request adapters batched into
one continuous batch. Covers the engine fused path, the batcher path
(mixed adapters in one tick), the sidecar RPC field, and the config
gates — all on the virtual 8-device CPU mesh (TP-sharded base weights
with replicated adapter factors)."""

import asyncio

import grpc
import grpc.aio
import numpy as np
import pytest

from ggrmcp_tpu.core.config import (
    BatchingConfig,
    LoraConfig,
    MeshConfig,
    ServingConfig,
)
from ggrmcp_tpu.models import llama
from ggrmcp_tpu.ops.sampling import SamplingConfig
from ggrmcp_tpu.rpc.pb import serving_pb2
from ggrmcp_tpu.serving.batching import ContinuousBatcher
from ggrmcp_tpu.serving.engine import GenerationEngine
from ggrmcp_tpu.serving.sidecar import Sidecar


def lora_serving(**kw) -> ServingConfig:
    kw.setdefault("mesh", MeshConfig(tensor=2, data=0))
    kw.setdefault(
        "batching", BatchingConfig(max_batch_size=4, kv_cache_max_seq=256)
    )
    kw.setdefault("lora", LoraConfig(adapters=["acme", "beta"], rank=4))
    return ServingConfig(**kw)


async def collect(batcher, prompt, max_new, adapter=0):
    """Submit and drain one request: (tokens, finish_reason)."""
    out: list[int] = []
    reason = None
    async for ids, reason in batcher.submit(
        prompt, max_new, SamplingConfig(temperature=0.0), adapter=adapter
    ):
        out.extend(ids)
    return out, reason


def random_factors(cfg, rank, seed=0, scale=0.2):
    # scale 0.2, not a whisper: the "trained factors take effect"
    # assertions compare GREEDY outputs, so the delta must actually
    # flip an argmax against the random-init model's confident logit
    # margins (0.05 moved logits by ~0.4 without flipping any token).
    rng = np.random.default_rng(seed)
    out = (cfg.num_heads + 2 * cfg.num_kv_heads) * cfg.head_dim
    a = rng.normal(0, scale, (cfg.num_layers, cfg.hidden_dim, rank))
    b = rng.normal(0, scale, (cfg.num_layers, rank, out))
    return a, b


@pytest.fixture(scope="module")
def lora_engine():
    cfg = llama.CONFIGS["tiny-llama"]
    eng = GenerationEngine(cfg, lora_serving())
    eng.set_lora_weights("acme", *random_factors(cfg, 4, seed=1))
    return eng


class TestEngineLora:
    def test_zero_init_adapter_is_noop(self):
        # Fresh engine: every adapter's B factor is zero → exact base.
        eng = GenerationEngine(llama.CONFIGS["tiny-llama"], lora_serving())
        base, _ = eng.generate([[5, 6, 7]], max_new_tokens=6)
        beta, _ = eng.generate([[5, 6, 7]], max_new_tokens=6,
                               adapters=["beta"])
        assert base == beta

    def test_loaded_adapter_changes_output_and_is_isolated(
        self, lora_engine
    ):
        base, _ = lora_engine.generate([[5, 6, 7]], max_new_tokens=8)
        acme, _ = lora_engine.generate(
            [[5, 6, 7]], max_new_tokens=8, adapters=["acme"]
        )
        beta, _ = lora_engine.generate(
            [[5, 6, 7]], max_new_tokens=8, adapters=["beta"]
        )
        assert acme != base  # trained factors take effect
        assert beta == base  # untouched adapter stays a no-op

    def test_mixed_batch_rows_keep_their_adapters(self, lora_engine):
        base, _ = lora_engine.generate([[5, 6, 7]], max_new_tokens=6)
        acme, _ = lora_engine.generate(
            [[5, 6, 7]], max_new_tokens=6, adapters=["acme"]
        )
        mixed, _ = lora_engine.generate(
            [[5, 6, 7], [5, 6, 7]], max_new_tokens=6, adapters=["acme", ""]
        )
        assert mixed[0] == acme[0]
        assert mixed[1] == base[0]

    def test_stream_with_adapter_matches_batch(self, lora_engine):
        streamed = list(lora_engine.generate_stream(
            [5, 6, 7], max_new_tokens=6, adapter="acme"
        ))
        batched, _ = lora_engine.generate(
            [[5, 6, 7]], max_new_tokens=6, adapters=["acme"]
        )
        assert streamed == batched[0]

    def test_unknown_adapter_rejected(self, lora_engine):
        with pytest.raises(ValueError, match="unknown adapter"):
            lora_engine.generate([[5]], 4, adapters=["nope"])

    def test_base_row_is_write_protected(self, lora_engine):
        with pytest.raises(ValueError, match="base adapter"):
            lora_engine.set_lora_weights(
                "", *random_factors(lora_engine.cfg, 4)
            )

    def test_gates(self):
        with pytest.raises(ValueError, match="lora is not supported for the moe family"):
            from ggrmcp_tpu.models import moe

            GenerationEngine(
                moe.CONFIGS["tiny-moe"],
                lora_serving(),
            )


class TestBatcherLora:
    async def test_mixed_adapters_one_tick(self, lora_engine):
        """Concurrent base/acme requests share the slot pool and each
        gets its own adapter's tokens — the whole point of batched
        multi-LoRA (no bucketing by adapter)."""
        batcher = ContinuousBatcher(
            lora_engine,
            BatchingConfig(max_batch_size=4, kv_cache_max_seq=256,
                           decode_steps_per_tick=4),
        )
        batcher.start()
        try:
            acme_id = lora_engine.resolve_adapter("acme")
            results = await asyncio.gather(
                collect(batcher, [5, 6, 7], 6, adapter=acme_id),
                collect(batcher, [5, 6, 7], 6, adapter=0),
                collect(batcher, [5, 6, 7], 6, adapter=acme_id),
            )
            solo_acme, _ = lora_engine.generate(
                [[5, 6, 7]], max_new_tokens=6, adapters=["acme"]
            )
            solo_base, _ = lora_engine.generate(
                [[5, 6, 7]], max_new_tokens=6
            )
            assert results[0][0] == solo_acme[0]
            assert results[1][0] == solo_base[0]
            assert results[2][0] == solo_acme[0]
        finally:
            await batcher.stop()

    async def test_chunked_prefill_carries_adapter(self, lora_engine):
        """A prompt past prefill_chunk takes the chunked admission path
        — its chunks must run under the request's adapter too."""
        batcher = ContinuousBatcher(
            lora_engine,
            BatchingConfig(max_batch_size=2, kv_cache_max_seq=256,
                           prefill_chunk=32),
        )
        batcher.start()
        try:
            prompt = [5 + (i % 7) for i in range(48)]  # > prefill_chunk
            acme_id = lora_engine.resolve_adapter("acme")
            chunked, reason = await collect(
                batcher, prompt, 6, adapter=acme_id
            )
            assert reason in ("length", "stop")
            solo, _ = lora_engine.generate(
                [prompt], max_new_tokens=6, adapters=["acme"]
            )
            assert chunked == solo[0]
        finally:
            await batcher.stop()


class TestLoraSafety:
    """Review-driven hazards: silent gather clipping on out-of-range
    ids, broadcasting factor installs."""

    def test_adapter_id_range_checked(self, lora_engine):
        with pytest.raises(ValueError, match="out of range"):
            lora_engine.generate([[5]], 4, adapters=[7])
        with pytest.raises(ValueError, match="out of range"):
            lora_engine.generate([[5]], 4, adapters=[-1])
        with pytest.raises(ValueError, match="adapters for"):
            lora_engine.generate([[5]], 4, adapters=[0, 0])

    def test_factor_shapes_checked(self, lora_engine):
        cfg = lora_engine.cfg
        a, b = random_factors(cfg, 4)
        with pytest.raises(ValueError, match="factor shapes"):
            lora_engine.set_lora_weights("beta", a[0], b)  # missing L axis


class TestLoraCompositions:
    """LoRA × the serving machinery it must ride: pipelined ticks
    (owner snapshots + device-resident feedback + per-slot adapter
    arrays), length-tiered pools, and int8 weight quantization (the
    delta applies on top of a QuantizedArray qkv matmul)."""

    async def test_mixed_adapters_under_pipelined_ticks(self, lora_engine):
        batcher = ContinuousBatcher(
            lora_engine,
            BatchingConfig(
                max_batch_size=4, kv_cache_max_seq=256,
                decode_steps_per_tick=4, pipeline_ticks="on",
            ),
        )
        batcher.start()
        try:
            acme_id = lora_engine.resolve_adapter("acme")
            got = await asyncio.gather(
                *(collect(batcher, [5, 6, 7], 6, adapter=acme_id if i % 2 else 0)
                  for i in range(6))
            )
            solo_acme, _ = lora_engine.generate(
                [[5, 6, 7]], max_new_tokens=6, adapters=["acme"]
            )
            solo_base, _ = lora_engine.generate([[5, 6, 7]], max_new_tokens=6)
            for i, (out, _) in enumerate(got):
                assert out == (solo_acme[0] if i % 2 else solo_base[0])
        finally:
            await batcher.stop()

    async def test_adapter_routes_through_tiers(self, lora_engine):
        from ggrmcp_tpu.serving.tiered import TieredBatcher

        batcher = TieredBatcher(
            lora_engine,
            BatchingConfig(
                max_batch_size=4, kv_cache_max_seq=128,
                kv_tiers=[[64, 2], [128, 2]],
            ),
        )
        batcher.start()
        try:
            acme_id = lora_engine.resolve_adapter("acme")
            short, _ = await collect(batcher, [5, 6, 7], 6, adapter=acme_id)
            long_p = [5 + (i % 7) for i in range(80)]  # → bigger tier
            long_out, _ = await collect(batcher, long_p, 6, adapter=acme_id)
            solo_s, _ = lora_engine.generate(
                [[5, 6, 7]], max_new_tokens=6, adapters=["acme"]
            )
            solo_l, _ = lora_engine.generate(
                [long_p], max_new_tokens=6, adapters=["acme"]
            )
            assert short == solo_s[0]
            assert long_out == solo_l[0]
        finally:
            await batcher.stop()

    def test_lora_on_int8_weights(self):
        cfg = llama.CONFIGS["tiny-llama"]
        eng = GenerationEngine(
            cfg, lora_serving(quantize="int8"),
        )
        base, _ = eng.generate([[5, 6, 7]], max_new_tokens=6)
        noop, _ = eng.generate(
            [[5, 6, 7]], max_new_tokens=6, adapters=["acme"]
        )
        assert noop == base  # zero-init delta on the quantized matmul
        eng.set_lora_weights("acme", *random_factors(cfg, 4, seed=2,
                                                     scale=0.5))
        tuned, _ = eng.generate(
            [[5, 6, 7]], max_new_tokens=6, adapters=["acme"]
        )
        assert tuned != base


class TestLoraPersistence:
    def test_factors_load_from_npz_dir(self, tmp_path):
        cfg = llama.CONFIGS["tiny-llama"]
        # Scale well past the tiny random model's argmax margin — the
        # assertion is "loaded factors take effect", not subtlety.
        a, b = random_factors(cfg, 4, seed=3, scale=0.5)
        np.savez(tmp_path / "acme.npz", a=a, b=b)
        # beta.npz intentionally absent → stays a no-op
        eng = GenerationEngine(
            cfg, lora_serving(
                lora=LoraConfig(
                    adapters=["acme", "beta"], rank=4, path=str(tmp_path)
                )
            ),
        )
        base, _ = eng.generate([[5, 6, 7]], max_new_tokens=6)
        acme, _ = eng.generate(
            [[5, 6, 7]], max_new_tokens=6, adapters=["acme"]
        )
        beta, _ = eng.generate(
            [[5, 6, 7]], max_new_tokens=6, adapters=["beta"]
        )
        assert acme != base  # loaded factors applied
        assert beta == base  # missing file → no-op

        # loaded-from-disk equals set_lora_weights with the same arrays
        eng2 = GenerationEngine(
            cfg, lora_serving(
                lora=LoraConfig(adapters=["acme", "beta"], rank=4)
            ),
        )
        eng2.set_lora_weights("acme", a, b)
        acme2, _ = eng2.generate(
            [[5, 6, 7]], max_new_tokens=6, adapters=["acme"]
        )
        assert acme2 == acme

    def test_path_traversal_names_rejected(self):
        cfg = llama.CONFIGS["tiny-llama"]
        for bad in ("../other", "a/b", ".hidden"):
            with pytest.raises(ValueError, match="plain name"):
                GenerationEngine(
                    cfg, lora_serving(
                        lora=LoraConfig(adapters=[bad], rank=4)
                    ),
                )

    def test_bad_factor_file_fails_loudly(self, tmp_path):
        cfg = llama.CONFIGS["tiny-llama"]
        np.savez(tmp_path / "acme.npz", a=np.zeros((2, 2)))  # no `b`, bad shape
        with pytest.raises(ValueError, match="lora factors"):
            GenerationEngine(
                cfg, lora_serving(
                    lora=LoraConfig(adapters=["acme"], rank=4,
                                    path=str(tmp_path))
                ),
            )


class TestSidecarLora:
    async def test_adapter_field_round_trip(self):
        serving = lora_serving()
        side = Sidecar(serving)
        port = await side.start(0)
        channel = grpc.aio.insecure_channel(f"localhost:{port}")
        gen = channel.unary_unary(
            "/ggrmcp.tpu.GenerateService/Generate",
            request_serializer=serving_pb2.GenerateRequest.SerializeToString,
            response_deserializer=serving_pb2.GenerateResponse.FromString,
        )
        try:
            base = await gen(serving_pb2.GenerateRequest(
                prompt="hello", max_new_tokens=4
            ))
            via = await gen(serving_pb2.GenerateRequest(
                prompt="hello", max_new_tokens=4, adapter="beta"
            ))
            # zero-init adapter → same tokens as base
            assert via.text == base.text
            with pytest.raises(grpc.aio.AioRpcError) as exc:
                await gen(serving_pb2.GenerateRequest(
                    prompt="hello", max_new_tokens=4, adapter="nope"
                ))
            assert exc.value.code() == grpc.StatusCode.INVALID_ARGUMENT
        finally:
            await channel.close()
            await side.stop()
