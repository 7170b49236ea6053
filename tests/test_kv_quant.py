"""Int8 KV cache (serving.kv_cache_dtype="int8"): values stored int8
with per-position/head scales — halves KV HBM and decode KV bandwidth.
Numerics must track the bf16 cache closely, and the whole serving
stack (engine generate, continuous batching, chunked prefill, prefix
pool) must run unchanged on the quantized cache.

No reference analogue (the Go gateway executes no models); TPU
serving-plane component (SURVEY.md §7 stage 6)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ggrmcp_tpu.core import config as cfgmod
from ggrmcp_tpu.core.config import (
    BatchingConfig,
    MeshConfig,
    ServingConfig,
)
from ggrmcp_tpu.models import llama
from ggrmcp_tpu.ops.quant import QuantizedArray
from ggrmcp_tpu.ops.sampling import SamplingConfig
from ggrmcp_tpu.serving.batching import ContinuousBatcher
from ggrmcp_tpu.serving.engine import GenerationEngine

CFG = llama.CONFIGS["tiny-llama"]


def serving_cfg(**kw) -> ServingConfig:
    kw.setdefault("kv_cache_dtype", "int8")
    kw.setdefault("mesh", MeshConfig(tensor=2, data=0))
    kw.setdefault(
        "batching", BatchingConfig(max_batch_size=4, kv_cache_max_seq=256)
    )
    return ServingConfig(**kw)


@pytest.fixture(scope="module")
def engine():
    return GenerationEngine(CFG, serving_cfg())


class TestKVQuantNumerics:
    def test_cached_logits_close_to_bf16_cache(self):
        """Prefill+decode through an int8 cache vs the dense cache on
        identical params: logits must agree within quantization noise."""
        params = llama.init_params(jax.random.PRNGKey(0), CFG)
        tokens = jnp.asarray(
            np.random.RandomState(0).randint(1, 500, (2, 24)), jnp.int32
        )
        step = jnp.asarray(
            np.random.RandomState(1).randint(1, 500, (2, 1)), jnp.int32
        )
        outs = {}
        for kv_dtype in ("", "int8"):
            cache = llama.KVCache.create(CFG, 2, 64, kv_dtype)
            logits_p, cache = llama.forward(params, CFG, tokens, cache)
            logits_d, _ = llama.forward(params, CFG, step, cache)
            outs[kv_dtype] = (np.asarray(logits_p), np.asarray(logits_d))
        for a, b in zip(outs[""], outs["int8"]):
            denom = np.maximum(np.abs(a).max(), 1e-6)
            assert np.abs(a - b).max() / denom < 0.05, (
                np.abs(a - b).max(), denom
            )

    def test_cache_halves_hbm(self):
        dense = llama.KVCache.create(CFG, 4, 128)
        quantized = llama.KVCache.create(CFG, 4, 128, "int8")
        assert isinstance(quantized.k, QuantizedArray)
        # int8 values + 1/head_dim scale overhead vs 2-byte dense...
        # tiny-llama is float32 (4-byte), so the ratio is even larger;
        # assert the halving against the dense bytes actually allocated.
        assert quantized.k.nbytes < dense.k.nbytes * 0.6

    def test_unknown_kv_dtype_rejected(self):
        with pytest.raises(ValueError):
            llama.KVCache.create(CFG, 1, 8, "int4")
        cfg = cfgmod.default()
        cfg.serving.kv_cache_dtype = "int4"
        with pytest.raises(ValueError):
            cfg.validate()

    def test_pp_combination_allowed(self):
        """int8 KV composes with pipeline serving since the staged
        forward threads QuantizedArray leaves (parallel/pipeline.py);
        greedy parity is pinned in test_pp_serving.py::TestPPInt8KV."""
        cfg = cfgmod.default()
        cfg.serving.kv_cache_dtype = "int8"
        cfg.serving.mesh.stage = 2
        cfg.validate()


class TestSyntheticWeights:
    """serving.synthetic_weights: direct-int8 random init for perf
    staging of models whose dense init exceeds chip HBM (llama3-8b or
    mistral-7b on one v5e chip; chip_smoke.py serves the latter)."""

    def test_requires_int8_and_no_checkpoint(self):
        cfg = cfgmod.default()
        cfg.serving.synthetic_weights = True
        with pytest.raises(ValueError):
            cfg.validate()  # quantize unset
        cfg.serving.quantize = "int8"
        cfg.validate()
        cfg.serving.checkpoint_path = "/tmp/ckpt"
        with pytest.raises(ValueError):
            cfg.validate()

    def test_engine_serves_from_synthetic_int8(self):
        from ggrmcp_tpu.ops.quant import QuantizedArray as QA

        eng = GenerationEngine(
            llama.CONFIGS["tiny-llama"],
            ServingConfig(
                model="tiny-llama", quantize="int8",
                synthetic_weights=True,
            ),
        )
        # weights really are the quantized structure, never densified
        assert isinstance(eng.params["layers"]["wqkv"], QA)
        assert isinstance(eng.params["lm_head"], QA)
        outs, reasons = eng.generate(
            [[3, 1, 4, 1, 5]], max_new_tokens=6, seed=0
        )
        assert len(outs[0]) <= 6 and reasons[0] in ("length", "stop")


class TestKVQuantServing:
    def test_engine_generate(self, engine):
        outs, lens = engine.generate(
            [[3, 1, 4, 1, 5], [9, 2, 6]], max_new_tokens=6, seed=0
        )
        assert len(outs) == 2 and all(len(o) <= 6 for o in outs)
        assert engine.use_flash is False  # int8 KV pins the XLA path

    async def test_batcher_greedy_deterministic(self, engine):
        """Same prompt twice through the int8 continuous batcher →
        identical greedy outputs (determinism within the config)."""
        prompt = [(i * 7 + 3) % 500 + 1 for i in range(20)]

        async def collect(batcher):
            out = []
            async for ids, _ in batcher.submit(
                prompt, 6, SamplingConfig(temperature=0.0)
            ):
                out.extend(ids)
            return out

        batcher = ContinuousBatcher(
            engine, BatchingConfig(max_batch_size=4, kv_cache_max_seq=256)
        )
        batcher.start()
        try:
            out1 = await collect(batcher)
            out2 = await collect(batcher)
        finally:
            await batcher.stop()
        assert out1 == out2 and len(out1) <= 6

    async def test_chunked_admission_on_int8(self, engine):
        """Chunked prefill on the quantized cache: a long prompt
        admitted through the [T, C] grid reproduces the engine's own
        greedy output, twice."""
        prompt = [(i * 13 + 5) % 500 + 1 for i in range(60)]
        expected, _ = engine.generate([prompt], max_new_tokens=5, seed=0)
        batcher = ContinuousBatcher(
            engine,
            BatchingConfig(
                max_batch_size=4, kv_cache_max_seq=256, prefill_chunk=16,
            ),
        )
        batcher.warmup()
        batcher.start()
        outs = []
        try:
            for _ in range(2):
                out = []
                async for ids, _ in batcher.submit(
                    prompt, 5, SamplingConfig(temperature=0.0)
                ):
                    out.extend(ids)
                outs.append(out)
        finally:
            await batcher.stop()
        assert outs == [expected[0], expected[0]]
