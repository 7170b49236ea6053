"""Ring-buffer KV cache (models/llama.py forward(ring=True)): writes at
pos % C with absolute-position masking, so a sliding-window model's KV
is bounded by ~window instead of the context. Equivalence contract: as
long as C >= window + step_len - 1 (docs/kv_ring_design.md), logits
must match a contiguous-cache run at every step — the window hides
everything the ring drops."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ggrmcp_tpu.models import llama

CFG = llama.CONFIGS["tiny-mistral"]  # sliding_window = 16
W = CFG.sliding_window


def step_logits(params, cache, tokens, ring):
    logits, cache = llama.forward(params, CFG, tokens, cache, ring=ring)
    return np.asarray(logits), cache


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.PRNGKey(0), CFG)


def run_schedule(params, capacity, ring, steps, kv_dtype=""):
    """Feed `steps` (list of [B, s] chunks) through one cache; collect
    the last-position logits of every step."""
    b = steps[0].shape[0]
    cache = llama.KVCache.create(CFG, b, capacity, kv_dtype)
    outs = []
    for chunk in steps:
        logits, cache = llama.forward(
            params, CFG, jnp.asarray(chunk), cache, ring=ring
        )
        outs.append(np.asarray(logits[:, -1]))
    return outs


def schedule(total, chunk, b=2, seed=0):
    rng = np.random.RandomState(seed)
    tokens = rng.randint(1, 500, (b, total)).astype(np.int32)
    return [
        tokens[:, off : off + chunk] for off in range(0, total, chunk)
    ]


class TestRingEquivalence:
    def test_ring_matches_contiguous_below_capacity(self, params):
        steps = schedule(24, 8)  # total 24 <= C = 32
        ring = run_schedule(params, 32, True, steps)
        flat = run_schedule(params, 32, False, steps)
        for r, f in zip(ring, flat):
            np.testing.assert_allclose(r, f, atol=1e-5)

    def test_ring_matches_contiguous_beyond_capacity(self, params):
        """Total length 48 through a C=24 ring (W=16, chunks of 8 →
        C >= W + s - 1 holds) vs a big contiguous cache: the window
        hides everything the ring overwrote."""
        steps = schedule(48, 8)
        ring = run_schedule(params, 24, True, steps)
        flat = run_schedule(params, 64, False, steps)
        for i, (r, f) in enumerate(zip(ring, flat)):
            np.testing.assert_allclose(r, f, atol=1e-5, err_msg=f"step {i}")

    def test_ring_decode_many_wraps(self, params):
        """Single-token decode across several wrap-arounds at the
        minimal legal capacity for the largest step (the static clobber
        assert is conservative over all offsets: C >= W + s_max - 1)."""
        prefill = schedule(8, 8)
        decode = schedule(40, 1, seed=3)
        ring = run_schedule(params, W + 7, True, prefill + decode)
        flat = run_schedule(params, 64, False, prefill + decode)
        for i, (r, f) in enumerate(zip(ring, flat)):
            np.testing.assert_allclose(r, f, atol=1e-5, err_msg=f"step {i}")

    def test_ring_composes_with_int8_kv(self, params):
        """Slightly looser bound than the float path: the two cache
        widths (24 vs 64) give different reduction trees, and the
        resulting last-bit differences amplify through the int8
        round-trips (~7e-4 observed); top-1 must agree exactly."""
        steps = schedule(48, 8, seed=5)
        ring = run_schedule(params, 24, True, steps, kv_dtype="int8")
        flat = run_schedule(params, 64, False, steps, kv_dtype="int8")
        for i, (r, f) in enumerate(zip(ring, flat)):
            np.testing.assert_allclose(
                r, f, atol=5e-3, rtol=5e-3, err_msg=f"step {i}"
            )
            assert (r.argmax(-1) == f.argmax(-1)).all(), f"step {i}"

    async def test_serving_ring_generation(self):
        """Engine + continuous batcher on a ring cache: total length
        (prompt + new) exceeds the ring capacity and the greedy output
        still matches the engine's contiguous windowed generate."""
        import asyncio

        from ggrmcp_tpu.core.config import (
            BatchingConfig,
            MeshConfig,
            ServingConfig,
        )
        from ggrmcp_tpu.ops.sampling import SamplingConfig
        from ggrmcp_tpu.serving.batching import ContinuousBatcher
        from ggrmcp_tpu.serving.engine import GenerationEngine

        engine = GenerationEngine(
            CFG,
            ServingConfig(
                kv_ring=True,
                mesh=MeshConfig(tensor=2, data=0),
                batching=BatchingConfig(
                    max_batch_size=4, prefill_chunk=8,
                ),
            ),
        )
        assert engine.ring_capacity == W + 8 - 1  # 23
        prompt = [(i * 11 + 3) % 500 + 1 for i in range(30)]
        max_new = 20  # 30 + 20 = 50 >> capacity 23
        expected, _ = engine.generate(
            [prompt], max_new_tokens=max_new, seed=0
        )

        batcher = ContinuousBatcher(
            engine, BatchingConfig(max_batch_size=4, prefill_chunk=8)
        )
        batcher.warmup()
        batcher.start()
        try:

            async def one(seed):
                acc: list[int] = []
                async for ids, _ in batcher.submit(
                    prompt, max_new, SamplingConfig(temperature=0.0),
                    seed=seed,
                ):
                    acc.extend(ids)
                return acc

            out = await one(0)
            # A concurrent pair exercises slot interleaving on the
            # shared ring.
            outs2 = await asyncio.gather(one(1), one(2))

            # Short prompt (<= prefill_chunk): FUSED admission (a
            # fresh mini never wraps, so contiguous == ring layout),
            # then decode wraps the ring anyway.
            short = [7, 3, 9, 4, 2]
            exp_short, _ = engine.generate(
                [short], max_new_tokens=30, seed=0
            )
            got: list[int] = []
            async for ids, _ in batcher.submit(
                short, 30, SamplingConfig(temperature=0.0)
            ):
                got.extend(ids)
        finally:
            await batcher.stop()
        assert out == expected[0]
        assert outs2[0] == expected[0] and outs2[1] == expected[0]
        assert got == exp_short[0]

    async def test_moe_ring_serving_matches_contiguous(self):
        """Ring serving for the MoE family end-to-end: the registered
        windowed config (`tiny-moe-sw`, the Mixtral-v0.1 shape) through
        engine + batcher with kv_ring, wrapping the ring, must match
        the contiguous windowed generate exactly."""
        from ggrmcp_tpu.core.config import BatchingConfig, ServingConfig
        from ggrmcp_tpu.models import moe
        from ggrmcp_tpu.ops.sampling import SamplingConfig
        from ggrmcp_tpu.serving.batching import ContinuousBatcher
        from ggrmcp_tpu.serving.engine import GenerationEngine

        mcfg = moe.CONFIGS["tiny-moe-sw"]
        engine = GenerationEngine(
            mcfg,
            ServingConfig(
                model="tiny-moe-sw",
                kv_ring=True,
                batching=BatchingConfig(max_batch_size=4, prefill_chunk=8),
            ),
        )
        assert engine.ring_capacity == mcfg.sliding_window + 8 - 1
        ref = GenerationEngine(mcfg, ServingConfig(model="tiny-moe-sw"))
        prompt = [(i * 11 + 3) % 500 + 1 for i in range(30)]
        expected, _ = ref.generate([prompt], max_new_tokens=20, seed=0)

        batcher = ContinuousBatcher(
            engine, BatchingConfig(max_batch_size=4, prefill_chunk=8)
        )
        batcher.warmup()
        batcher.start()
        try:
            got: list[int] = []
            async for ids, _ in batcher.submit(
                prompt, 20, SamplingConfig(temperature=0.0), seed=0
            ):
                got.extend(ids)
        finally:
            await batcher.stop()
        assert got == expected[0]

    def test_config_and_engine_rejections(self):
        from ggrmcp_tpu.core import config as cfgmod
        from ggrmcp_tpu.core.config import MeshConfig, ServingConfig
        from ggrmcp_tpu.serving.engine import GenerationEngine

        cfg = cfgmod.default()
        cfg.serving.kv_ring = True
        cfg.serving.batching.kv_tiers = [[64, 2], [256, 2]]
        with pytest.raises(ValueError, match="kv_tiers"):
            cfg.validate()
        cfg.serving.batching.kv_tiers = []
        cfg.validate()  # ok now
        cfg.serving.mesh.stage = 2
        cfg.validate()  # round 3: ring composes with pipeline serving
        cfg.serving.mesh.stage = 1

        with pytest.raises(ValueError, match="sliding-window"):
            GenerationEngine(
                llama.CONFIGS["tiny-llama"],  # no window
                ServingConfig(
                    kv_ring=True, mesh=MeshConfig(tensor=2, data=0)
                ),
            )

        from ggrmcp_tpu.core.config import BatchingConfig

        with pytest.raises(ValueError, match="max_seq_len"):
            GenerationEngine(
                CFG,  # W=16, max_seq_len=1024
                ServingConfig(
                    kv_ring=True, mesh=MeshConfig(tensor=2, data=0),
                    batching=BatchingConfig(prefill_chunk=1024),
                ),
            )

    def test_moe_ring_equivalence(self):
        """The MoE family shares the attention trunk; a windowed MoE
        config must produce identical logits through a ring cache
        (beyond capacity) and a contiguous one."""
        from ggrmcp_tpu.models import moe

        mcfg = moe.CONFIGS["tiny-moe-sw"]
        mparams = moe.init_params(jax.random.PRNGKey(4), mcfg)
        chunks = schedule(48, 8, seed=11)

        def run(capacity, ring):
            cache = moe.KVCache.create(mcfg, 2, capacity)
            outs = []
            for chunk in chunks:
                logits, cache = moe.forward(
                    mparams, mcfg, jnp.asarray(chunk), cache, ring=ring
                )
                outs.append(np.asarray(logits[:, -1]))
            return outs

        ring_outs = run(16 + 8 - 1, True)
        flat_outs = run(64, False)
        for i, (r, f) in enumerate(zip(ring_outs, flat_outs)):
            np.testing.assert_allclose(r, f, atol=1e-5, err_msg=f"step {i}")

    async def test_batcher_chunk_mismatch_rejected(self):
        from ggrmcp_tpu.core.config import (
            BatchingConfig,
            MeshConfig,
            ServingConfig,
        )
        from ggrmcp_tpu.serving.batching import ContinuousBatcher
        from ggrmcp_tpu.serving.engine import GenerationEngine

        engine = GenerationEngine(
            CFG,
            ServingConfig(
                kv_ring=True, mesh=MeshConfig(tensor=2, data=0),
                batching=BatchingConfig(prefill_chunk=8),
            ),
        )
        with pytest.raises(ValueError, match="ring capacity was sized"):
            ContinuousBatcher(engine, BatchingConfig(prefill_chunk=16))

    def test_clobber_capacity_rejected(self, params):
        """C < W + s - 1 would destroy in-window keys before the
        queries attend — the model layer rejects it at trace time."""
        steps = schedule(48, 8, seed=7)
        with pytest.raises(AssertionError, match="clobber"):
            run_schedule(params, W, True, steps)  # C = W: illegal
        plain = llama.CONFIGS["tiny-llama"]  # no sliding window
        with pytest.raises(AssertionError, match="window"):
            llama.forward(
                llama.init_params(jax.random.PRNGKey(1), plain),
                plain,
                jnp.asarray(schedule(8, 8)[0]),
                llama.KVCache.create(plain, 2, 24),
                ring=True,
            )


# Heavy JAX-compile/serving integration module: excluded from the
# fast `make test` signal; always in `make test-all` / CI.
pytestmark = pytest.mark.slow
