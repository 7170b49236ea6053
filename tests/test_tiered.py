"""Length-tiered KV cache (VERDICT r1 #9): mixed-length admission
without worst-case allocation, correct routing, and end-to-end serving
through the sidecar."""

import numpy as np
import pytest

from ggrmcp_tpu.core.config import BatchingConfig, MeshConfig, ServingConfig
from ggrmcp_tpu.models import llama
from ggrmcp_tpu.ops.sampling import SamplingConfig
from ggrmcp_tpu.serving.engine import GenerationEngine
from ggrmcp_tpu.serving.tiered import TieredBatcher

TIERS = [[64, 3], [256, 1]]


@pytest.fixture(scope="module")
def engine():
    return GenerationEngine(
        llama.CONFIGS["tiny-llama"],
        ServingConfig(model="tiny-llama", mesh=MeshConfig(tensor=2, data=0)),
    )


def test_config_validation():
    from ggrmcp_tpu.core import config as cfgmod

    cfg = cfgmod.default()
    cfg.serving.batching.kv_tiers = [[512, 8], [256, 4]]  # not ascending
    with pytest.raises(ValueError, match="ascending"):
        cfg.validate()
    cfg.serving.batching.kv_tiers = [[512, 8], [4096, 2]]
    cfg.validate()
    cfg.serving.batching.kv_tiers = [[512, 8, 0], [4096, 2]]
    with pytest.raises(ValueError, match=r"\[max_seq, slots\]"):
        cfg.validate()


def test_hbm_headroom_vs_flat_pool(engine):
    """The point of tiering: same worst-case request capacity, less KV
    memory than a flat pool of equal slot count × global max."""
    tiered = TieredBatcher(
        engine, BatchingConfig(kv_tiers=TIERS, max_queue_delay_ms=1.0)
    )
    slots = sum(s for _, s in TIERS)
    flat_bytes = 2 * (  # k + v
        engine.cfg.num_layers * slots * 256  # global max seq
        * engine.cfg.num_kv_heads * engine.cfg.head_dim
        * np.dtype(engine.cfg.jnp_dtype).itemsize
    )
    assert tiered.cache_bytes() < flat_bytes / 2


def test_routing_picks_smallest_fitting_tier(engine):
    tiered = TieredBatcher(
        engine, BatchingConfig(kv_tiers=TIERS, max_queue_delay_ms=1.0)
    )
    short, long_ = tiered.tiers
    assert tiered._route(10, 16) is short
    assert tiered._route(100, 16) is long_
    assert tiered._route(40, 30) is long_  # 40+30+1 > 64
    # Oversized → largest tier (its fit_request clamps).
    assert tiered._route(1000, 64) is long_


async def test_mixed_lengths_generate(engine):
    import asyncio

    tiered = TieredBatcher(
        engine, BatchingConfig(kv_tiers=TIERS, max_queue_delay_ms=2.0)
    )
    tiered.start()

    async def run(prompt_len: int, max_new: int, seed: int):
        ids: list[int] = []
        reason = None
        async for chunk, r in tiered.submit(
            [3 + seed % 40] * prompt_len, max_new,
            SamplingConfig(temperature=0.8), seed=seed,
        ):
            ids.extend(chunk)
            reason = r
        assert reason in ("stop", "length")
        assert len(ids) <= max_new
        return ids

    try:
        # 6 concurrent requests across both tiers (3 short slots force
        # queueing too).
        outs = await asyncio.wait_for(
            asyncio.gather(
                run(5, 6, 1), run(8, 4, 2), run(12, 6, 3),
                run(100, 6, 4), run(5, 5, 5), run(90, 4, 6),
            ),
            timeout=120,
        )
        assert all(len(o) > 0 for o in outs)
    finally:
        await tiered.stop()


async def test_long_prompt_chunked_into_long_tier(engine):
    """Composition of the long-context pieces: a prompt that (a) routes
    to the long tier and (b) exceeds prefill_chunk — so it admits via
    CHUNKED prefill inside the tier — must produce exactly the fused
    whole-prompt greedy output."""
    prompt = [(i * 7 + 3) % 500 + 1 for i in range(100)]
    expected, _ = engine.generate([prompt], max_new_tokens=5, seed=0)

    tiered = TieredBatcher(
        engine,
        BatchingConfig(
            kv_tiers=TIERS, max_queue_delay_ms=1.0, prefill_chunk=32
        ),
    )
    assert tiered._route(len(prompt), 5) is tiered.tiers[-1]
    tiered.start()
    try:
        out: list[int] = []
        async for ids, _reason in tiered.submit(
            prompt, 5, SamplingConfig(temperature=0.0)
        ):
            out.extend(ids)
        assert out == expected[0]
    finally:
        await tiered.stop()


async def test_sidecar_with_tiers():
    import grpc
    import grpc.aio

    from ggrmcp_tpu.rpc.pb import serving_pb2
    from ggrmcp_tpu.serving.sidecar import Sidecar

    side = Sidecar(
        ServingConfig(
            model="tiny-llama",
            mesh=MeshConfig(tensor=2, data=0),
            batching=BatchingConfig(kv_tiers=TIERS, max_queue_delay_ms=2.0),
        )
    )
    port = await side.start(0)
    channel = grpc.aio.insecure_channel(f"localhost:{port}")
    try:
        gen = channel.unary_unary(
            "/ggrmcp.tpu.GenerateService/Generate",
            request_serializer=serving_pb2.GenerateRequest.SerializeToString,
            response_deserializer=serving_pb2.GenerateResponse.FromString,
        )
        resp = await gen(
            serving_pb2.GenerateRequest(
                prompt="tiered", max_new_tokens=5, return_tokens=True
            )
        )
        assert 0 < resp.completion_tokens <= 5
    finally:
        await channel.close()
        await side.stop()


# Heavy JAX-compile/serving integration module: excluded from the
# fast `make test` signal; always in `make test-all` / CI.
pytestmark = pytest.mark.slow


async def test_tiers_on_pp_mesh_match_single_device():
    """Tiers × pipeline stages: each tier's ContinuousBatcher drives
    the staged cached forward; tier routing must not disturb greedy
    output vs an unstaged single-device engine."""
    import jax

    from ggrmcp_tpu.parallel import mesh as mesh_mod

    mesh = mesh_mod.build_mesh(
        MeshConfig(stage=2, tensor=2, data=0), jax.devices()[:4]
    )
    bcfg = BatchingConfig(
        max_batch_size=4, kv_tiers=TIERS, max_queue_delay_ms=1.0,
        prefill_chunk=32,
    )
    pp = GenerationEngine(
        llama.CONFIGS["tiny-llama"],
        ServingConfig(
            model="tiny-llama",
            mesh=MeshConfig(stage=2, tensor=2, data=0),
            batching=bcfg,
        ),
        mesh=mesh,
    )
    assert pp.pp_serving
    ref = GenerationEngine(
        llama.CONFIGS["tiny-llama"],
        ServingConfig(model="tiny-llama"),
        mesh=mesh_mod.build_mesh(MeshConfig(tensor=1), jax.devices()[:1]),
    )
    short = [5, 3, 8]
    long = [(i * 7 + 3) % 500 + 1 for i in range(100)]
    exp_short, _ = ref.generate([short], max_new_tokens=5, seed=0)
    exp_long, _ = ref.generate([long], max_new_tokens=5, seed=0)

    tiered = TieredBatcher(pp, bcfg)
    tiered.warmup()
    tiered.start()
    try:
        for prompt, expected in ((short, exp_short[0]), (long, exp_long[0])):
            out: list[int] = []
            async for ids, _reason in tiered.submit(
                prompt, 5, SamplingConfig(temperature=0.0), seed=0
            ):
                out.extend(ids)
            assert out == expected
    finally:
        await tiered.stop()
