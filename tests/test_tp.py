"""Tensor-parallel serving plane (docs/tensor_parallel_serving.md).

The contract under test, in order of importance:

1. BIT-IDENTITY — greedy outputs on an N-chip tensor mesh are
   byte-equal to the 1-chip run with the SAME weights, across every
   admission path (fused trickle/burst, chunked, interleaved), with
   the paged KV arena on, and under injected tick faults (chaos
   replay). Token ids, not logits:
   multichip reduction order may perturb the last float ulp, but the
   served stream must be the same stream.
2. NO MASQUERADE — a sharding spec silently downgraded to replication
   is counted (engine.spec_downgrades → the mesh_spec_downgrades
   gauge) and the mesh identity (tp_chips/mesh_devices/mesh_shape)
   flows through ServingStats.
3. STABILITY — a repeated same-shape wave adds zero compiles (the
   sharded programs are cached like the single-chip ones).

Runs on the suite's forced multi-device CPU mesh (tier-1, marker
`tp`); `make test-tp` re-runs it alone on a forced 2-device mesh —
the stand-in recipe for a real ≥2-chip TPU window.
"""

import asyncio

import jax
import numpy as np
import pytest

from ggrmcp_tpu.core.config import (
    BatchingConfig,
    MeshConfig,
    ServingConfig,
)
from ggrmcp_tpu.models import llama
from ggrmcp_tpu.ops.sampling import SamplingConfig
from ggrmcp_tpu.parallel import mesh as mesh_mod
from ggrmcp_tpu.serving.batching import ContinuousBatcher
from ggrmcp_tpu.serving.engine import GenerationEngine
from ggrmcp_tpu.utils import failpoints

pytestmark = pytest.mark.tp

GREEDY = SamplingConfig(temperature=0.0)

# No eos id (2) anywhere: parity compares full-length streams.
SHORT_A = [5, 6, 7, 9, 11]
SHORT_B = [13, 3, 44, 210, 87, 6]
# Shared preamble (same first 24 tokens) — the fused same-wave /
# paged-sharing arrival shape.
PRE = [3 + (i * 11 % 490) for i in range(24)]
SHARED_A = PRE + [7, 8, 9]
SHARED_B = PRE + [30, 31]
# Longer than prefill_chunk=32 → the chunked / interleaved path.
LONG = [3 + (i * 7 % 500) for i in range(80)]

WAVE = [SHORT_A, SHORT_B, SHARED_A, SHARED_B]


def _host_params():
    return llama.init_params(
        jax.random.PRNGKey(7), llama.CONFIGS["tiny-llama"]
    )


@pytest.fixture(scope="module")
def params_host():
    # ONE host weight tree shared by every engine: cross-mesh identity
    # is only meaningful over identical weights.
    return _host_params()


@pytest.fixture(scope="module")
def eng1(params_host):
    return GenerationEngine(
        llama.CONFIGS["tiny-llama"], ServingConfig(),
        mesh=mesh_mod.build_mesh(MeshConfig(tensor=1), jax.devices()[:1]),
        params=params_host,
    )


@pytest.fixture(scope="module")
def eng2(params_host):
    return GenerationEngine(
        llama.CONFIGS["tiny-llama"],
        ServingConfig(mesh=MeshConfig(tensor=2, data=0)),
        params=params_host,
    )


def _cfg(**kw) -> BatchingConfig:
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("kv_cache_max_seq", 128)
    kw.setdefault("prefill_chunk", 32)
    return BatchingConfig(**kw)


async def _collect(batcher, prompt, max_new, seed=0, first_event=None):
    out, reason = [], None
    async for ids, reason in batcher.submit(prompt, max_new, GREEDY,
                                            seed=seed):
        if first_event is not None and not first_event.is_set():
            first_event.set()
        out.extend(ids)
    assert reason in ("stop", "length")
    return out


async def _consume(it):
    out, reason = [], None
    async for ids, reason in it:
        out.extend(ids)
    assert reason in ("stop", "length")
    return out


async def _run_wave(engine, cfg, prompts=WAVE, max_new=6):
    batcher = ContinuousBatcher(engine, cfg)
    batcher.start()
    try:
        outs = await _burst(batcher, prompts, max_new)
    finally:
        await batcher.stop()
    return outs, batcher


async def _burst(batcher, prompts, max_new, seed0=0):
    """Enqueue the whole wave synchronously BEFORE yielding to the
    loop: every run groups the admissions identically (one burst), so
    cross-mesh comparisons and compile counts are deterministic."""
    its = [
        batcher.submit(p, max_new, GREEDY, seed=seed0 + i)
        for i, p in enumerate(prompts)
    ]
    return await asyncio.gather(*(_consume(it) for it in its))


@pytest.fixture(scope="module")
def wave_1chip(eng1):
    return asyncio.run(_run_wave(eng1, _cfg()))[0]


@pytest.fixture(scope="module")
def wave_tp(eng2):
    return asyncio.run(_run_wave(eng2, _cfg()))[0]


class TestMeshIdentity:
    def test_mesh_stats_and_proto_roundtrip(self, eng2, wave_tp):
        from ggrmcp_tpu.rpc.pb import serving_pb2

        stats = eng2.mesh_stats()
        assert stats["tp_chips"] == 2
        assert stats["mesh_devices"] == len(jax.devices())
        assert "tensor=2" in stats["mesh_shape"]
        # tiny-llama divides cleanly on tensor=2: NO weight spec was
        # downgraded — this mesh serves real TP, and the gauge proves
        # it (the whole anti-masquerade point).
        assert stats["mesh_spec_downgrades"] == 0
        # And the full batcher stats tree still constructs the proto.
        batcher = ContinuousBatcher(eng2, _cfg())
        serving_pb2.ServingStatsResponse(**batcher.stats())

    def test_downgrade_counted_and_visible(self, params_host):
        """tiny-llama's 4 KV heads cannot shard over tensor=8: the KV
        cache spec must downgrade — COUNTED, not silent."""
        if len(jax.devices()) < 8:
            pytest.skip("needs 8 virtual devices (tier-1 conftest)")
        eng = GenerationEngine(
            llama.CONFIGS["tiny-llama"],
            ServingConfig(mesh=MeshConfig(tensor=8, data=0)),
            params=params_host,
        )
        assert eng.spec_downgrades == 0  # weights all divide by 8
        eng.make_cache(2, 64)
        assert eng.spec_downgrades >= 1  # KVH=4 % tensor=8 → replicated
        assert eng.mesh_stats()["mesh_spec_downgrades"] >= 1

    def test_compatible_spec_observer(self):
        from jax.sharding import PartitionSpec as P

        mesh = mesh_mod.build_mesh(
            MeshConfig(tensor=2, data=0), jax.devices()
        )
        seen = []
        out = mesh_mod.compatible_spec(
            P(None, "tensor"), (4, 7), mesh,
            on_downgrade=lambda dim, e, size, ax: seen.append(
                (dim, e, size, ax)
            ),
        )
        assert out == P(None, None)
        assert seen == [(1, "tensor", 7, 2)]
        # Dropping over a size-1 axis is not a downgrade.
        seen.clear()
        one = mesh_mod.build_mesh(MeshConfig(tensor=1), jax.devices()[:1])
        assert mesh_mod.compatible_spec(
            P("tensor"), (7,), one,
            on_downgrade=lambda *a: seen.append(a),
        ) == P("tensor")
        assert not seen

    def test_mesh_shape_str(self):
        one = mesh_mod.build_mesh(MeshConfig(tensor=1), jax.devices()[:1])
        assert mesh_mod.mesh_shape_str(one) == "single"
        two = mesh_mod.build_mesh(
            MeshConfig(tensor=2, data=1), jax.devices()[:2]
        )
        assert mesh_mod.mesh_shape_str(two) == "tensor=2"


class TestGreedyBitIdentity:
    def test_wave_1chip_vs_tp(self, wave_1chip, wave_tp):
        """Fused trickle/burst + shared-preamble admissions: the served
        token streams are identical on 1 chip and the tensor mesh."""
        assert wave_1chip == wave_tp
        assert all(len(o) >= 1 for o in wave_tp)

    async def test_chunked_and_interleaved_admission(self, eng1, eng2):
        """A long (> prefill_chunk) prompt admitted mid-decode rides
        the tick-fused chunk path on the TP mesh; output identical to
        the 1-chip serialized run."""

        async def run(engine, mode):
            batcher = ContinuousBatcher(
                engine, _cfg(prefill_interleave=mode,
                             prefill_interleave_rows=2,
                             decode_steps_per_tick=1,
                             pipeline_ticks="off"),
            )
            batcher.start()
            try:
                started = asyncio.Event()
                short = asyncio.create_task(
                    _collect(batcher, SHORT_A, 20, first_event=started)
                )
                await started.wait()
                long_out = await _collect(batcher, LONG, 8)
                short_out = await short
            finally:
                await batcher.stop()
            return batcher, short_out, long_out

        _, short1, long1 = await run(eng1, "off")
        b2, short2, long2 = await run(eng2, "on")
        assert b2.interleaved_admissions == 1  # the TP path engaged
        assert short1 == short2
        assert long1 == long2

    @pytest.mark.parametrize("sampling", [
        SamplingConfig(temperature=0.7, top_k=8),
        SamplingConfig(temperature=0.7, top_p=0.8),
        SamplingConfig(temperature=0.7),
    ], ids=["top_k", "top_p", "temperature_alone"])
    async def test_sampled_rows_identical_across_meshes(
        self, eng1, eng2, sampling
    ):
        """Seeded sampling (temperature + top-k) also reproduces across
        meshes: the RNG stream is device-count independent and the
        filtered distributions round the same way on tiny logits. So
        does each branch of the sampler's gates (ops/sampling.py): the
        sort under top-k or top-p, and the draw without it when a
        sampling row sets neither; the predicates are replicated
        per-row scalars beside vocabulary-sharded logits."""

        async def run(engine):
            batcher = ContinuousBatcher(engine, _cfg())
            batcher.start()
            try:
                out = []
                async for ids, reason in batcher.submit(
                    SHORT_B, 8, sampling, seed=123,
                ):
                    out.extend(ids)
            finally:
                await batcher.stop()
            return out

        assert await run(eng1) == await run(eng2)


class TestPagedTimesTP:
    async def test_paged_on_tp_bit_identical_and_shares(
        self, eng2, wave_tp
    ):
        """The paged arena (pages head-sharded over tensor, block
        tables replicated) serves the same streams as the contiguous
        cache on the same mesh — and same-preamble admissions actually
        SHARE pages through the sharded arena."""
        outs, batcher = await _run_wave(
            eng2, _cfg(paged_kv="on", paged_kv_page_size=8)
        )
        assert outs == wave_tp
        stats = batcher.pages.stats()
        assert stats["paged_prefix_hits"] >= 1  # SHARED_B reused PRE's pages
        assert batcher.cache.table.shape[1] == 128 // 8

    async def test_paged_tp_1chip_parity(self, eng1, wave_tp):
        """Transitivity check, closed directly: paged on the 1-chip
        mesh equals flat on the TP mesh."""
        outs, _ = await _run_wave(
            eng1, _cfg(paged_kv="on", paged_kv_page_size=8)
        )
        assert outs == wave_tp


class TestChaosTimesTP:
    @pytest.fixture(autouse=True)
    def clean_failpoints(self):
        failpoints.registry.disarm()
        yield
        failpoints.registry.disarm()

    async def test_tick_failure_replay_tp_bit_identical(
        self, eng2, wave_tp
    ):
        """Injected tick faults on the TP mesh: victims replay with
        their emitted prefix and the streams stay bit-identical —
        recovery rebuilds the SHARDED cache correctly."""
        failpoints.registry.arm("tick_fail", every=4)
        outs, batcher = await _run_wave(eng2, _cfg(tick_retry_limit=8))
        assert batcher.replayed >= 1  # faults actually fired
        assert outs == wave_tp


class TestCompileStability:
    async def test_repeated_wave_adds_no_compiles(self, eng2):
        """Same-shape traffic on the TP mesh reuses every compiled
        program — admission and tick alike."""
        batcher = ContinuousBatcher(eng2, _cfg())
        batcher.start()
        try:
            # Two warm waves: the first tick's output cache carries
            # jit-propagated shardings that can differ from
            # make_cache's out_shardings, so the SECOND wave's
            # admission may legitimately compile once more; steady
            # state is reached there.
            await _burst(batcher, WAVE, 4)
            await _burst(batcher, WAVE, 4, seed0=20)
            before = (
                batcher._tick._cache_size(),
                batcher._admit_full._cache_size(),
                batcher._admit_single._cache_size(),
            )
            await _burst(batcher, WAVE, 4, seed0=10)
            after = (
                batcher._tick._cache_size(),
                batcher._admit_full._cache_size(),
                batcher._admit_single._cache_size(),
            )
        finally:
            await batcher.stop()
        assert after == before


class TestSidecarTPE2E:
    @pytest.fixture(scope="class")
    def tokenizer_file(self, tmp_path_factory):
        """A real byte-level BPE tokenizer.json (the Llama-3 scheme,
        built locally — this environment has no egress for the true
        128,256-vocab file; the watcher ladder supplies it on TPU
        via GGRMCP_BENCH_TOKENIZER)."""
        from tokenizers import Tokenizer, decoders, pre_tokenizers
        from tokenizers.models import BPE
        from tokenizers.trainers import BpeTrainer

        tok = Tokenizer(BPE(unk_token=None))
        tok.pre_tokenizer = pre_tokenizers.ByteLevel(
            add_prefix_space=False
        )
        tok.decoder = decoders.ByteLevel()
        trainer = BpeTrainer(
            vocab_size=300,
            special_tokens=["<pad>", "<s>", "</s>"],
            initial_alphabet=pre_tokenizers.ByteLevel.alphabet(),
            show_progress=False,
        )
        tok.train_from_iterator(
            ["the quick brown fox jumps over the lazy dog"] * 4, trainer
        )
        path = tmp_path_factory.mktemp("tp-tok") / "tokenizer.json"
        tok.save(str(path))
        return str(path)

    async def test_generate_on_tp_mesh_with_hf_tokenizer(
        self, tokenizer_file
    ):
        """tools/call-shaped serving on a tensor mesh with a real HF
        tokenizer: the sidecar builds the mesh FIRST, the batcher ticks
        shard over it, ServingStats carries the mesh identity, and the
        wire text is the HF tokenizer's decode — the CPU stand-in for
        the ≥2-chip llama3-8b capture (watcher stage_8b_tp)."""
        import grpc
        import grpc.aio

        from ggrmcp_tpu.rpc.pb import serving_pb2
        from ggrmcp_tpu.serving.sidecar import Sidecar
        from ggrmcp_tpu.serving.tokenizer import HFTokenizer

        side = Sidecar(ServingConfig(
            model="tiny-llama",
            tokenizer_path=tokenizer_file,
            mesh=MeshConfig(tensor=2, data=0),
            batching=BatchingConfig(max_batch_size=4,
                                    kv_cache_max_seq=128),
        ))
        assert isinstance(side.tokenizer, HFTokenizer)
        assert side.generation.mesh_stats()["tp_chips"] == 2
        port = await side.start(0)
        channel = grpc.aio.insecure_channel(f"localhost:{port}")
        try:
            gen = channel.unary_unary(
                "/ggrmcp.tpu.GenerateService/Generate",
                request_serializer=(
                    serving_pb2.GenerateRequest.SerializeToString
                ),
                response_deserializer=(
                    serving_pb2.GenerateResponse.FromString
                ),
            )
            resp = await gen(serving_pb2.GenerateRequest(
                prompt="the quick brown fox", max_new_tokens=4,
                return_tokens=True,
            ))
            assert 0 < resp.completion_tokens <= 4
            assert resp.text == side.tokenizer.decode(
                list(resp.token_ids)
            )
            stats_rpc = channel.unary_unary(
                "/ggrmcp.tpu.ModelInfoService/GetServingStats",
                request_serializer=(
                    serving_pb2.ServingStatsRequest.SerializeToString
                ),
                response_deserializer=(
                    serving_pb2.ServingStatsResponse.FromString
                ),
            )
            stats = await stats_rpc(serving_pb2.ServingStatsRequest())
            assert stats.tp_chips == 2
            assert stats.mesh_devices == len(jax.devices())
            assert "tensor=2" in stats.mesh_shape
            assert stats.mesh_spec_downgrades == 0
        finally:
            await channel.close()
            await side.stop()


class TestFlagshipFallback:
    def test_hf_checkpoint_optional_falls_back_loudly(self):
        """Weights unobtainable + the explicit opt-in → the sidecar
        serves serving.model random-init on the mesh instead of dying
        (the zero-egress ladder posture for llama3-8b)."""
        from ggrmcp_tpu.serving.sidecar import Sidecar

        side = Sidecar(ServingConfig(
            model="tiny-llama",
            hf_checkpoint_path="/nope/llama3-8b-weights",
            hf_checkpoint_optional=True,
            mesh=MeshConfig(tensor=2, data=0),
            batching=BatchingConfig(max_batch_size=4,
                                    kv_cache_max_seq=128),
        ))
        assert side.generation is not None
        assert side.generation.cfg.name == "tiny-llama"
        assert side.generation.mesh_stats()["tp_chips"] == 2

    def test_missing_checkpoint_without_optin_dies(self):
        """Default posture: a production config naming absent weights
        fails at startup, never quietly serves noise."""
        from ggrmcp_tpu.serving.sidecar import Sidecar

        with pytest.raises(FileNotFoundError):
            Sidecar(ServingConfig(
                model="tiny-llama",
                hf_checkpoint_path="/nope/llama3-8b-weights",
                mesh=MeshConfig(tensor=2, data=0),
            ))


@pytest.mark.slow
class TestLlama38BTP:
    """The flagship geometry end to end — full llama3-8b architecture
    (32 layers, GQA 8 KV heads, 128,256 vocab) random-init on the
    tensor mesh. 16 GB of bf16 weights: slow-marked and env-gated; the
    watcher ladder runs it on a real ≥2-chip window (stage_8b_tp), CI
    proves the mechanism on tiny shapes above."""

    async def test_llama3_8b_generates_on_tp_mesh(self):
        import os

        if os.environ.get("GGRMCP_TP_LLAMA3") != "1":
            pytest.skip("set GGRMCP_TP_LLAMA3=1 (16 GB init + long "
                        "compile; ladder-only)")
        eng = GenerationEngine(
            llama.CONFIGS["llama3-8b"],
            ServingConfig(mesh=MeshConfig(tensor=0)),
        )
        assert eng.mesh_stats()["mesh_spec_downgrades"] == 0
        outs, reasons = eng.generate([[1, 2077, 9906]], max_new_tokens=4)
        assert len(outs[0]) >= 1
