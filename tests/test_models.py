"""Model-plane tests on the virtual 8-device CPU mesh: forward shapes,
KV-cache consistency, RoPE/attention/sampling invariants, sharding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ggrmcp_tpu.models import bert, common, llama
from ggrmcp_tpu.ops.attention import attention_xla, flash_attention
from ggrmcp_tpu.ops.rope import apply_rope
from ggrmcp_tpu.ops.sampling import SamplingConfig, sample, sample_dynamic

CFG = llama.CONFIGS["tiny-llama"]


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def bert_setup():
    cfg = bert.CONFIGS["bert-tiny"]
    return cfg, bert.init_params(jax.random.PRNGKey(1), cfg)


class TestOps:
    def test_rope_zero_position_is_identity(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 4, 32))
        out = apply_rope(x, jnp.zeros((1, 1), jnp.int32))
        np.testing.assert_allclose(out, x, atol=1e-6)

    def test_rope_preserves_norm(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 4, 32))
        pos = jnp.broadcast_to(jnp.arange(8)[None], (2, 8))
        out = apply_rope(x, pos)
        np.testing.assert_allclose(
            jnp.linalg.norm(out, axis=-1), jnp.linalg.norm(x, axis=-1), rtol=1e-5
        )

    def test_attention_causality(self):
        key = jax.random.PRNGKey(0)
        q = jax.random.normal(key, (1, 8, 2, 16))
        k = jax.random.normal(jax.random.fold_in(key, 1), (1, 8, 2, 16))
        v = jax.random.normal(jax.random.fold_in(key, 2), (1, 8, 2, 16))
        out1 = attention_xla(q, k, v, causal=True)
        # Perturbing future K/V must not change past outputs.
        k2 = k.at[:, -1].add(100.0)
        v2 = v.at[:, -1].add(100.0)
        out2 = attention_xla(q, k2, v2, causal=True)
        np.testing.assert_allclose(out1[:, :-1], out2[:, :-1], atol=1e-5)
        assert not np.allclose(out1[:, -1], out2[:, -1])

    def test_flash_matches_xla(self):
        key = jax.random.PRNGKey(3)
        shape = (2, 256, 4, 64)
        q = jax.random.normal(key, shape)
        k = jax.random.normal(jax.random.fold_in(key, 1), shape)
        v = jax.random.normal(jax.random.fold_in(key, 2), shape)
        ref = attention_xla(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                              interpret=True)
        np.testing.assert_allclose(out, ref, atol=2e-3, rtol=2e-3)

    def test_flash_non_causal(self):
        key = jax.random.PRNGKey(4)
        shape = (1, 128, 2, 32)
        q = jax.random.normal(key, shape)
        k = jax.random.normal(jax.random.fold_in(key, 1), shape)
        v = jax.random.normal(jax.random.fold_in(key, 2), shape)
        ref = attention_xla(q, k, v, causal=False)
        out = flash_attention(q, k, v, causal=False, block_q=64, block_k=64,
                              interpret=True)
        np.testing.assert_allclose(out, ref, atol=2e-3, rtol=2e-3)

    def test_flash_gqa_native(self):
        # K/V carry fewer heads; the kernel maps query head → shared KV
        # head, matching XLA-with-repeat numerics.
        key = jax.random.PRNGKey(5)
        q = jax.random.normal(key, (2, 128, 8, 32))
        k = jax.random.normal(jax.random.fold_in(key, 1), (2, 128, 2, 32))
        v = jax.random.normal(jax.random.fold_in(key, 2), (2, 128, 2, 32))
        ref = attention_xla(
            q, jnp.repeat(k, 4, axis=2), jnp.repeat(v, 4, axis=2), causal=True
        )
        out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                              interpret=True)
        np.testing.assert_allclose(out, ref, atol=2e-3, rtol=2e-3)

    def test_flash_cached_prefill_semantics(self):
        # The serving prefill shape: q is a fresh prompt written into a
        # longer cache; per-batch q_offset and kv_len drive the mask.
        key = jax.random.PRNGKey(6)
        b, sq, sk, h, d = 2, 128, 256, 2, 32
        q = jax.random.normal(key, (b, sq, h, d))
        k = jax.random.normal(jax.random.fold_in(key, 1), (b, sk, h, d))
        v = jax.random.normal(jax.random.fold_in(key, 2), (b, sk, h, d))
        q_offset = jnp.array([0, 64], jnp.int32)
        kv_len = jnp.array([128, 192], jnp.int32)
        ref = attention_xla(
            q, k, v, causal=True, q_offset=q_offset, kv_len=kv_len
        )
        out = flash_attention(
            q, k, v, causal=True, q_offset=q_offset, kv_len=kv_len,
            block_q=64, block_k=64, interpret=True,
        )
        np.testing.assert_allclose(out, ref, atol=2e-3, rtol=2e-3)

    def test_flash_sharded_matches_xla(self):
        """shard_map-wrapped flash (batch over data, heads over tensor)
        must match the XLA path — the multi-chip flash route."""
        from functools import partial

        from ggrmcp_tpu.core.config import MeshConfig
        from ggrmcp_tpu.ops.attention import flash_attention_sharded
        from ggrmcp_tpu.parallel import mesh as mesh_mod

        mesh = mesh_mod.build_mesh(MeshConfig(data=2, tensor=4))
        key = jax.random.PRNGKey(8)
        b, s, h, kvh, d = 4, 128, 8, 4, 32
        q = jax.random.normal(key, (b, s, h, d))
        k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, kvh, d))
        v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, kvh, d))
        q_offset = jnp.array([0, 0, 32, 16], jnp.int32)
        kv_len = jnp.array([128, 96, 64, 128], jnp.int32)
        ref = attention_xla(
            q, jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2),
            causal=True, q_offset=q_offset, kv_len=kv_len,
        )
        out = jax.jit(
            partial(
                flash_attention_sharded, mesh=mesh, causal=True,
                block_q=64, block_k=64, interpret=True,
            )
        )(q, k, v, q_offset=q_offset, kv_len=kv_len)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-3, rtol=2e-3)

    def test_dispatcher_flash_mesh_route_and_fallback(self):
        """attention(..., use_flash=True, flash_mesh=...) must take the
        sharded route for shardable shapes and fall back to XLA —
        counted, never silently — for per-call shapes the mesh can't
        take (odd batch)."""
        from functools import partial

        from ggrmcp_tpu.core.config import MeshConfig
        from ggrmcp_tpu.ops.attention import attention, dispatch_counts
        from ggrmcp_tpu.parallel import mesh as mesh_mod

        mesh = mesh_mod.build_mesh(MeshConfig(data=2, tensor=4))
        key = jax.random.PRNGKey(11)
        before = dict(dispatch_counts)

        def run(b):
            q = jax.random.normal(key, (b, 128, 8, 32))
            k = jax.random.normal(jax.random.fold_in(key, 1), (b, 128, 4, 32))
            v = jax.random.normal(jax.random.fold_in(key, 2), (b, 128, 4, 32))
            ref = attention_xla(
                q, jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2),
                causal=True,
            )
            out = jax.jit(
                partial(
                    attention, use_flash=True, flash_mesh=mesh,
                    interpret=True,
                )
            )(q, k, v)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=2e-3, rtol=2e-3)

        run(4)  # shardable → flash_attention_sharded
        assert dispatch_counts["flash_sharded"] == (
            before.get("flash_sharded", 0) + 1
        )
        run(3)  # batch 3 % data 2 != 0 → XLA fallback, on the record
        assert dispatch_counts["xla_fallback"] == (
            before.get("xla_fallback", 0) + 1
        )

    def test_flash_compiles_unless_interpret_is_asked_for(self):
        """No interpreter fallback: off-TPU the default (compiled)
        kernel refuses instead of quietly running interpreted."""
        q = jnp.zeros((1, 128, 2, 32))
        with pytest.raises(Exception, match="(?i)interpret|tpu|mosaic"):
            jax.block_until_ready(flash_attention(q, q, q))
        out = flash_attention(q, q, q, interpret=True)
        assert out.shape == q.shape

    def test_flash_sharded_rejects_bad_shapes(self):
        from ggrmcp_tpu.core.config import MeshConfig
        from ggrmcp_tpu.ops.attention import flash_attention_sharded
        from ggrmcp_tpu.parallel import mesh as mesh_mod

        mesh = mesh_mod.build_mesh(MeshConfig(data=2, tensor=4))
        q = jnp.zeros((3, 128, 8, 32))  # batch 3 % data 2 != 0
        k = jnp.zeros((3, 128, 4, 32))
        with pytest.raises(ValueError, match="divisible"):
            flash_attention_sharded(q, k, k, mesh)
        q = jnp.zeros((4, 128, 8, 32))
        k = jnp.zeros((4, 128, 2, 32))  # kvh 2 % tensor 4 != 0
        with pytest.raises(ValueError, match="kv heads"):
            flash_attention_sharded(q, k, k, mesh)

    def test_attention_dispatcher_gqa(self):
        # The dispatcher accepts narrow K/V and repeats for the XLA path.
        from ggrmcp_tpu.ops.attention import attention

        key = jax.random.PRNGKey(7)
        q = jax.random.normal(key, (1, 16, 4, 16))
        k = jax.random.normal(jax.random.fold_in(key, 1), (1, 16, 2, 16))
        v = jax.random.normal(jax.random.fold_in(key, 2), (1, 16, 2, 16))
        out = attention(q, k, v, causal=True, use_flash=False)
        ref = attention_xla(
            q, jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2), causal=True
        )
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_greedy_sampling(self):
        logits = jnp.array([[0.0, 5.0, 1.0], [2.0, 0.0, -1.0]])
        out = sample(logits, jax.random.PRNGKey(0), SamplingConfig())
        assert out.tolist() == [1, 0]

    def test_top_k_restricts_support(self):
        logits = jnp.array([[10.0, 9.0, -50.0, -60.0]])
        cfg = SamplingConfig(temperature=1.0, top_k=2)
        draws = {
            int(sample(logits, jax.random.PRNGKey(i), cfg)[0]) for i in range(20)
        }
        assert draws <= {0, 1}

    def test_top_p_restricts_support(self):
        logits = jnp.array([[10.0, 1.0, 0.5, 0.1]])
        cfg = SamplingConfig(temperature=1.0, top_p=0.5)
        draws = {
            int(sample(logits, jax.random.PRNGKey(i), cfg)[0]) for i in range(20)
        }
        assert draws == {0}

    def test_dynamic_sampling_mixed_batch(self):
        logits = jnp.array([[0.0, 5.0, 1.0], [10.0, 9.5, -50.0]])
        out = sample_dynamic(
            logits,
            seeds=jnp.array([1, 2], jnp.uint32),
            step=jnp.int32(0),
            temperature=jnp.array([0.0, 1.0]),  # row0 greedy, row1 sampled
            top_k=jnp.array([0, 2], jnp.int32),
            top_p=jnp.array([1.0, 1.0]),
        )
        assert int(out[0]) == 1
        assert int(out[1]) in (0, 1)

    def test_dynamic_greedy_matches_static(self):
        logits = jax.random.normal(jax.random.PRNGKey(7), (4, 100))
        static = sample(logits, jax.random.PRNGKey(0), SamplingConfig())
        dynamic = sample_dynamic(
            logits, jnp.zeros(4, jnp.uint32), jnp.int32(0),
            jnp.zeros(4), jnp.zeros(4, jnp.int32), jnp.ones(4),
        )
        assert static.tolist() == dynamic.tolist()


class TestLlama:
    def test_param_count_matches_analytic(self, params):
        assert common.count_params(params) == llama.num_params(CFG)

    def test_forward_shapes(self, params):
        tokens = jnp.zeros((2, 16), jnp.int32)
        logits, cache = llama.forward(params, CFG, tokens)
        assert logits.shape == (2, 16, CFG.vocab_size)
        assert logits.dtype == jnp.float32
        assert cache is None

    def test_prefill_matches_no_cache(self, params):
        tokens = jnp.array([[3, 1, 4, 1, 5, 9, 2, 6]])
        ref, _ = llama.forward(params, CFG, tokens)
        cache = llama.KVCache.create(CFG, 1, 16)
        got, cache = llama.forward(params, CFG, tokens, cache)
        np.testing.assert_allclose(got, ref, atol=1e-4)
        assert cache.length.tolist() == [8]

    def test_incremental_decode_matches_full(self, params):
        full = jnp.array([[3, 1, 4, 1, 5, 9, 2, 6]])
        ref, _ = llama.forward(params, CFG, full)
        cache = llama.KVCache.create(CFG, 1, 16)
        _, cache = llama.forward(params, CFG, full[:, :5], cache)
        outs = []
        for i in range(5, 8):
            logits, cache = llama.forward(params, CFG, full[:, i : i + 1], cache)
            outs.append(logits[:, 0])
        got = jnp.stack(outs, axis=1)
        np.testing.assert_allclose(got, ref[:, 5:8], atol=1e-3, rtol=1e-3)

    def test_gqa_heads(self):
        assert CFG.num_kv_heads < CFG.num_heads

    def test_known_configs(self):
        cfg8b = llama.CONFIGS["llama3-8b"]
        assert abs(llama.num_params(cfg8b) / 1e9 - 8.0) < 0.5


class TestBert:
    def test_embed_shapes_and_norm(self, bert_setup):
        cfg, params = bert_setup
        tokens = jnp.array([[101, 5, 6, 102, 0, 0]])
        out = bert.embed(params, cfg, tokens)
        assert out.shape == (1, cfg.hidden_dim)
        np.testing.assert_allclose(np.linalg.norm(out, axis=-1), 1.0, atol=1e-5)

    def test_padding_invariance(self, bert_setup):
        cfg, params = bert_setup
        short = jnp.array([[101, 5, 6, 102]])
        padded = jnp.array([[101, 5, 6, 102, 0, 0, 0, 0]])
        e1 = bert.embed(params, cfg, short)
        e2 = bert.embed(params, cfg, padded)
        np.testing.assert_allclose(e1, e2, atol=1e-4)

    def test_pooling_modes(self, bert_setup):
        cfg, params = bert_setup
        tokens = jnp.array([[101, 5, 6, 102]])
        outs = {
            p: bert.embed(params, cfg, tokens, pooling=p)
            for p in ("mean", "cls", "max")
        }
        assert not np.allclose(outs["mean"], outs["cls"])
        assert not np.allclose(outs["mean"], outs["max"])


# Heavy JAX-compile/serving integration module: excluded from the
# fast `make test` signal; always in `make test-all` / CI.
pytestmark = pytest.mark.slow
