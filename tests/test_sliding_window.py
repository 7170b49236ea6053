"""Sliding-window attention (Mistral family): the window mask, its
equivalence to full attention when the window covers the sequence, and
cached (prefill+decode) vs uncached numerics through the tiny-mistral
config (models/llama.py CONFIGS, ops/attention.py window mask)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ggrmcp_tpu.core.config import BatchingConfig, MeshConfig, ServingConfig
from ggrmcp_tpu.models import llama
from ggrmcp_tpu.ops.attention import attention_xla, flash_attention
from ggrmcp_tpu.serving.engine import GenerationEngine

CFG = llama.CONFIGS["tiny-mistral"]


def naive_windowed(q, k, v, window):
    """Reference per-position loop: query i attends keys
    [max(0, i-window+1), i]."""
    b, s, h, d = q.shape
    out = np.zeros_like(np.asarray(q), dtype=np.float32)
    qf = np.asarray(q, np.float32)
    kf = np.asarray(k, np.float32)
    vf = np.asarray(v, np.float32)
    scale = d ** -0.5
    for bi in range(b):
        for i in range(s):
            lo = max(0, i - window + 1)
            scores = np.einsum(
                "hd,khd->hk", qf[bi, i], kf[bi, lo : i + 1]
            ) * scale
            w = np.exp(scores - scores.max(-1, keepdims=True))
            w /= w.sum(-1, keepdims=True)
            out[bi, i] = np.einsum("hk,khd->hd", w, vf[bi, lo : i + 1])
    return out


class TestWindowMask:
    def test_matches_naive_reference(self):
        key = jax.random.PRNGKey(0)
        q = jax.random.normal(key, (2, 12, 4, 8))
        k = jax.random.normal(jax.random.fold_in(key, 1), q.shape)
        v = jax.random.normal(jax.random.fold_in(key, 2), q.shape)
        out = attention_xla(q, k, v, causal=True, window=5)
        ref = naive_windowed(q, k, v, 5)
        np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5)

    def test_window_covering_sequence_equals_full(self):
        key = jax.random.PRNGKey(3)
        q = jax.random.normal(key, (1, 10, 2, 8))
        k = jax.random.normal(jax.random.fold_in(key, 1), q.shape)
        v = jax.random.normal(jax.random.fold_in(key, 2), q.shape)
        full = attention_xla(q, k, v, causal=True)
        windowed = attention_xla(q, k, v, causal=True, window=10)
        np.testing.assert_allclose(
            np.asarray(full), np.asarray(windowed), atol=1e-6
        )

    def test_window_with_offset_and_kv_len(self):
        """Cached-decode shape: one query at absolute position 20 over
        a 32-slot cache with 21 valid keys and window 8 must equal the
        same computation windowed manually."""
        key = jax.random.PRNGKey(7)
        q = jax.random.normal(key, (1, 1, 2, 8))
        k = jax.random.normal(jax.random.fold_in(key, 1), (1, 32, 2, 8))
        v = jax.random.normal(jax.random.fold_in(key, 2), (1, 32, 2, 8))
        out = attention_xla(
            q, k, v, causal=True,
            q_offset=jnp.asarray([20]), kv_len=jnp.asarray([21]), window=8,
        )
        # valid keys: positions 13..20 (window 8 ending at the query)
        ref = attention_xla(
            q, k[:, 13:21], v[:, 13:21], causal=False,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5
        )


class TestFlashWindow:
    """The Pallas kernel's window mask + block skipping (interpret mode
    on CPU) must match the XLA windowed path bit-for-... well, 1e-5."""

    def _rand(self, key, shape):
        return jax.random.normal(key, shape, jnp.float32)

    @pytest.mark.parametrize("window", [64, 128, 200])
    def test_fresh_prefill_parity(self, window):
        from ggrmcp_tpu.ops.attention import flash_attention

        key = jax.random.PRNGKey(11)
        q = self._rand(key, (2, 256, 4, 16))
        kk = self._rand(jax.random.fold_in(key, 1), (2, 256, 2, 16))
        vv = self._rand(jax.random.fold_in(key, 2), (2, 256, 2, 16))
        out = flash_attention(
            q, kk, vv, causal=True, window=window, interpret=True,
            block_q=64, block_k=64,
        )
        k_rep = jnp.repeat(kk, 2, axis=2)
        v_rep = jnp.repeat(vv, 2, axis=2)
        ref = attention_xla(q, k_rep, v_rep, causal=True, window=window)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5
        )

    def test_cached_prefill_parity_with_offsets(self):
        from ggrmcp_tpu.ops.attention import flash_attention

        key = jax.random.PRNGKey(13)
        q = self._rand(key, (2, 64, 4, 16))
        kk = self._rand(jax.random.fold_in(key, 1), (2, 256, 4, 16))
        vv = self._rand(jax.random.fold_in(key, 2), (2, 256, 4, 16))
        q_off = jnp.asarray([128, 70])
        kv_len = jnp.asarray([192, 134])
        out = flash_attention(
            q, kk, vv, causal=True, q_offset=q_off, kv_len=kv_len,
            window=80, interpret=True, block_q=64, block_k=64,
        )
        ref = attention_xla(
            q, kk, vv, causal=True, q_offset=q_off, kv_len=kv_len,
            window=80,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5
        )


class TestFlashOperands:
    """The kernel on the operands serving hands it (bf16), a KV head's
    whole query group a block, and the block sizes it picks. Here and
    not in tests/test_models.py, which is `slow` as a module: these
    run in tier-1."""

    @pytest.mark.parametrize("case", [
        # 32 query heads on 8 KV heads, each row its own offset, kv_len
        # ending inside a key block
        dict(id="gqa_32_on_8_row_offsets", h=32, kvh=8, q_offset=[0, 70],
             kv_len=[128, 198]),
        dict(id="kv_len_inside_a_block", h=8, kvh=2, q_offset=[0, 0],
             kv_len=[100, 37]),
        dict(id="a_row_with_no_keys", h=8, kvh=2, q_offset=[0, 64],
             kv_len=[0, 192]),
        dict(id="reps_1", h=4, kvh=4, q_offset=[0, 70], kv_len=[128, 198]),
        dict(id="blocks_from_the_shapes", h=8, kvh=2, q_offset=[0, 70],
             kv_len=[128, 198], blocks={}),
        dict(id="not_causal", h=8, kvh=2, q_offset=None, kv_len=[384, 150],
             causal=False),
        # the served head width
        dict(id="heads_of_128", h=4, kvh=2, q_offset=[0, 70],
             kv_len=[128, 198], d=128),
        # the window's lower edge inside a 64-key block, between it and
        # the diagonal blocks that run unmasked
        dict(id="window_edge_inside_a_block", h=8, kvh=2, q_offset=[256, 70],
             kv_len=[384, 198], window=100),
        dict(id="window_edge_reps_1", h=4, kvh=4, q_offset=[256, 70],
             kv_len=[384, 198], window=100),
        # narrower than a block: edge and diagonal cut the same block
        dict(id="window_inside_one_block", h=8, kvh=2, q_offset=[256, 70],
             kv_len=[384, 198], window=40),
    ], ids=lambda case: case["id"])
    def test_flash_bf16_operands_match_xla(self, case):
        """bf16 in, as the serving cache hands them over: the kernel's
        products take them as they are (float32 only where it
        accumulates), a KV head's whole query group a block. Held to
        attention_xla on the same bf16 values, which rounds its weights
        to bf16 the same way; both round the output (half an ulp at
        |x| <= 1 is 2e-3). A row that may see no key emits zeros."""
        key = jax.random.PRNGKey(21)
        b, sq, sk, d = 2, 128, 384, case.get("d", 16)
        q = jax.random.normal(key, (b, sq, case["h"], d), jnp.bfloat16)
        k, v = (
            jax.random.normal(jax.random.fold_in(key, i),
                              (b, sk, case["kvh"], d), jnp.bfloat16)
            for i in (1, 2)
        )
        rows = dict(
            causal=case.get("causal", True), window=case.get("window"),
            q_offset=(None if case["q_offset"] is None
                      else jnp.asarray(case["q_offset"], jnp.int32)),
            kv_len=jnp.asarray(case["kv_len"], jnp.int32),
        )
        reps = case["h"] // case["kvh"]
        ref = attention_xla(
            q, jnp.repeat(k, reps, axis=2), jnp.repeat(v, reps, axis=2),
            **rows)
        out = flash_attention(
            q, k, v, interpret=True,
            **case.get("blocks", dict(block_q=64, block_k=64)), **rows)
        assert out.dtype == jnp.bfloat16
        out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
        for row, n in enumerate(case["kv_len"]):
            if n == 0:
                assert not out[row].any()
            else:
                np.testing.assert_allclose(
                    out[row], ref[row], atol=2e-2, rtol=2e-2)

    @pytest.mark.parametrize("sq,sk,reps,blocks", [
        (512, 2048, 4, (128, 512)),  # a chunk of the admission grid
        (256, 2048, 4, (128, 512)),  # a suffix on a reused prefix
        (256, 256, 4, (128, 256)),  # a fresh short prompt
        (512, 2048, 1, (512, 512)),  # no group: the rows are queries
        (512, 2048, 16, (128, 128)),  # a wide group narrows the key block
        (384, 640, 4, (128, 128)),  # what divides
        (64, 64, 2, (64, 64)),  # shorter than a block: one block
    ])
    def test_flash_blocks_from_the_shapes(self, sq, sk, reps, blocks):
        from ggrmcp_tpu.ops.attention import _flash_blocks

        assert _flash_blocks(sq, sk, reps) == blocks


class TestMistralModel:
    def test_cached_matches_uncached(self):
        """Prefill+decode through the cache must reproduce the
        uncached windowed forward's logits at each position."""
        params = llama.init_params(jax.random.PRNGKey(0), CFG)
        tokens = jnp.asarray(
            np.random.RandomState(0).randint(1, 500, (1, 40)), jnp.int32
        )
        full_logits, _ = llama.forward(params, CFG, tokens)  # no cache
        cache = llama.KVCache.create(CFG, 1, 64)
        pre, cache = llama.forward(params, CFG, tokens[:, :39], cache)
        dec, _ = llama.forward(params, CFG, tokens[:, 39:40], cache)
        np.testing.assert_allclose(
            np.asarray(full_logits[:, 38]), np.asarray(pre[:, -1]),
            rtol=2e-4, atol=2e-4,
        )
        np.testing.assert_allclose(
            np.asarray(full_logits[:, 39]), np.asarray(dec[:, -1]),
            rtol=2e-4, atol=2e-4,
        )

    def test_window_actually_limits_context(self):
        """Perturbing a token OUTSIDE the last position's window must
        not change that position's logits; perturbing inside must."""
        params = llama.init_params(jax.random.PRNGKey(1), CFG)
        base = np.random.RandomState(1).randint(1, 500, (1, 40))
        w = CFG.sliding_window  # 16

        def last_logits(tokens):
            logits, _ = llama.forward(
                params, CFG, jnp.asarray(tokens, jnp.int32)
            )
            return np.asarray(logits[0, -1])

        ref = last_logits(base)
        # NOTE: with 4 layers the receptive field is 4*w; position 39's
        # single-LAYER window is [24, 39], but stacking layers lets
        # earlier tokens influence later ones transitively. Only tokens
        # outside the full receptive field are guaranteed inert — with
        # 40 < 4*16 there are none, so test a 1-layer config instead.
        one_layer = dataclasses.replace(CFG, num_layers=1)
        p1 = llama.init_params(jax.random.PRNGKey(2), one_layer)

        def last1(tokens):
            logits, _ = llama.forward(
                p1, one_layer, jnp.asarray(tokens, jnp.int32)
            )
            return np.asarray(logits[0, -1])

        ref1 = last1(base)
        outside = base.copy()
        outside[0, 5] = (outside[0, 5] + 7) % 500 + 1  # pos 5 < 39-16+1
        np.testing.assert_allclose(last1(outside), ref1, atol=1e-5)
        inside = base.copy()
        inside[0, 30] = (inside[0, 30] + 7) % 500 + 1  # inside window
        assert np.abs(last1(inside) - ref1).max() > 1e-4

    def test_engine_serving(self):
        engine = GenerationEngine(
            CFG,
            ServingConfig(
                mesh=MeshConfig(tensor=2, data=0),
                batching=BatchingConfig(
                    max_batch_size=4, kv_cache_max_seq=128
                ),
            ),
        )
        prompts = [[3, 1, 4, 1, 5] * 6, [9, 2, 6]]  # 30 > window of 16
        outs, reasons = engine.generate(prompts, max_new_tokens=6, seed=0)
        assert len(outs) == 2 and all(len(o) <= 6 for o in outs)
        assert all(r in ("length", "stop") for r in reasons)


class TestSPWindowedPrefill:
    """sp_prefill x sliding-window (round-3 compat close): windowed
    ring/Ulysses masking makes the sequence-parallel prefill path legal
    for Mistral-family models; greedy decode must equal the non-SP
    engine exactly."""

    def test_sp_engine_matches_local(self):
        from ggrmcp_tpu.parallel import mesh as mesh_mod

        seq_mesh = mesh_mod.build_mesh(
            MeshConfig(sequence=4, data=0, tensor=1)
        )
        sp_engine = GenerationEngine(
            CFG,
            ServingConfig(
                model="tiny-mistral",
                mesh=MeshConfig(sequence=4, data=0, tensor=1),
                sp_prefill="ring", sp_prefill_min_seq=64,
            ),
            mesh=seq_mesh,
        )
        assert sp_engine.sp_prefill == "ring"  # no longer disabled
        ref_engine = GenerationEngine(
            CFG,
            ServingConfig(model="tiny-mistral", sp_prefill=""),
            mesh=mesh_mod.build_mesh(MeshConfig(sequence=1, tensor=0)),
        )
        # 37 tokens bucket to 64 (>= min_seq, divisible by 4); the
        # prompt exceeds the window of 16 so the mask really bites.
        prompt = list(range(3, 40))
        sp_out, _ = sp_engine.generate([prompt], max_new_tokens=8, seed=0)
        ref_out, _ = ref_engine.generate([prompt], max_new_tokens=8, seed=0)
        assert sp_out == ref_out

    def test_sp_rejected_with_kv_ring(self):
        """kv_ring caches are ring-capacity sized; the sp fresh-prefill
        contract needs the cache sized to the full chunk — the engine
        must refuse the combination loudly."""
        from ggrmcp_tpu.parallel import mesh as mesh_mod

        seq_mesh = mesh_mod.build_mesh(
            MeshConfig(sequence=4, data=0, tensor=1)
        )
        with pytest.raises(ValueError, match="kv_ring"):
            GenerationEngine(
                CFG,
                ServingConfig(
                    model="tiny-mistral",
                    mesh=MeshConfig(sequence=4, data=0, tensor=1),
                    sp_prefill="ring", sp_prefill_min_seq=64,
                    kv_ring=True,
                ),
                mesh=seq_mesh,
            )

