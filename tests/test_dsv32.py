"""The `deepseek_v32` member of the latent-attention family
(models/mla_moe.py: q-compressed queries, the sparse-attention indexer
with its own cache plane, group-limited routing, a share of the
experts, YaRN) at its tiny preset, against the benchmark's plain
reference (benchmark/reference_dsv32.py, which shares no code with it).
Every comparison is of logits, not tokens. Contexts pass the preset's
`index_topk` (16), so the selection binds unless a test says not."""

import asyncio
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ggrmcp_tpu.core.config import BatchingConfig, MeshConfig, ServingConfig
from ggrmcp_tpu.models import common, family_module, get_model, llama
from ggrmcp_tpu.models import mla_moe as M
from ggrmcp_tpu.ops import attention as A
from ggrmcp_tpu.ops import rope as rope_ops
from ggrmcp_tpu.ops.sampling import SamplingConfig
from ggrmcp_tpu.serving.batching import ContinuousBatcher
from ggrmcp_tpu.serving.engine import GenerationEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmark import reference_dsv32 as R  # noqa: E402

CFG = M.CONFIGS["tiny-dsv32"]
with open(os.path.join(
        ROOT, "tests", "benchmark", "rehearsal_dsv32", "benchmark",
        "configs", "tiny-dsv32-cpu.json")) as f:
    REF_MODEL = json.load(f)

# float32 on both sides, the same operations in another order (the
# program absorbs W_UK into the query and walks the keys in blocks with
# an online softmax; the reference expands K/V and takes one softmax):
# logits of magnitude ~3.5 agree to ~5e-6, and a selection that differed
# in one key would move them by 1e-2 and more.
ATOL = 2e-4


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: M.init_params(k, CFG))(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def ref():
    weights = R.family_init_weights(jax, REF_MODEL)
    return weights, R.make_layers(jax, REF_MODEL)


@pytest.fixture(scope="module")
def engine():
    return GenerationEngine(
        CFG, ServingConfig(mesh=MeshConfig(tensor=1, data=1)))


def ids_of(n, salt=0):
    rng = np.random.RandomState(salt)
    return [int(t) for t in rng.randint(3, CFG.vocab_size, n)]


def ref_logits(ref, ids, model=REF_MODEL):
    weights, layers = ref
    x = R.hidden_states(jax, model, weights, layers, ids)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    return np.asarray(x @ weights["lm_head"])


def test_registry_has_the_published_model_the_cut_and_the_tiny_member():
    family, full = get_model("deepseek-v3.2")
    _, cut = get_model("deepseek-v3.2-ep16-5l")
    assert family == "mla_moe" and family_module(cut) is M
    assert (full.num_layers, full.first_dense_layers, full.vocab_size,
            full.experts_held) == (61, 3, 129280, None)
    # the cut changes depth, leading dense layers, experts held and the
    # vocabulary, and no width
    assert dataclasses.replace(
        cut, num_layers=61, first_dense_layers=3, vocab_size=129280,
        experts_held=None, name=full.name) == full
    assert (cut.num_layers, cut.first_dense_layers, cut.experts_held,
            cut.vocab_size) == (5, 1, (0, 16), 16160)
    assert (cut.hidden_dim, cut.num_heads, cut.q_lora_rank, cut.latent_dim,
            cut.index_heads, cut.index_head_dim, cut.index_topk,
            cut.num_experts, cut.experts_per_token, cut.n_group,
            cut.topk_group, cut.expert_ffn_dim, cut.ffn_dim) == (
        7168, 128, 1536, 576, 64, 128, 2048, 256, 8, 8, 4, 2048, 18432)
    # ISSUE 33's count: 4,635M parameters, and a token's cache a layer
    assert abs(M.num_params(cut) / 1e6 - 4635.5) < 0.5
    assert cut.kv_planes == ((640,), (128,))
    assert abs(cut.softmax_scale
               - 192 ** -0.5 * (0.1 * np.log(40) + 1) ** 2) < 1e-9
    # every mechanism live in the tiny member
    assert CFG.q_lora_rank and CFG.index_topk and CFG.rope_scaling
    assert 1 < CFG.topk_group < CFG.n_group
    assert CFG.num_experts_held < CFG.num_experts


def test_yarn_frequencies_blend_between_the_two_correction_dimensions():
    """Published numbers: rope 64, theta 10,000, factor 40 over 4,096,
    beta 32 / 1: the correction dimensions are 10 and 23 (the floor of
    10.98 and the ceiling of 23.02), so pairs 0-10 keep f_i, pairs
    23-31 take f_i / 40, pair 16 the ramp's (16 - 10) / 13."""
    f = 1.0 / 10000 ** (np.arange(0, 64, 2) / 64)
    got = np.asarray(rope_ops.rope_freqs(
        64, 10000.0, ("yarn", 40.0, 4096, 32.0, 1.0)))
    np.testing.assert_allclose(got[:11], f[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], f[23:] / 40, rtol=1e-6)
    r = 6 / 13
    np.testing.assert_allclose(
        got[16], f[16] / 40 * r + f[16] * (1 - r), rtol=1e-6)
    assert rope_ops.yarn_softmax_gain(None) == 1.0
    assert rope_ops.yarn_softmax_gain((8.0, 1.0, 4.0, 8192)) == 1.0  # llama3


def test_the_engine_draws_the_references_weights_bit_for_bit(engine, ref):
    weights, _ = ref
    for name, leaf in weights.items():
        stack, _, key = name.partition(".")
        mine = (engine.params[name] if not key else
                engine.params["dense" if stack == "dense" else "layers"][key])
        assert mine.dtype == leaf.dtype and bool((mine == leaf).all()), name
    held = engine.params["layers"]["w_gate"].shape[1]
    assert held == 8 and engine.params["layers"]["router"].shape[-1] == 16


@pytest.mark.parametrize("n", [70, 12])
def test_forward_agrees_with_the_reference(params, ref, n):
    """70 tokens: past position 15 every query selects 16 of its keys.
    12 tokens: no query sees more than `index_topk`, nothing binds."""
    ids = ids_of(n)
    logits, _ = M.forward(params, CFG, jnp.asarray([ids]))
    np.testing.assert_allclose(
        np.asarray(logits[0]), ref_logits(ref, ids), atol=ATOL)


def test_the_selection_is_what_the_reference_is_compared_on(ref):
    """The same reference without its selection gives other logits
    where the selection binds and the same ones where it does not: a
    program that attended every key would fail the tests above."""
    weights, _ = ref
    dense = (weights, R.make_layers(jax, REF_MODEL, select=False))
    long, short = ids_of(70), ids_of(12)
    assert np.abs(ref_logits(dense, long) - ref_logits(ref, long)).max() > 0.1
    np.testing.assert_allclose(
        ref_logits(dense, short), ref_logits(ref, short), atol=1e-6)


def test_prefill_then_decode_through_paged_planes_agrees(params, ref):
    """Prefill 40 tokens and decode 6 through a paged cache with
    scattered pages, latent and indexer key of a token in the same
    page; every step's logits against the reference's one full
    forward. The decode steps take the gather-by-token-index path."""
    ids = ids_of(46, salt=1)
    want = ref_logits(ref, ids)
    cache = llama.PagedKVCache.create(CFG, 2, 64, 12, 8)
    assert cache.k.shape == (3, 12, 8, 128) and cache.v.shape == (3, 12, 8, 32)
    table = np.full((2, 8), 12, np.int32)
    table[0, :6] = [7, 2, 9, 0, 4, 11]
    cache = cache._replace(table=jnp.asarray(table))
    step = jax.jit(lambda p, t, c, v: M.forward(
        p, CFG, t, c, valid=v, with_stats=True))
    tokens = jnp.asarray([ids[:40], [0] * 40])
    valid = jnp.asarray([[True] * 40, [False] * 40])
    logits, cache, _ = step(params, tokens, cache, valid)
    np.testing.assert_allclose(np.asarray(logits[0]), want[:40], atol=ATOL)
    for i in range(40, 46):
        logits, cache, counts = step(
            params, jnp.asarray([[ids[i]], [0]]), cache,
            jnp.asarray([[True], [False]]))
        np.testing.assert_allclose(
            np.asarray(logits[0, 0]), want[i], atol=ATOL)
        # one row selected 16 of its i + 1 keys in each of 3 layers
        assert counts[-3:].tolist() == [3 * 16, 3 * (i + 1), 3]
    assert int(cache.length[0]) == 46
    assert float(jnp.abs(cache.v[:, 7]).max()) > 0  # page 7: positions 0-7


def test_chunked_admission_and_a_suffix_agree(params, ref):
    """What the batcher's admissions run: a contiguous mini cache
    filled chunk by chunk (each chunk's queries select among the chunks
    before them and their own), then a short suffix on the whole
    history (the re-admission of a follow-up turn), then one decode
    step over the contiguous plane."""
    ids = ids_of(90, salt=2)
    want = ref_logits(ref, ids)
    cache = llama.KVCache.create(CFG, 1, 128)
    step = jax.jit(lambda p, t, c: M.forward(p, CFG, t, c))
    got = []
    for lo, hi in ((0, 32), (32, 64), (64, 84), (84, 89), (89, 90)):
        logits, cache = step(params, jnp.asarray([ids[lo:hi]]), cache)
        got.append(np.asarray(logits[0]))
    np.testing.assert_allclose(np.concatenate(got), want, atol=ATOL)


def test_a_chunks_counts_are_its_masks_and_its_scores(params):
    """A chunk's counts are read off what the chunk path made: the
    entries of each query's mask and its finite index scores, for the
    queries that saw more than `index_topk` (16) keys, in 3 layers."""
    ids = ids_of(64, salt=4)
    cache = llama.KVCache.create(CFG, 1, 128)
    step = jax.jit(lambda p, t, c, v: M.forward(
        p, CFG, t, c, valid=v, with_stats=True))
    # positions 0-31: the queries at 16-31 see 17-32 keys
    _, cache, counts = step(
        params, jnp.asarray([ids[:32]]), cache, jnp.ones((1, 32), bool))
    assert counts[-3:].tolist() == [
        3 * 16 * 16, 3 * sum(range(17, 33)), 3 * 16]
    # positions 32-63, the last 8 padding: 24 real queries of 33-56 keys
    valid = jnp.arange(32)[None] < 24
    _, _, counts = step(params, jnp.asarray([ids[32:]]), cache, valid)
    assert counts[-3:].tolist() == [
        3 * 24 * 16, 3 * sum(range(33, 57)), 3 * 24]


def test_a_sparse_chunk_asks_for_the_kernel_with_its_selection(
        params, monkeypatch):
    """`attention_block` over a contiguous plane, 512 queries on a past
    of 512, the last 112 of them padding: it asks `latent_prefill` for
    the kernel, once, with each query's selection and the row's last
    real query. Here the kernel is a stub that attends densely what it
    was handed (folded queries, the plane, the mask); the block's
    output and its three counts are the masked walk's."""
    lp = jax.tree.map(lambda a: a[1], params["layers"])
    cache = llama.KVCache.create(CFG, 1, 1024)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 1, 512, CFG.hidden_dim))
    positions = jnp.arange(1024)[None]
    valid = jnp.arange(512)[None] < 400
    _, planes, _ = M.attention_block(
        x[0], lp, CFG, positions[:, :512], (cache.k, cache.v),
        jnp.asarray([0]), None, 2)

    def chunk():
        before = A.dispatch_counts["sparse_chunk"]
        out, _, counts = M.attention_block(
            x[1], lp, CFG, positions[:, 512:], planes, jnp.asarray([512]),
            None, 2, valid=valid)
        assert A.dispatch_counts["sparse_chunk"] == before + 1
        return np.asarray(out)[valid], counts.tolist()

    want, want_counts = chunk()  # on the CPU the real one answers None
    calls = []

    def stub(q, plane, layer, q_offset, kv_len, last_q, allowed=None, *,
             value_width, scale, **kw):
        calls.append((q, plane, layer, q_offset, kv_len, last_q, allowed, kw))
        keys = plane[layer]  # [B, S_max, W]
        k_pos = jnp.arange(keys.shape[1])[None, None]
        q_pos = q_offset[:, None] + jnp.arange(q.shape[1])[None]
        seen = allowed & (k_pos <= q_pos[..., None]) & (
            k_pos < kv_len[:, None, None])
        scores = jnp.einsum("bshw,bkw->bhsk", q, keys) * scale
        weights = jax.nn.softmax(
            jnp.where(seen[:, None], scores, -1e30), axis=-1)
        return jnp.einsum("bhsk,bkc->bshc", weights, keys[..., :value_width])

    monkeypatch.setattr(A, "latent_prefill", stub)
    got, got_counts = chunk()
    (q, plane, layer, q_offset, kv_len, last_q, allowed, kw), = calls
    assert q.shape == (1, 512, CFG.num_heads, 128)
    # the whole plane, this chunk's latents written: never a copy of a row
    assert plane.shape == planes[0].shape == (3, 1, 1024, 128)
    assert float(jnp.abs(plane[2, 0, 512:912]).min(0).max()) > 0
    assert float(jnp.abs(planes[0][2, 0, 512:]).max()) == 0
    assert (int(layer), q_offset.tolist(), kv_len.tolist(), last_q.tolist()
            ) == (2, [512], [1024], [911])
    assert allowed.shape == (1, 512, 1024) and allowed.dtype == jnp.bool_
    assert set(kw) == {"use_flash", "flash_mesh"}
    picked = np.asarray(allowed[0])
    assert (picked.sum(-1) == CFG.index_topk).all()
    assert not np.triu(picked[:, 512:], k=1).any()  # none past the query
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert got_counts == want_counts == [
        400 * 16, sum(range(513, 913)), 400]


@pytest.mark.parametrize("s", [1, 8])
def test_the_counts_follow_the_path_and_not_the_lengths(
        params, monkeypatch, s):
    """The same step with an indexer that scores every other key only
    (odd positions read -inf): the counts say so. A decode step (s = 1)
    and a suffix (s = 8) over 40 cached tokens."""
    ids = ids_of(40 + s, salt=5)
    cache = llama.KVCache.create(CFG, 1, 64)
    _, cache = M.forward(params, CFG, jnp.asarray([ids[:40]]), cache)
    whole = M.index_scores

    def half(*args):
        scores = whole(*args)
        return jnp.where(jnp.arange(scores.shape[-1]) % 2 == 0, scores,
                         -jnp.inf)

    monkeypatch.setattr(M, "index_scores", half)
    _, _, counts = M.forward(
        params, CFG, jnp.asarray([ids[40:]]), cache, with_stats=True)
    # query t (position 40 + t) is scored keys 0, 2, .. <= 40 + t
    scored = [(40 + t) // 2 + 1 for t in range(s)]
    assert counts[-3:].tolist() == [3 * 16 * s, 3 * sum(scored), 3 * s]
    # and an indexer that scores nothing selects nothing
    monkeypatch.setattr(
        M, "index_scores", lambda *a: jnp.full_like(whole(*a), -jnp.inf))
    _, _, counts = M.forward(
        params, CFG, jnp.asarray([ids[40:]]), cache, with_stats=True)
    assert counts[-3:].tolist() == ([0, 0, 3] if s == 1 else [0, 0, 0])


def test_selection_is_exact_and_ties_go_to_the_lower_position():
    inf = float("inf")
    scores = jnp.asarray([
        [5.0, 1.0, 5.0, 3.0, 5.0, 3.0, -inf, -inf],  # ties at the cut
        [2.0, 7.0, 1.0, -inf, -inf, -inf, -inf, -inf],  # fewer than k
        [0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0],  # all equal
        [1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0, 8.0],
    ])
    mask = np.asarray(M.selection_mask(scores, 4))
    assert mask.tolist() == [
        [True, False, True, True, True, False, False, False],
        [True, True, True, False, False, False, False, False],
        [True, True, True, True, False, False, False, False],
        [False, False, False, False, True, True, True, True],
    ]
    # told how far the keys reach, the sort narrows and the set stays
    for reach in (6, 8):
        got = M.selection_mask(scores, 3, reach=jnp.int32(reach))
        assert np.asarray(got).tolist() == np.asarray(
            M.selection_mask(scores, 3)).tolist()
    assert not np.asarray(M.selection_mask(scores, 3, reach=jnp.int32(0))).any()
    # the decode path's order: lax.top_k keeps the lower index first
    _, idx = jax.lax.top_k(scores, 4)
    assert sorted(idx[0].tolist()) == [0, 2, 3, 4]
    assert idx[2].tolist() == [0, 1, 2, 3]


def test_group_limited_routing_differs_from_the_plain_top_k():
    """16 experts in 4 groups of 4, 2 groups stay, 4 experts a token.
    The four largest scores overall sit in groups 0, 1, 2 and 3; the
    groups' scores (sum of their two largest) are 1.6, 1.0, 1.5, 0.8:
    groups 0 and 2 stay, and the choice is their four best."""
    u = np.zeros((1, 16), np.float32)
    u[0, [0, 1]] = [0.9, 0.7]  # group 0: 1.6
    u[0, [4, 5]] = [0.95, 0.05]  # group 1: 1.0, holds the largest
    u[0, [8, 9]] = [0.8, 0.7]  # group 2: 1.5
    u[0, [12]] = [0.8]  # group 3: 0.8
    grouped = M.choose_experts(jnp.asarray(u), CFG)
    assert sorted(grouped[0].tolist()) == [0, 1, 8, 9]
    plain = M.choose_experts(
        jnp.asarray(u), dataclasses.replace(CFG, n_group=1, topk_group=1))
    assert sorted(plain[0].tolist()) == [0, 4, 8, 12]


def test_the_shares_add_up_to_the_uncut_layer(params):
    """The routed parts that the two shares of the 16 experts compute
    (experts 0-7 and 8-15, each from its own half of the banks), plus
    the shared expert counted once, are the uncut reference's expert
    layer. Float32; the reference sums all 16 experts in expert order,
    the shares sum their own 4-or-fewer hits a token: 1e-5."""
    whole = dict(REF_MODEL, n_routed_experts=16,
                 deployment={"n_routed_experts": 16, "experts_first": 0})
    full_w = R.family_init_weights(jax, whole)
    layers = R.make_layers(jax, whole)
    x = jax.random.normal(jax.random.PRNGKey(3), (40, CFG.hidden_dim))
    w1 = {k.split(".", 1)[1]: v[0] for k, v in full_w.items()
          if k.startswith("moe.")}
    want = np.asarray(layers[1](x, w1))

    banks = ("w_gate", "w_up", "w_down")
    lp = {k: v for k, v in w1.items() if k not in banks}
    for name, width in (("attn_norm", 128), ("mlp_norm", 128),
                        ("kv_norm", 32), ("q_norm", 48), ("idx_k_norm", 32)):
        lp[name] = jnp.ones((width,))
    lp["idx_k_bias"] = jnp.zeros((32,))
    positions = jnp.arange(40)[None]
    att, _, _ = M.attention_block(
        x[None], lp, CFG, positions, None, None, None, 0)
    n = common.rms_norm(att, lp["mlp_norm"], CFG.norm_eps)
    shared = M._swiglu(n[0], lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    total = att[0] + shared
    absent = 0
    for first in (0, 8):
        share_cfg = dataclasses.replace(CFG, experts_held=(first, 8))
        held = tuple(full_w["moe." + b][:, first:first + 8] for b in banks)
        out, stats = M.moe_ffn(n, lp, held, 0, share_cfg)
        total = total + (out[0] - shared)
        absent += int(stats[3])
        assert int(stats[2]) + int(stats[3]) == 40 * CFG.experts_per_token
    # a pair absent from one share is present in the other
    assert absent == 40 * CFG.experts_per_token
    np.testing.assert_allclose(np.asarray(total), want, atol=1e-5)


def test_logits_do_not_depend_on_who_shares_the_batch(params):
    a, b = ids_of(40, salt=4), ids_of(40, salt=5)
    alone, _ = M.forward(params, CFG, jnp.asarray([a]))
    both, _ = M.forward(params, CFG, jnp.asarray([a, b]))
    np.testing.assert_allclose(
        np.asarray(both[0]), np.asarray(alone[0]), atol=1e-5)


@pytest.mark.parametrize("kv_dtype, loose, tight", [
    # int8 with a scale a token keeps 7 bits of both planes (0.03-0.08
    # of logits ~3.5 where nothing is selected)
    ("int8", 0.15, 1e-4),
    # float8_e4m3fn keeps 4: coarser (0.13-0.95), still the same model
    ("fp8", 1.5, 1e-3),
])
def test_quantized_planes_carry_both_kinds_of_state(
        params, kv_dtype, loose, tight):
    """The latent AND the indexer's key go through the cache's storage
    precision. Up to position 15 nothing is selected and the logits
    stay near the float32 cache's (`loose`), without being equal to
    them (`tight`: the planes really are coarser). Past it the coarser
    indexer keys pick other latents for some queries (16 of 17-48
    near-tied random scores), which moves a logit by more than any
    rounding does: there the test asks only that the path runs and
    differs."""
    ids = ids_of(48, salt=6)
    step = jax.jit(lambda p, t, c: M.forward(p, CFG, t, c))

    def run(kind):
        cache = llama.KVCache.create(CFG, 1, 64, kind)
        out, cache = step(params, jnp.asarray([ids[:40]]), cache)
        outs = [np.asarray(out[0])]
        for i in range(40, 48):
            out, cache = step(params, jnp.asarray([[ids[i]]]), cache)
            outs.append(np.asarray(out[0]))
        return np.concatenate(outs), cache

    exact, _ = run("")
    coarse, cache = run(kv_dtype)
    plane = cache.v.q if kv_dtype == "int8" else cache.v
    assert plane.shape == (3, 1, 64, 32)
    assert plane.dtype == (jnp.int8 if kv_dtype == "int8"
                           else jnp.float8_e4m3fn)
    diff = np.abs(coarse - exact).max(-1)
    assert tight < diff[:16].max() < loose
    assert np.isfinite(coarse).all() and diff[16:].max() > tight


# A latent of whole lanes (rank 128 in a 256-wide plane), as the
# kernel's dispatch asks on the chip, and chunks of 256 queries.
LANE = dataclasses.replace(CFG, name="tiny-dsv32-lane", kv_lora_rank=128)
LANE_MODEL = dict(REF_MODEL, kv_lora_rank=128)


@pytest.fixture(scope="module")
def lane_params():
    return jax.jit(lambda k: M.init_params(k, LANE))(jax.random.PRNGKey(0))


def took(before):
    return [A.dispatch_counts[key] - before.get(key, 0)
            for key in ("sparse_chunk", "latent_prefill", "xla_fallback")]


@pytest.mark.parametrize("path", ["walk", "kernel"])
def test_chunks_a_suffix_and_a_step_agree_on_both_paths(
        lane_params, path, request):
    """A contiguous mini cache filled by two chunks of 256 queries (the
    second's tail is padding), a suffix of 60 on the whole history and
    a decode step, every real position's logits against the float32
    reference: through the masked walk (the CPU's path), and with the
    platform answering TPU through the kernel given each query's
    selection (the suffix and the step keep their own paths)."""
    if path == "kernel":
        request.getfixturevalue("latent_prefill_on_tpu")
    ids = ids_of(512, salt=12)
    weights = R.family_init_weights(jax, LANE_MODEL)
    want = ref_logits(
        (weights, R.make_layers(jax, LANE_MODEL)), ids, LANE_MODEL)
    cache = llama.KVCache.create(LANE, 1, 512)
    step = jax.jit(lambda p, t, c, v: M.forward(p, LANE, t, c, valid=v))
    before = dict(A.dispatch_counts)
    got = []
    # the first chunk's tail is padding too: the batcher then sets the
    # row's length back, as here
    for lo, hi, real in ((0, 256, 250), (250, 506, 201), (451, 511, 60),
                         (511, 512, 1)):
        tokens = np.zeros((1, hi - lo), np.int32)
        tokens[0, :real] = ids[lo:lo + real]
        logits, cache = step(
            lane_params, jnp.asarray(tokens), cache,
            jnp.arange(hi - lo)[None] < real)
        cache = cache._replace(length=jnp.asarray([lo + real], jnp.int32))
        got.append(np.asarray(logits[0, :real]))
    # the chunk program's two layer scans trace their bodies once each
    # (both chunks run it), then the suffix's; the step gathers
    assert took(before) == [4, 2 if path == "kernel" else 0, 0]
    np.testing.assert_allclose(np.concatenate(got), want, atol=ATOL)


KEEPS_THE_WALK = {
    "fp8_plane": dict(kv_dtype="fp8"),
    "int8_plane": dict(kv_dtype="int8"),
    "the_paged_arena": dict(paged=True),
    "a_suffix_of_128": dict(s=128),
}


@pytest.mark.parametrize("case", KEEPS_THE_WALK, ids=list(KEEPS_THE_WALK))
def test_a_sparse_chunk_keeps_the_walk(
        lane_params, latent_prefill_on_tpu, case):
    """On a TPU, with lane-wide latents: a float8 or quantized plane
    (the benchmark's `fp8_kv` control reads the walk), the paged arena
    and 128 queries or fewer still select a query, in the masked walk:
    no kernel program, and no fallback counted (they never were the
    kernel's kind)."""
    c = dict(kv_dtype="", s=256, paged=False)
    c.update(KEEPS_THE_WALK[case])
    if c["paged"]:
        cache = llama.PagedKVCache.create(LANE, 1, 512, 32, 16)
        cache = cache._replace(table=jnp.arange(32, dtype=jnp.int32)[None])
    else:
        cache = llama.KVCache.create(LANE, 1, 512, c["kv_dtype"])
    before = dict(A.dispatch_counts)
    logits, _ = jax.jit(lambda p, t, c: M.forward(p, LANE, t, c))(
        lane_params, jnp.asarray([ids_of(c["s"], salt=13)]), cache)
    assert np.isfinite(np.asarray(logits)).all()
    assert took(before) == [2, 0, 0]


def test_a_sparse_chunk_that_wanted_the_kernel_is_a_counted_fallback(
        lane_params, latent_prefill_on_tpu):
    """The engine turned kernels off for its mesh: the chunk walks,
    and says so, as a model without an indexer does."""
    before = dict(A.dispatch_counts)
    jax.jit(lambda p, t, c: M.forward(p, LANE, t, c, use_flash=False))(
        lane_params, jnp.asarray([ids_of(256, salt=13)]),
        llama.KVCache.create(LANE, 1, 512))
    assert took(before) == [2, 0, 2]


async def _collect(batcher, prompt, max_new, seed):
    out = []
    async for ids, _ in batcher.submit(
        prompt, max_new, SamplingConfig(temperature=0.0), seed=seed
    ):
        out.extend(ids)
    return out


async def test_page_reuse_and_cow_carry_the_indexer_plane(engine):
    """Through ContinuousBatcher with paging, chunked admission, a
    shared head (page reuse) that diverges inside a page (copy on
    write), twice over: greedy outputs equal the engine's own uncached
    generate (a reused page whose indexer keys were lost or stale would
    select other latents), and the new counters move."""
    head = ids_of(44, salt=7)  # 5.5 pages of 8: the divergence is CoW
    prompts = [head + ids_of(6, salt=20 + s) for s in range(3)]
    prompts += [ids_of(100, salt=8), ids_of(10, salt=9)]
    expected, _ = engine.generate(prompts, max_new_tokens=6, seed=0)
    batcher = ContinuousBatcher(engine, BatchingConfig(
        max_batch_size=4, kv_cache_max_seq=256, paged_kv="on",
        paged_kv_page_size=8, prefill_chunk=32))
    assert batcher.cache.k.shape[-1] == 128 and batcher.cache.v.shape[-1] == 32
    batcher.start()
    try:
        waves = [
            await asyncio.gather(*(
                _collect(batcher, p, 6, i) for i, p in enumerate(prompts)))
            for _ in range(2)
        ]
    finally:
        await batcher.stop()
    assert waves[0] == expected and waves[1] == expected
    stats = batcher.counter_stats()
    assert stats["paged_prefix_hits"] >= 5 and stats["paged_cow_copies"] >= 1
    assert stats["paged_pages_reused"] > 0
    steps = stats["moe_layer_steps"]
    assert steps > 0 and steps % CFG.num_expert_layers == 0
    assert 0 < stats["moe_experts_hit"] <= steps * CFG.num_experts_held
    # half the experts are held: pairs split between here and elsewhere
    assert stats["moe_routed_pairs"] > 0 and stats["moe_pairs_absent"] > 0
    # every decoding row is past 16 keys except the 10-token prompt's
    # first steps: a selection of exactly 16 each time it ran
    assert stats["sparse_layer_steps"] > 0
    assert stats["sparse_keys_selected"] == 16 * stats["sparse_layer_steps"]
    assert stats["sparse_keys_visible"] > stats["sparse_keys_selected"]


async def test_kananas_counters_read_zero_where_it_has_no_such_mechanism():
    eng = GenerationEngine(
        M.CONFIGS["tiny-mla-moe"],
        ServingConfig(mesh=MeshConfig(tensor=1, data=1)))
    batcher = ContinuousBatcher(eng, BatchingConfig(
        max_batch_size=2, kv_cache_max_seq=128, paged_kv="on",
        paged_kv_page_size=8, prefill_chunk=32))
    batcher.start()
    try:
        await _collect(batcher, ids_of(40, salt=11), 4, 0)
    finally:
        await batcher.stop()
    stats = batcher.counter_stats()
    assert stats["moe_routed_pairs"] > 0
    assert (stats["moe_pairs_absent"], stats["sparse_keys_selected"],
            stats["sparse_keys_visible"], stats["sparse_layer_steps"]) == (
        0, 0, 0, 0)


def test_kananas_forward_is_the_parents():
    """The family's older member, fixed seed, against numbers taken
    from the parent commit's tree (846e03d) on this machine: none of
    what this member added is on its path."""
    cfg = M.CONFIGS["tiny-mla-moe"]
    params = jax.jit(lambda k: M.init_params(k, cfg))(jax.random.PRNGKey(0))
    ids = [int(t) for t in np.random.RandomState(5).randint(3, 512, 48)]
    logits = np.asarray(M.forward(params, cfg, jnp.asarray([ids]))[0][0])
    assert logits[-1, :6].tolist() == [
        0.029482141137123108, 0.02163916826248169, 0.5422777533531189,
        0.10119706392288208, 0.6627091765403748, -0.17093384265899658]
    assert float(logits.sum()) == -128.55804443359375
    assert cfg.kv_planes == ((128,), (0,)) and cfg.softmax_scale == 1.0 / np.sqrt(32.0)


@pytest.mark.parametrize("keep, feature", [
    ("index_topk", "a model with a sparse-attention indexer"),
    ("experts_held", "a model that holds a share of its experts"),
])
def test_a_mesh_is_refused_by_mechanism(keep, feature):
    """Two devices (the test session has 8 CPU devices): refused for
    the mechanism the configuration carries, by name."""
    from ggrmcp_tpu.parallel import mesh as mesh_mod

    cfg = CFG if keep == "index_topk" else dataclasses.replace(
        CFG, index_topk=0, index_heads=0, index_head_dim=0)
    mesh = mesh_mod.build_mesh(
        MeshConfig(tensor=2, data=1), jax.devices()[:2])
    with pytest.raises(ValueError) as err:
        GenerationEngine(cfg, ServingConfig(), mesh=mesh)
    message = str(err.value)
    assert feature in message and "mla_moe family" in message
    assert "tiny-dsv32" in message


@pytest.mark.parametrize("planes", ["dense", "latent"])
def test_the_arena_gather_is_the_same_a_layer_at_a_time(planes):
    """`llama.paged_view_layers(by_layer=True)` (what `ARENA_BY_LAYER`
    asks of the batcher's admission programs) reads what the one gather
    reads, for a dense [L, N, P, KVH, Dh] arena and a latent
    [L, N, P, width] one; only the latent family asks for it (on the
    chip the dense family's 32 layers read 5% slower a call that way:
    PERF.md, PR 33)."""
    rng = np.random.RandomState(3)
    shape = (3, 7, 4, 2, 8) if planes == "dense" else (3, 7, 4, 16)
    arena = jnp.asarray(rng.randn(*shape), jnp.float32)
    table = jnp.asarray([[0, 5, 7], [6, 2, 9]], jnp.int32)  # 7, 9: sentinels
    np.testing.assert_array_equal(
        np.asarray(llama.paged_view_layers(arena, table, by_layer=True)),
        np.asarray(llama.paged_view_layers(arena, table)))
    assert M.ARENA_BY_LAYER and not getattr(llama, "ARENA_BY_LAYER", False)
