"""The keye family (models/keye.py: GQA K and V per head, the
sparse-attention indexer with its key in a THIRD plane of the cache,
softmax-routed experts) at its tiny preset, against the benchmark's
plain reference (benchmark/reference_keye.py, which shares no code with
it). Every comparison is of logits, not tokens. Contexts pass the
preset's `index_topk` (16), so the selection binds unless a test says
not."""

import asyncio
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ggrmcp_tpu.core.config import (
    BatchingConfig,
    LoraConfig,
    MeshConfig,
    ServingConfig,
)
from ggrmcp_tpu.models import family_module, family_name, get_model, llama
from ggrmcp_tpu.models import keye as K
from ggrmcp_tpu.models import mla_moe
from ggrmcp_tpu.ops import attention as A
from ggrmcp_tpu.ops import indexer
from ggrmcp_tpu.ops.sampling import SamplingConfig
from ggrmcp_tpu.serving.batching import ContinuousBatcher
from ggrmcp_tpu.serving.engine import GenerationEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmark import reference_keye as R  # noqa: E402

CFG = K.CONFIGS["tiny-keye"]
with open(os.path.join(
        ROOT, "tests", "benchmark", "rehearsal_keye", "benchmark",
        "configs", "tiny-keye-cpu.json")) as f:
    REF_MODEL = json.load(f)

# float32 on both sides, the same operations in another order (the
# program walks the keys in blocks with an online softmax, or gathers
# the selected ones; the reference takes one masked softmax over all):
# logits of magnitude ~3 agree to ~5e-6, and a selection that differed
# in one key would move them by 1e-2 and more.
ATOL = 2e-4


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: K.init_params(k, CFG))(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def ref():
    weights = R.family_init_weights(jax, REF_MODEL)
    return weights, R.make_layers(jax, REF_MODEL, by_rank=True)


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
    family, full = get_model("keye-vl-2.0-30b-a3b")
    _, cut = get_model("keye-vl-2.0-30b-a3b-6l")
    assert family == "keye" and family_module(cut) is K
    assert family_name(CFG) == "keye"
    # the cut changes depth and no width
    assert dataclasses.replace(cut, num_layers=48, name=full.name) == full
    assert (full.num_layers, cut.num_layers) == (48, 6)
    assert (cut.hidden_dim, cut.num_heads, cut.num_kv_heads, cut.head_dim,
            cut.index_heads, cut.index_head_dim, cut.index_topk,
            cut.num_experts, cut.experts_per_token, cut.expert_ffn_dim,
            cut.vocab_size, cut.rope_theta, cut.norm_eps) == (
        2048, 32, 4, 128, 16, 64, 2048, 128, 8, 768, 151936, 1e7, 1e-6)
    # ISSUE 37's count: 625.4M a layer, 4,375M in all; 2,176 B a token
    # a layer in three planes
    assert abs(K.num_params(cut) / 1e6 - 4374.6) < 0.5
    assert abs((K.num_params(full) - K.num_params(cut)) / 42e6 - 625.4) < 0.1
    # (the 64-value key in a plane of one whole lane tile: 128 B of the
    # 2,304 B stored are padding)
    assert cut.kv_planes == ((4, 128), (4, 128), (128,))
    assert sum(2 * int(np.prod(p)) for p in cut.kv_planes) == 2176 + 128
    assert K.admission_rows(cut) == 1
    # every mechanism live in the tiny member
    assert 0 < CFG.index_topk < 40 and CFG.num_kv_heads == 4
    assert CFG.num_heads >= 8 and CFG.num_experts >= 16
    assert len(CFG.kv_planes) == 3
    # without an indexer the family keeps two planes
    plain = dataclasses.replace(CFG, index_topk=0)
    assert len(plain.kv_planes) == 2 and K.admission_rows(plain) is None


def test_the_engine_draws_the_references_weights_bit_for_bit(engine, ref):
    weights, _ = ref
    for name, leaf in weights.items():
        stack, _, key = name.partition(".")
        mine = engine.params[name] if not key else engine.params[stack][key]
        assert mine.dtype == leaf.dtype and bool((mine == leaf).all()), name
    drawn = {n.partition(".")[2] or n for n in weights}
    undrawn = set(engine.params["layers"]) - drawn
    assert undrawn == {"attn_norm", "mlp_norm", "q_norm", "k_norm",
                       "idx_k_norm", "idx_k_bias"}
    assert K.num_params(CFG) == sum(
        x.size for x in jax.tree_util.tree_leaves(engine.params))


@pytest.mark.parametrize("n", [70, 12])
def test_forward_agrees_with_the_reference(params, ref, n):
    """70 tokens: past position 15 every query selects 16 of its keys.
    12 tokens: no query sees more than `index_topk`, nothing binds."""
    ids = ids_of(n)
    logits, _ = K.forward(params, CFG, jnp.asarray([ids]))
    np.testing.assert_allclose(
        np.asarray(logits[0]), ref_logits(ref, ids), atol=ATOL)


def test_the_selection_is_what_the_reference_is_compared_on(ref):
    """The same reference without its selection gives other logits
    where the selection binds and the same ones where it does not: a
    program that attended every key would fail the tests above."""
    weights, _ = ref
    dense = (weights, R.make_layers(jax, REF_MODEL, select=False))
    long, short = ids_of(70), ids_of(12)
    assert np.abs(ref_logits(dense, long) - ref_logits(ref, long)).max() > 0.05
    np.testing.assert_allclose(
        ref_logits(dense, short), ref_logits(ref, short), atol=1e-6)


def test_the_references_two_forms_of_the_selection_are_one_set(ref):
    """The reference run at the published widths finds S_t from the
    topk-th largest score and the ties' positions; the definition by
    rank (what every other test here compares with: `ref`) gives the
    same logits, on weights whose index scores tie at the cut (the
    indexer's queries rounded to a few values)."""
    weights, _ = ref
    coarse = dict(weights)
    coarse["layers.idx_wq"] = jnp.round(weights["layers.idx_wq"] * 4) / 4
    coarse["layers.idx_wk"] = jnp.round(weights["layers.idx_wk"] * 4) / 4
    ids = ids_of(70, salt=9)
    by_rank = ref_logits((coarse, R.make_layers(jax, REF_MODEL, by_rank=True)), ids)
    by_cut = ref_logits((coarse, R.make_layers(jax, REF_MODEL)), ids)
    np.testing.assert_allclose(by_cut, by_rank, atol=1e-6)
    assert np.abs(by_rank - ref_logits(ref, ids)).max() > 1e-3  # other weights


def test_without_an_indexer_the_model_attends_every_key(params, ref):
    """`index_topk` 0 picks the dense path by the config's key alone:
    the reference without its selection, through a two-plane cache."""
    weights, _ = ref
    dense = (weights, R.make_layers(jax, REF_MODEL, select=False))
    plain = dataclasses.replace(CFG, index_topk=0)
    ids = ids_of(50, salt=3)
    cache = llama.KVCache.create(plain, 1, 64)
    assert cache.extra == ()
    logits, cache = K.forward(params, plain, jnp.asarray([ids[:40]]), cache)
    got = [np.asarray(logits[0])]
    for i in range(40, 50):
        logits, cache = K.forward(params, plain, jnp.asarray([[ids[i]]]), cache)
        got.append(np.asarray(logits[0]))
    np.testing.assert_allclose(
        np.concatenate(got), ref_logits(dense, ids), atol=ATOL)


@pytest.mark.parametrize("paged", [False, True], ids=["contiguous", "paged"])
def test_prefill_then_decode_through_three_planes_agrees(params, ref, paged):
    """Prefill 40 tokens and decode 6 through a cache of three planes,
    contiguous and paged (scattered pages; K, V and the indexer key of
    a token in the same page); every step's logits against the
    reference's one full forward. The decode steps take the
    gather-by-token-index path."""
    ids = ids_of(46, salt=1)
    want = ref_logits(ref, ids)
    if paged:
        cache = llama.PagedKVCache.create(CFG, 2, 64, 12, 8)
        assert cache.k.shape == cache.v.shape == (3, 12, 8, 4, 16)
        assert [p.shape for p in cache.extra] == [(3, 12, 8, 128)]
        table = np.full((2, 8), 12, np.int32)
        table[0, :6] = [7, 2, 9, 0, 4, 11]
        cache = cache._replace(table=jnp.asarray(table))
    else:
        cache = llama.KVCache.create(CFG, 2, 64)
        assert cache.k.shape == (3, 2, 64, 4, 16)
        assert [p.shape for p in cache.extra] == [(3, 2, 64, 128)]
    step = jax.jit(lambda p, t, c, v: K.forward(
        p, CFG, t, c, valid=v, with_stats=True))
    tokens = jnp.asarray([ids[:40], [0] * 40])
    valid = jnp.asarray([[True] * 40, [False] * 40])
    logits, cache, _ = step(params, tokens, cache, valid)
    np.testing.assert_allclose(np.asarray(logits[0]), want[:40], atol=ATOL)
    before = A.dispatch_counts["sparse_gqa_decode"]
    for i in range(40, 46):
        logits, cache, counts = step(
            params, jnp.asarray([[ids[i]], [0]]), cache,
            jnp.asarray([[True], [False]]))
        np.testing.assert_allclose(
            np.asarray(logits[0, 0]), want[i], atol=ATOL)
        # one row selected 16 of its i + 1 keys in each of 3 layers
        assert counts[-3:].tolist() == [3 * 16, 3 * (i + 1), 3]
    assert A.dispatch_counts["sparse_gqa_decode"] == before + 1  # one trace
    assert int(cache.length[0]) == 46
    if paged:  # page 7 holds positions 0-7 of all three kinds of state
        for plane in llama.cache_planes(cache):
            assert float(jnp.abs(plane[:, 7]).max()) > 0
            assert float(jnp.abs(plane[:, 1]).max()) == 0  # never mapped


def test_chunked_admission_equals_one_shot_and_a_suffix_agrees(params, ref):
    """What the batcher's admissions run: a contiguous mini cache
    filled chunk by chunk (each chunk's queries select among the chunks
    before them and their own), then a short suffix on the whole
    history (the re-admission of a follow-up turn), then one decode
    step; and the same prompt in one shot."""
    ids = ids_of(90, salt=2)
    want = ref_logits(ref, ids)
    step = jax.jit(lambda p, t, c: K.forward(p, CFG, t, c))
    cache = llama.KVCache.create(CFG, 1, 128)
    before = A.dispatch_counts["sparse_gqa_chunk"]
    got = []
    for lo, hi in ((0, 32), (32, 64), (64, 84), (84, 89), (89, 90)):
        logits, cache = step(params, jnp.asarray([ids[lo:hi]]), cache)
        got.append(np.asarray(logits[0]))
    assert A.dispatch_counts["sparse_gqa_chunk"] == before + 3  # 32, 20, 5
    np.testing.assert_allclose(np.concatenate(got), want, atol=ATOL)
    one_shot, whole = step(
        params, jnp.asarray([ids[:89]]), llama.KVCache.create(CFG, 1, 128))
    np.testing.assert_allclose(
        np.asarray(one_shot[0]), np.concatenate(got[:4]), atol=1e-5)
    for a, b in zip(llama.cache_planes(whole), llama.cache_planes(cache)):
        np.testing.assert_allclose(
            np.asarray(a[:, :, :89]), np.asarray(b[:, :, :89]), atol=1e-5)


@pytest.mark.parametrize("s", [1, 8])
def test_the_selection_is_the_references_set_ties_included(
        params, monkeypatch, s):
    """Index scores with ties at the cut (rounded to one decimal): the
    keys a decode step gathers (s = 1) and the keys a suffix's mask
    allows (s = 8) are the reference's rule, the `topk` largest with
    ties to the lower position, read off what `gqa_attention` was
    handed."""
    ids = ids_of(40 + s, salt=5)
    cache = llama.KVCache.create(CFG, 1, 64)
    _, cache = K.forward(params, CFG, jnp.asarray([ids[:40]]), cache)
    whole = indexer.index_scores
    seen = {}

    def coarse(*args):
        scores = jnp.round(whole(*args), 1)
        seen["scores"] = scores
        return scores

    real = K.gqa_attention

    def spy(q, fetch, n_blocks, block, q_pos, kv_len, kvh, key_pos=None,
            allowed=None):
        if key_pos is not None:
            seen["picked"] = key_pos(0)
        if allowed is not None:
            seen["mask"] = jnp.concatenate(
                [allowed(i) for i in range(64 // block)], axis=-1)
        return real(q, fetch, n_blocks, block, q_pos, kv_len, kvh,
                    key_pos=key_pos, allowed=allowed)

    monkeypatch.setattr(indexer, "index_scores", coarse)
    monkeypatch.setattr(K, "gqa_attention", spy)
    lp = jax.tree.map(lambda a: a[2], {
        k: v for k, v in params["layers"].items() if not k.startswith("w_")})
    x = jax.random.normal(jax.random.PRNGKey(3), (1, s, CFG.hidden_dim))
    K.attention_block(
        x, lp, CFG, 40 + jnp.arange(s)[None], llama.cache_planes(cache),
        jnp.asarray([40]), None, 2)
    scores = np.asarray(seen["scores"][0])  # [s, 64]
    for t in range(s):
        keys = np.arange(40 + t + 1)
        # largest first, the lower position first among equals
        order = sorted(keys, key=lambda k: (-scores[t, k], k))
        want = sorted(order[:16])
        assert len(set(scores[t, keys])) < len(keys)  # ties exist
        if s == 1:
            assert sorted(np.asarray(seen["picked"][0]).tolist()) == want
        else:
            assert np.flatnonzero(np.asarray(seen["mask"][0, t])).tolist() == want


@pytest.mark.parametrize("s", [1, 8])
def test_an_indexer_that_scores_half_the_keys_reads_so_in_the_counts(
        params, monkeypatch, s):
    """The same step with an indexer that scores every other key only
    (odd positions read -inf): the three `sparse_*` counts say so. A
    decode step (s = 1) and a suffix (s = 8) over 40 cached tokens."""
    ids = ids_of(40 + s, salt=5)
    cache = llama.KVCache.create(CFG, 1, 64)
    _, cache = K.forward(params, CFG, jnp.asarray([ids[:40]]), cache)
    whole = indexer.index_scores

    def half(*args):
        scores = whole(*args)
        return jnp.where(jnp.arange(scores.shape[-1]) % 2 == 0, scores,
                         -jnp.inf)

    monkeypatch.setattr(indexer, "index_scores", half)
    _, _, counts = K.forward(
        params, CFG, jnp.asarray([ids[40:]]), cache, with_stats=True)
    # query t (position 40 + t) is scored keys 0, 2, .. <= 40 + t
    scored = [(40 + t) // 2 + 1 for t in range(s)]
    assert counts[-3:].tolist() == [3 * 16 * s, 3 * sum(scored), 3 * s]
    # and an indexer that scores nothing selects nothing
    monkeypatch.setattr(
        indexer, "index_scores",
        lambda *a: jnp.full_like(whole(*a), -jnp.inf))
    _, _, counts = K.forward(
        params, CFG, jnp.asarray([ids[40:]]), cache, with_stats=True)
    assert counts[-3:].tolist() == ([0, 0, 3] if s == 1 else [0, 0, 0])


def test_the_softmax_routers_weights_sum_to_one_and_follow_the_reference(
        params, ref):
    weights, _ = ref
    x = jax.random.normal(jax.random.PRNGKey(7), (24, CFG.hidden_dim))
    lp = {"router": params["layers"]["router"][1]}
    idx, w = mla_moe.route(x, lp, CFG)
    assert idx.shape == w.shape == (24, CFG.experts_per_token)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-6)
    p = np.asarray(jax.nn.softmax(x @ weights["layers.router"][1], axis=-1))
    for t in range(24):
        top = np.argsort(-p[t], kind="stable")[: CFG.experts_per_token]
        assert sorted(np.asarray(idx[t]).tolist()) == sorted(top.tolist())
        np.testing.assert_allclose(
            np.asarray(w[t]), p[t, np.asarray(idx[t])] / p[t, top].sum(),
            atol=1e-6)
    # the sigmoid form (the latent family's) is chosen by the same key
    assert mla_moe.CONFIGS["tiny-mla-moe"].router_scoring == "sigmoid"
    assert CFG.router_scoring == "softmax"


def test_logits_do_not_depend_on_who_shares_the_batch(params):
    a, b = ids_of(40, salt=4), ids_of(40, salt=5)
    alone, _ = K.forward(params, CFG, jnp.asarray([a]))
    both, _ = K.forward(params, CFG, jnp.asarray([a, b]))
    np.testing.assert_allclose(
        np.asarray(both[0]), np.asarray(alone[0]), atol=1e-5)


@pytest.mark.parametrize("kv_dtype, loose, tight", [
    # int8 with a scale a token and head keeps 7 bits of every plane
    ("int8", 0.15, 1e-4),
    # float8_e4m3fn keeps 4: coarser, still the same model
    ("fp8", 1.5, 1e-3),
])
def test_quantized_planes_carry_all_three_kinds_of_state(
        params, kv_dtype, loose, tight):
    """K, V AND the indexer's key go through the cache's storage
    precision. Up to position 15 nothing is selected and the logits
    stay near the float32 cache's (`loose`), without being equal to
    them (`tight`: the planes really are coarser). Past it the coarser
    indexer keys pick other tokens for some queries, which moves a
    logit by more than any rounding does: there the test asks only
    that the path runs and differs."""
    ids = ids_of(48, salt=6)
    step = jax.jit(lambda p, t, c: K.forward(p, CFG, t, c))

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
    for plane, shape in zip(llama.cache_planes(cache), (
            (3, 1, 64, 4, 16), (3, 1, 64, 4, 16), (3, 1, 64, 128))):
        values = plane.q if kv_dtype == "int8" else plane
        assert values.shape == shape
        assert values.dtype == (
            jnp.int8 if kv_dtype == "int8" else jnp.float8_e4m3fn)
    diff = np.abs(coarse - exact).max(-1)
    assert tight < diff[:16].max() < loose
    assert np.isfinite(coarse).all() and diff[16:].max() > tight


async def _collect(batcher, prompt, max_new, seed):
    out = []
    async for ids, _ in batcher.submit(
        prompt, max_new, SamplingConfig(temperature=0.0), seed=seed
    ):
        out.extend(ids)
    return out


async def test_a_readmission_from_reused_three_plane_pages_equals_cold(engine):
    """Through ContinuousBatcher with paging, chunked admission, a
    shared head (page reuse) that diverges inside a page (copy on
    write), twice over: greedy outputs equal the engine's own uncached
    generate (a reused page whose indexer keys, K or V were lost or
    stale would select or attend other tokens), and the counters move."""
    head = ids_of(44, salt=7)  # 5.5 pages of 8: the divergence is CoW
    prompts = [head + ids_of(6, salt=20 + s) for s in range(3)]
    prompts += [ids_of(100, salt=8), ids_of(10, salt=9)]
    expected, _ = engine.generate(prompts, max_new_tokens=6, seed=0)
    batcher = ContinuousBatcher(engine, BatchingConfig(
        max_batch_size=4, kv_cache_max_seq=256, paged_kv="on",
        paged_kv_page_size=8, prefill_chunk=32))
    assert [p.shape[-1] for p in llama.cache_planes(batcher.cache)] == [
        16, 16, 128]
    assert batcher._mini_rows == 1 and not batcher._arena_by_layer
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
    assert steps > 0 and steps % CFG.num_layers == 0
    assert 0 < stats["moe_experts_hit"] <= steps * CFG.num_experts
    assert stats["moe_routed_pairs"] > 0 and stats["moe_pairs_absent"] == 0
    # every decoding row is past 16 keys except the 10-token prompt's
    # first steps: a selection of exactly 16 each time it ran
    assert stats["sparse_layer_steps"] > 0
    assert stats["sparse_keys_selected"] == 16 * stats["sparse_layer_steps"]
    assert stats["sparse_keys_visible"] > stats["sparse_keys_selected"]
    assert batcher.cache_bytes() >= sum(
        p.nbytes for p in llama.cache_planes(batcher.cache))


@pytest.mark.parametrize("kv_dtype, planes", [("fp8", "float8_e4m3fn"), ("", "")])
def test_the_references_lower_planes_are_what_a_float8_cache_hands_back(
        params, ref, kv_dtype, planes):
    """The check's second reference (`make_layers(planes=)`: float32
    but for K, V and the indexer's keys, rounded to the dtype) is the
    program served from a cache of that dtype: prefill 40 and decode
    20 through float8 planes agree with it to float32 rounding (2e-6
    here), and lie as far from the float32 reference as the two
    references lie apart (logits move by 1 and more: other keys are
    selected). That is what the paired statistic rests on: tokens
    served from float8 planes are the lower reference's tokens."""
    weights, layers = ref
    ids = ids_of(60, salt=4)
    same = (weights, R.make_layers(jax, REF_MODEL, planes=planes))
    other = (weights, R.make_layers(
        jax, REF_MODEL, planes="" if planes else "float8_e4m3fn"))
    cache = llama.KVCache.create(CFG, 1, 64, kv_dtype)
    logits, cache = K.forward(params, CFG, jnp.asarray([ids[:40]]), cache)
    got = [np.asarray(logits[0])]
    for i in range(40, 60):
        logits, cache = K.forward(params, CFG, jnp.asarray([[ids[i]]]), cache)
        got.append(np.asarray(logits[0]))
    got = np.concatenate(got)
    np.testing.assert_allclose(got, ref_logits(same, ids), atol=ATOL)
    assert np.abs(got - ref_logits(other, ids)).max() > 0.5


@pytest.mark.parametrize("family, model, planes", [
    ("llama", "tiny-llama", [(4, 32), (4, 32)]),
    ("mla_moe", "tiny-mla-moe", [(128,), (0,)]),
    ("mla_moe", "tiny-dsv32", [(128,), (32,)]),
])
def test_dense_and_latent_cache_pytrees_keep_their_two_planes(
        family, model, planes):
    """The families that were here keep the cache leaves their programs
    always took as operands: two planes, the table, the lengths."""
    name, cfg = get_model(model)
    assert name == family and list(cfg.kv_planes) == planes
    cache = llama.KVCache.create(cfg, 2, 32)
    paged = llama.PagedKVCache.create(cfg, 2, 32, 4, 8)
    assert cache.extra == () and paged.extra == ()
    assert [x.shape for x in jax.tree_util.tree_leaves(cache)] == [
        (cfg.num_layers, 2, 32, *planes[0]), (cfg.num_layers, 2, 32, *planes[1]),
        (2,)]
    assert [x.shape for x in jax.tree_util.tree_leaves(paged)] == [
        (cfg.num_layers, 4, 8, *planes[0]), (cfg.num_layers, 4, 8, *planes[1]),
        (2, 4), (2,)]
    # positional construction, as before the third field
    assert llama.KVCache(cache.k, cache.v, cache.length) == cache
    doubled = llama.map_planes(lambda a, b: a + b + 1, cache, cache)
    assert doubled.extra == () and float(doubled.k.min()) == 1.0


def _serving(**kw):
    return ServingConfig(mesh=MeshConfig(tensor=1, data=1), **kw)


@pytest.mark.parametrize("serving, feature", [
    (_serving(lora=LoraConfig(adapters=["a"])), "lora"),
    (_serving(kv_ring=True), "kv_ring"),
    (_serving(batching=BatchingConfig(kv_tiers=[[64, 2], [128, 2]])),
     "batching.kv_tiers"),
    (_serving(batching=BatchingConfig(paged_kv_host_bytes=1 << 20)),
     "batching.paged_kv_host_bytes"),
    (_serving(role="prefill"), "a non-mixed serving.role"),
    (_serving(quantize="int8"), "quantize"),
    (ServingConfig(mesh=MeshConfig(tensor=1, data=1, stage=2)),
     "pipeline-parallel serving"),
])
def test_what_reads_two_planes_is_refused_by_name(serving, feature):
    with pytest.raises(ValueError) as err:
        GenerationEngine(CFG, serving)
    message = str(err.value)
    assert feature in message and "keye family" in message
    assert "tiny-keye" in message


def test_a_mesh_is_refused_for_the_indexer():
    from ggrmcp_tpu.parallel import mesh as mesh_mod

    mesh = mesh_mod.build_mesh(
        MeshConfig(tensor=2, data=1), jax.devices()[:2])
    with pytest.raises(ValueError) as err:
        GenerationEngine(CFG, ServingConfig(), mesh=mesh)
    assert "a model with a sparse-attention indexer" in str(err.value)
    assert "keye family" in str(err.value)
