"""The latent-attention + routed-experts family (models/mla_moe.py) at
its tiny preset: against the benchmark's plain reference (which shares
no code with it), through the paged latent cache, in both attention
forms, alone and in company, through the batcher with page reuse and
copy-on-write, and the one place that refuses what it cannot do."""

import asyncio
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
from ggrmcp_tpu.models import mla_moe as M
from ggrmcp_tpu.ops.sampling import SamplingConfig
from ggrmcp_tpu.serving.batching import ContinuousBatcher
from ggrmcp_tpu.serving.engine import GenerationEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmark import reference_mla_moe as R  # noqa: E402

CFG = M.CONFIGS["tiny-mla-moe"]
with open(os.path.join(
        ROOT, "tests", "benchmark", "rehearsal_mla_moe", "benchmark",
        "configs", "tiny-mla-moe-cpu.json")) as f:
    REF_MODEL = json.load(f)


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


def _serving(**kw):
    return ServingConfig(mesh=MeshConfig(tensor=1, data=1), **kw)


def ids_of(n, salt=0):
    rng = np.random.RandomState(salt)
    return [int(t) for t in rng.randint(3, CFG.vocab_size, n)]


def ref_logits(ref, ids):
    weights, layers = ref
    x = R.hidden_states(jax, REF_MODEL, weights, layers, ids)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    return np.asarray(x @ weights["lm_head"])


def test_registry_resolves_the_family_and_both_depths():
    family, full = get_model("kanana-2-30b-a3b")
    _, served = get_model("kanana-2-30b-a3b-6l")
    assert family == "mla_moe" and family_module(full) is M
    assert family_name(CFG) == "mla_moe"
    assert (full.num_layers, served.num_layers) == (48, 6)
    import dataclasses

    assert dataclasses.replace(served, num_layers=48, name=full.name) == full
    # the published widths, and the bytes ISSUE 28 counted from them
    assert (full.hidden_dim, full.num_experts, full.experts_per_token,
            full.latent_dim, full.vocab_size) == (2048, 128, 6, 576, 128256)
    assert abs(M.num_params(served) / 1e6 - 3789.6) < 0.5
    # dropless: the config has no capacity to read
    assert not hasattr(full, "capacity_factor")
    assert full.kv_planes == ((640,), (0,))  # one plane, lane-padded


def test_the_engine_draws_the_references_weights_bit_for_bit(engine, ref):
    weights, _ = ref
    for name, leaf in weights.items():
        stack, _, key = name.partition(".")
        mine = (engine.params[name] if not key else
                engine.params["dense" if stack == "dense" else "layers"][key])
        assert mine.dtype == leaf.dtype and bool((mine == leaf).all()), name
    assert float(jnp.abs(engine.params["layers"]["router_bias"]).min()) > 0


def test_forward_agrees_with_the_reference(params, ref):
    ids = ids_of(70)
    logits, _ = M.forward(params, CFG, jnp.asarray([ids]))
    np.testing.assert_allclose(
        np.asarray(logits[0]), ref_logits(ref, ids), atol=2e-4)


def test_prefill_then_decode_through_latent_pages_agrees(params, ref):
    """Prefill 40 tokens and decode 6 through a paged latent cache with
    scattered pages; every step's logits against the reference's one
    full forward."""
    ids = ids_of(46, salt=1)
    want = ref_logits(ref, ids)
    cache = llama.PagedKVCache.create(CFG, 2, 64, 12, 8)
    assert cache.k.shape == (3, 12, 8, 128) and cache.v.size == 0
    table = np.full((2, 8), 12, np.int32)
    table[0, :6] = [7, 2, 9, 0, 4, 11]
    cache = cache._replace(table=jnp.asarray(table))
    step = jax.jit(lambda p, t, c, v: M.forward(p, CFG, t, c, valid=v))
    tokens = jnp.asarray([ids[:40], [0] * 40])
    valid = jnp.asarray([[True] * 40, [False] * 40])
    logits, cache = step(params, tokens, cache, valid)
    np.testing.assert_allclose(np.asarray(logits[0]), want[:40], atol=2e-4)
    for i in range(40, 46):
        logits, cache = step(
            params, jnp.asarray([[ids[i]], [0]]), cache,
            jnp.asarray([[True], [False]]))
        np.testing.assert_allclose(
            np.asarray(logits[0, 0]), want[i], atol=2e-4)
    assert int(cache.length[0]) == 46


def test_absorbed_and_expanded_are_one_attention(params, monkeypatch):
    tokens = jnp.asarray([ids_of(48, salt=2)])
    cache = llama.KVCache.create(CFG, 1, 64)
    monkeypatch.setattr(M, "ABSORBED_MAX_QUERIES", 0)
    expanded, _ = M.forward(params, CFG, tokens, cache)
    monkeypatch.setattr(M, "ABSORBED_MAX_QUERIES", 1 << 20)
    absorbed, _ = M.forward(params, CFG, tokens, cache)
    assert float(jnp.abs(expanded).max()) > 0.5
    np.testing.assert_allclose(
        np.asarray(absorbed), np.asarray(expanded), atol=2e-4)


def test_logits_do_not_depend_on_who_shares_the_batch(params):
    """The defect ROADMAP B7 named: with capacity dispatch a row's
    output depended on the other rows. Here every routed pair is
    computed, so a prompt alone and among seven others agree."""
    mine = ids_of(33, salt=3)
    others = [ids_of(33, salt=10 + i) for i in range(7)]
    alone, _ = M.forward(params, CFG, jnp.asarray([mine]))
    crowd, _, stats = M.forward(
        params, CFG, jnp.asarray([mine] + others), with_stats=True)
    np.testing.assert_allclose(
        np.asarray(crowd[0]), np.asarray(alone[0]), atol=1e-5)
    # every pair was computed: 8 rows x 33 tokens x 2 experts x 2 layers
    assert int(stats[2]) == 8 * 33 * 2 * 2


def test_selection_uses_the_bias_and_weights_do_not(params):
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    banks = tuple(params["layers"][n] for n in ("w_gate", "w_up", "w_down"))
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 12, CFG.hidden_dim))
    got, _ = M.moe_ffn(x, lp, banks, jnp.int32(0), CFG)
    xt = np.asarray(x[0], np.float64)
    s = 1.0 / (1.0 + np.exp(-(xt @ np.asarray(lp["router"], np.float64))))
    bias = np.asarray(lp["router_bias"], np.float64)
    assert (np.argsort(-s, -1)[:, :2] != np.argsort(-(s + bias), -1)[:, :2]).any()

    def by_hand(weigh_with_bias):
        out = np.zeros_like(xt)
        for t in range(xt.shape[0]):
            chosen = np.argsort(-(s[t] + bias))[:CFG.experts_per_token]
            w = (s[t] + bias if weigh_with_bias else s[t])[chosen]
            w = w / (w.sum() + 1e-20) * CFG.routed_scaling
            for e, we in zip(chosen, w):
                g, u, d = (np.asarray(lp[n][e], np.float64)
                           for n in ("w_gate", "w_up", "w_down"))
                a = xt[t] @ g
                out[t] += we * ((a / (1 + np.exp(-a)) * (xt[t] @ u)) @ d)
            g, u, d = (np.asarray(lp[n], np.float64)
                       for n in ("ws_gate", "ws_up", "ws_down"))
            a = xt[t] @ g
            out[t] += (a / (1 + np.exp(-a)) * (xt[t] @ u)) @ d
        return out

    np.testing.assert_allclose(np.asarray(got[0]), by_hand(False), atol=1e-4)
    assert np.abs(np.asarray(got[0]) - by_hand(True)).max() > 1e-2


def test_int8_latents_stay_close(params):
    """The lower-precision control of the benchmark's check: an int8
    latent cache runs through the same block walk and moves the logits
    a little, not a lot."""
    tokens = jnp.asarray([ids_of(40, salt=6)])
    exact, _ = M.forward(params, CFG, tokens, llama.KVCache.create(CFG, 1, 64))
    rough, cache = M.forward(
        params, CFG, tokens, llama.KVCache.create(CFG, 1, 64, "int8"))
    assert cache.k.q.dtype == jnp.int8
    # The median, not the maximum: a token whose last chosen expert was
    # nearly tied routes elsewhere and moves its own logits a lot.
    err = float(jnp.median(jnp.abs(rough - exact)))
    assert 1e-5 < err < 0.02, err


def test_fp8_latents_are_coarser_than_int8(params):
    """`kv_cache_dtype: fp8`, the benchmark's control: float8_e4m3fn
    keeps 4 significant bits where int8 with a scale a token keeps 7,
    about what bf16's 8 are."""
    tokens = jnp.asarray([ids_of(40, salt=6)])
    exact, _ = M.forward(params, CFG, tokens, llama.KVCache.create(CFG, 1, 64))
    err = {}
    for kind in ("int8", "fp8"):
        rough, cache = M.forward(
            params, CFG, tokens, llama.KVCache.create(CFG, 1, 64, kind))
        err[kind] = float(jnp.median(jnp.abs(rough - exact)))
    assert cache.k.dtype == jnp.float8_e4m3fn and cache.v.size == 0
    assert err["fp8"] > 3 * err["int8"], err
    with pytest.raises(ValueError, match="kv_cache_dtype fp8 is not "
                                         "supported for the llama family"):
        GenerationEngine(llama.CONFIGS["tiny-llama"],
                         _serving(kv_cache_dtype="fp8"))


async def _collect(batcher, prompt, max_new, seed):
    out = []
    async for ids, _ in batcher.submit(
        prompt, max_new, SamplingConfig(temperature=0.0), seed=seed
    ):
        out.extend(ids)
    return out


async def test_page_reuse_and_cow_over_latent_pages(engine):
    """Through ContinuousBatcher with paging, chunked admission, a
    shared head (page reuse) that diverges inside a page (copy on
    write), twice over: greedy outputs equal the engine's own uncached
    generate, and the new counters move."""
    head = ids_of(44, salt=7)  # 5.5 pages of 8: the divergence is CoW
    prompts = [head + ids_of(6, salt=20 + s) for s in range(3)]
    prompts += [ids_of(100, salt=8), ids_of(10, salt=9)]
    expected, _ = engine.generate(prompts, max_new_tokens=6, seed=0)
    batcher = ContinuousBatcher(engine, BatchingConfig(
        max_batch_size=4, kv_cache_max_seq=256, paged_kv="on",
        paged_kv_page_size=8, prefill_chunk=32))
    assert batcher.cache.k.shape[-1] == 128 and batcher.cache.v.size == 0
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
    assert stats["prefill_tokens_reused"] > 0
    assert stats["prefill_tokens_computed"] + stats["prefill_tokens_reused"] \
        == 2 * sum(len(p) for p in prompts)
    steps = stats["moe_layer_steps"]
    assert steps > 0 and steps % CFG.num_expert_layers == 0
    assert 0 < stats["moe_experts_hit"] <= steps * CFG.num_experts
    assert stats["moe_routed_pairs"] <= steps * 4 * CFG.experts_per_token
    assert stats["moe_load_max_sum"] >= steps  # some expert was hit a step


def _spy_on_chunked(batcher) -> list:
    """Every `_admit_chunked` call's (tokens' shape, rows' lengths)."""
    calls = []
    inner = batcher._admit_chunked

    def spy(params, tokens, true_len, *rest):
        calls.append((tuple(tokens.shape), np.asarray(true_len).tolist()))
        return inner(params, tokens, true_len, *rest)

    batcher._admit_chunked = spy
    return calls


async def _one_round(batcher, prompts, max_new):
    """Every prompt queued before the loop starts: one admission round."""
    tasks = [asyncio.ensure_future(_collect(batcher, p, max_new, i))
             for i, p in enumerate(prompts)]
    await asyncio.sleep(0)
    batcher.start()
    try:
        return await asyncio.gather(*tasks)
    finally:
        await batcher.stop()


async def test_deep_grids_round_up_and_go_one_row_a_call(engine):
    """Cold prompts past the family's DEEP_GRID_CHUNKS (7 and 6 chunks
    against 4): each is admitted alone, in arrival order (so two that
    arrive together do not finish together); the two shallow ones
    beside them share one call, which goes first. Nothing rounds up any
    more: every call's `tokens` is (R, T_max, 16) whatever the depths,
    T_max the batcher's ceil(256 / 16), and the program runs each row's
    own chunks. Outputs are the engine's own."""
    prompts = [ids_of(100, salt=30), ids_of(40, salt=32),
               ids_of(90, salt=31), ids_of(50, salt=33)]
    expected, _ = engine.generate(prompts, max_new_tokens=5, seed=0)
    batcher = ContinuousBatcher(engine, BatchingConfig(
        max_batch_size=4, kv_cache_max_seq=256, paged_kv="on",
        paged_kv_page_size=8, prefill_chunk=16))
    assert batcher._deep_grid == 4 and batcher._grid_chunks == 16
    calls = _spy_on_chunked(batcher)
    outs = await _one_round(batcher, prompts, 5)
    assert outs == expected
    assert calls == [
        ((2, 16, 16), [40, 50]), ((1, 16, 16), [100]), ((1, 16, 16), [90])]


async def test_a_family_without_deep_grids_keeps_depth_and_group():
    """llama's module names no DEEP_GRID_CHUNKS: the same cold prompts
    go in one call, as before the latent family came (the batcher asks
    the module, it never tells families apart), on the same
    (R, T_max, 16) grid: the bucket's fourth row is padding, of length
    0, and runs no chunk."""
    cfg = llama.CONFIGS["tiny-llama"]
    eng = GenerationEngine(cfg, _serving())
    rng = np.random.RandomState(5)
    prompts = [[int(t) for t in rng.randint(3, cfg.vocab_size, n)]
               for n in (100, 90, 40)]
    expected, _ = eng.generate(prompts, max_new_tokens=3, seed=0)
    batcher = ContinuousBatcher(eng, BatchingConfig(
        max_batch_size=4, kv_cache_max_seq=256, paged_kv="on",
        paged_kv_page_size=8, prefill_chunk=16))
    assert batcher._deep_grid is None and not batcher._head_at_index
    assert not batcher._routing_stats
    calls = _spy_on_chunked(batcher)
    outs = await _one_round(batcher, prompts, 3)
    assert outs == expected
    assert calls == [((4, 16, 16), [100, 90, 40, 0])]


@pytest.fixture(scope="module", params=["llama", "mla_moe", "keye"])
def family_engine(request):
    from ggrmcp_tpu.models import keye

    cfg = {"llama": llama.CONFIGS["tiny-llama"], "mla_moe": CFG,
           "keye": keye.CONFIGS["tiny-keye"]}[request.param]
    return GenerationEngine(cfg, _serving())


def _paged_batcher(eng):
    return ContinuousBatcher(eng, BatchingConfig(
        max_batch_size=4, kv_cache_max_seq=128, paged_kv="on",
        paged_kv_page_size=8, prefill_chunk=16))


def _exact_depth_grid(batcher):
    """The admission program as it was before a row's chunk count was
    read on the device: the [R, T, C] grid scanned to the deepest
    prompt's exact depth, rows and no-op chunks alike."""
    def impl(params, tokens, true_len, cache, slots, seeds, temps, ks, ps,
             adapters, g0, g_allow, g_trans):
        mini = batcher._make_mini(tokens.shape[0], batcher.max_seq)
        fl, mini = batcher._chunked_scan(
            params, tokens, true_len, mini, adapters, jnp.int32(0))
        return batcher._chunked_finish(
            cache, mini, slots, true_len, fl, seeds, temps, ks, ps,
            g0, g_allow, g_trans)

    return jax.jit(impl)


@pytest.mark.parametrize("lens", [
    pytest.param((100, 48, 21), id="mixed_depths_and_a_padding_row"),
    pytest.param((128, 33), id="max_seq_beside_a_short_one"),
    pytest.param((64,), id="exactly_k_chunks_alone"),
])
def test_each_rows_own_chunks_write_what_the_exact_grid_wrote(
    family_engine, lens
):
    """A cold group as one `_admit_chunked` call on the (R, T_max, C)
    grid against the exact-depth grid of before, a row a call: the same
    first tokens and the same pages, bit for bit, in all three
    families: mixed
    depths (7, 3 and 2 chunks, the 48-token prompt exactly 3 x 16) in a
    bucket of four whose last row is padding; a prompt of max_seq (8
    chunks, the grid's whole depth); a prompt of exactly 4 chunks in a
    bucket of one, whose mini is the row's own."""
    eng, batcher = family_engine, _paged_batcher(family_engine)
    c, b = 16, 4
    r = 1 if len(lens) == 1 else b
    rng = np.random.RandomState(sum(lens))
    true_len = np.zeros((r,), np.int32)
    slots = np.full((r,), b, np.int32)
    grid = np.zeros((r, batcher._grid_chunks, c), np.int32)
    for j, n in enumerate(lens):
        prompt = rng.randint(3, eng.cfg.vocab_size, n)
        batcher.pages.admit(j, prompt.tolist(), n, share=False)
        grid[j].reshape(-1)[:n] = prompt
        true_len[j], slots[j] = n, j
    batcher._tables_dirty = True
    batcher._sync_tables()
    g_tables = batcher._grammar_tables()

    def args(rows):
        """The rows `rows` of the call, greedy and unconstrained."""
        n, zi = len(rows), jnp.zeros((len(rows),), jnp.int32)
        return (jnp.asarray(true_len[rows]),), (
            jnp.asarray(slots[rows]), jnp.zeros((n,), jnp.uint32),
            jnp.zeros((n,), jnp.float32), zi, jnp.ones((n,), jnp.float32),
            zi, zi, *g_tables)

    # What stood before, a row a call so that its products have this
    # program's shapes (a [4, 16] chunk and a [1, 16] one round their
    # sums differently): each row on the grid of its own exact depth.
    exact = _exact_depth_grid(batcher)
    want, want_first = jax.tree.map(jnp.copy, batcher.cache), []
    for j, n in enumerate(lens):
        lens_j, rest = args([j])
        first, want = exact(
            eng.params, jnp.asarray(grid[j:j + 1, :-(-n // c)]), *lens_j,
            want, *rest)
        want_first.append(int(first[0]))
    lens_all, rest = args(list(range(r)))
    got_first, got = batcher._admit_chunked(
        eng.params, jnp.asarray(grid), *lens_all,
        jax.tree.map(jnp.copy, batcher.cache), *rest)
    assert np.asarray(got_first)[:len(lens)].tolist() == want_first
    leaves, want_leaves = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(leaves) == len(want_leaves)
    written = 0
    for leaf, want_leaf in zip(leaves, want_leaves):
        np.testing.assert_array_equal(
            np.asarray(leaf, np.float32), np.asarray(want_leaf, np.float32))
        written += int(np.count_nonzero(np.asarray(leaf, np.float32)))
    assert written > 0  # the pages hold the prompts' state


async def test_one_chunked_program_a_row_bucket_whatever_the_depths(
    family_engine
):
    """Rounds of three different depths, a row each (3, 5 and 7 chunks;
    48 tokens are exactly 3 x 16), then a round of three prompts of 3
    and 4 chunks in a bucket of four (a row a call where the family's
    `admission_rows` says so): outputs are `engine.generate`'s,
    and `_admit_chunked` holds one compiled program a row bucket, where
    the exact-depth grid compiled one a (rows, depth) pair. The counter
    says which chunks ran: each row's own."""
    eng, batcher = family_engine, _paged_batcher(family_engine)
    rng = np.random.RandomState(11)
    rounds = [[48], [75], [100], [37, 61, 50]]
    calls = _spy_on_chunked(batcher)
    traced, walk = [], batcher._chunked_rows

    def tracing(params, tokens, *rest):  # runs once a program traced
        traced.append(tuple(tokens.shape))
        return walk(params, tokens, *rest)

    batcher._chunked_rows = tracing
    # A family with an indexer admits a row a call (`admission_rows`).
    group = batcher._mini_rows > 1
    buckets = [(1, 8, 16)] + [(4, 8, 16)] * group
    batcher.start()
    try:
        for lens in rounds:
            prompts = [[int(t) for t in rng.randint(3, eng.cfg.vocab_size, n)]
                       for n in lens]
            expected, _ = eng.generate(prompts, max_new_tokens=4, seed=0)
            tasks = [asyncio.ensure_future(_collect(batcher, p, 4, i))
                     for i, p in enumerate(prompts)]
            assert await asyncio.gather(*tasks) == expected
            assert traced == buckets[:1 if len(lens) == 1 else 2]
    finally:
        await batcher.stop()
    assert [shape for shape, _ in calls] == 3 * [(1, 8, 16)] + (
        [(4, 8, 16)] if group else 3 * [(1, 8, 16)])
    stats = batcher.counter_stats()
    assert stats["prefill_chunk_tokens_run"] == 16 * (3 + 5 + 7 + 3 + 4 + 4)
    assert stats["prefill_tokens_computed"] == 48 + 75 + 100 + 37 + 61 + 50


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
def test_one_refusal_names_the_family_and_the_feature(serving, feature):
    with pytest.raises(ValueError) as err:
        GenerationEngine(CFG, serving)
    message = str(err.value)
    assert feature in message and "mla_moe family" in message
    assert "tiny-mla-moe" in message


def test_paging_is_refused_by_family_not_for_every_expert_model():
    import inspect

    from ggrmcp_tpu.models import moe
    from ggrmcp_tpu.serving import engine as engine_mod

    assert "batching.paged_kv" not in engine_mod._UNSUPPORTED["mla_moe"]
    assert "dense Llama only" not in inspect.getsource(engine_mod)
    # the capacity-dispatch family has no block-table path: same table
    old = GenerationEngine(moe.CONFIGS["tiny-moe"], _serving())
    with pytest.raises(ValueError, match="paged_kv is not supported for "
                                         "the moe family"):
        old.make_paged_cache(2, 64, 8, 8)
