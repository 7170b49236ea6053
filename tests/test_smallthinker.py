"""The SmallThinker family (models/smallthinker.py) at `tiny-smallthinker`
on the CPU: two periods of one full layer (no positional encoding) to
three window layers (window 32, RoPE), 8 ReGLU experts of which 2 a
token, routed from the attention block's normed input.

1. THE FAMILY AND ITS FORWARD against `benchmark/reference_smallthinker.py`
   (float32 `jax.numpy`, no cache): whole sequences, chunked prefill
   into a contiguous cache, decode through pages of two kinds PAST the
   window with the pages behind it unmapped and poisoned, and a
   re-admission on what is left. `route`'s softmax form equals
   top-k-then-softmax; the ReGLU gate in the grouped kernel (the
   interpreter) equals the task loop's.
2. THE PAGED-DECODE KERNEL'S window walk (the interpreter) over a table
   whose entries behind the window are unmapped.
3. THROUGH THE BATCHER: every admission path equals the engine's own
   uncached generate, a second turn re-admits on pages of both kinds
   after the first turn's were let go, the books balance; the float8
   cache (the benchmark's control) serves; what the family cannot be
   composed with is refused by name.

Marker `paged` (tier-1).
"""

import asyncio
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ggrmcp_tpu.core.config import BatchingConfig, MeshConfig, ServingConfig
from ggrmcp_tpu.models import family_module, family_name, get_model, llama
from ggrmcp_tpu.models import mla_moe as M
from ggrmcp_tpu.models import smallthinker as S
from ggrmcp_tpu.ops import attention as A
from ggrmcp_tpu.ops import experts as X
from ggrmcp_tpu.ops.sampling import SamplingConfig
from ggrmcp_tpu.serving.batching import ContinuousBatcher
from ggrmcp_tpu.serving.engine import _UNSUPPORTED, GenerationEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmark import reference_smallthinker as R  # noqa: E402

pytestmark = pytest.mark.paged

CFG = S.CONFIGS["tiny-smallthinker"]
with open(os.path.join(
        ROOT, "tests", "benchmark", "rehearsal_smallthinker", "benchmark",
        "configs", "tiny-smallthinker-cpu.json")) as f:
    REF_MODEL = json.load(f)

# float32 on both sides, the same operations in another order (the
# program scores a chunk against the cache's whole width and sums the
# chosen experts' results a pair at a time; the reference takes one
# softmax over the sequence and every expert under a weight mask):
# logits of magnitude ~1 agree to ~3e-6.
ATOL = 2e-4
GREEDY = SamplingConfig(temperature=0.0)
W, PAGE = CFG.sliding_window, 16


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: S.init_params(k, CFG))(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def ref():
    return R.to_host(jax, REF_MODEL, R.family_init_weights(jax, REF_MODEL))


@pytest.fixture(scope="module")
def engine():
    return GenerationEngine(
        CFG, ServingConfig(mesh=MeshConfig(tensor=1, data=1)))


def ids_of(n, salt=0):
    rng = np.random.RandomState(salt)
    return [int(t) for t in rng.randint(3, CFG.vocab_size, n)]


def ref_logits(ref, ids, **kw):
    return np.asarray(R.logits_of(jax, REF_MODEL, ref, ids, **kw))


# ---------------------------------------------------------------------------
# The family and its forward
# ---------------------------------------------------------------------------


def test_registry_layer_kinds_and_the_published_count():
    assert family_name(CFG) == "smallthinker" and family_module(CFG) is S
    for name in ("smallthinker-21b-a3b", "smallthinker-21b-a3b-8l",
                 "tiny-smallthinker"):
        assert get_model(name) == ("smallthinker", S.CONFIGS[name])
    full = S.CONFIGS["smallthinker-21b-a3b"]
    assert full.layer_kinds == ("full", "window", "window", "window") * 13
    assert full.cache_kinds == ((13, None), (39, 4096))
    served = S.CONFIGS["smallthinker-21b-a3b-8l"]
    assert served.cache_kinds == ((2, None), (6, 4096))
    assert served.layer_kinds == full.layer_kinds[:8]
    # ISSUE 53: 398.6M a layer, 8 layers with embedding and head 3,967M
    assert S.num_params(served) == 3_966_937_600
    assert (S.num_params(full) - S.num_params(served)) // 44 == (
        20_971_520 + 163_840 + 64 * 5_898_240 + 2 * 2560)
    # a full layer has neither window nor rotary; a window layer both
    assert (S.kind_cfg(served, "full").sliding_window,
            S.kind_cfg(served, "full").rope_theta) == (None, 0.0)
    assert S.kind_cfg(served, "window") is served
    assert (served.sliding_window, served.rope_theta) == (4096, 1.5e6)
    # every other family declares one kind that keeps everything
    for other in ("tiny-llama", "tiny-mistral", "tiny-keye", "tiny-jamba",
                  "tiny-mla-moe"):
        cfg = get_model(other)[1]
        assert cfg.cache_kinds == ((cfg.cache_layers, None),)


def test_the_reference_and_the_program_share_one_recipe(params):
    theirs = [(name, tuple(shape), scale, dt)
              for name, shape, scale, dt in R.leaf_recipe(REF_MODEL)]
    mine = [(".".join(path), tuple(shape), scale, dt)
            for path, shape, scale, dt in S.leaf_recipe(CFG)]
    assert mine == theirs
    assert S.num_params(CFG) == sum(
        x.size for x in jax.tree_util.tree_leaves(params))


def test_forward_equals_the_reference(params, ref):
    ids = ids_of(150, salt=1)  # four to five windows
    got, _ = S.forward(params, CFG, jnp.asarray([ids]))
    want = ref_logits(ref, ids)
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=ATOL)
    # and the reference without its window is another model there
    assert np.abs(ref_logits(ref, ids, window=False) - want)[W:].max() > 0.05


def test_routes_softmax_form_is_top_k_then_softmax(params):
    """`mla_moe.route` (softmax over all experts, the top k, their
    weights renormalised) equals the equation as the config reads: the
    top k of the logits, softmax over those."""
    x = jax.random.normal(jax.random.PRNGKey(5), (64, CFG.hidden_dim))
    lp = {"router": params["layers"]["router"][3]}
    idx, weight = M.route(x, lp, CFG)
    logits = x.astype(jnp.float32) @ lp["router"]
    top, at = jax.lax.top_k(logits, CFG.experts_per_token)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(at))
    np.testing.assert_allclose(
        np.asarray(weight), np.asarray(jax.nn.softmax(top, -1)), atol=1e-6)


@pytest.mark.parametrize("act", ["relu", "silu"])
def test_the_gates_activation_is_the_callers_in_kernel_and_loop(act):
    """`grouped_swiglu` (the interpreter) and `_looped_tasks` with the
    same `act` agree, both differ from the other activation's, and a
    float32 `jnp` product holds them."""
    d, f, e, k, t = 128, 128, 8, 2, 24
    key = jax.random.PRNGKey(7)
    banks = tuple(
        jax.random.normal(jax.random.fold_in(key, i), (2, e, *s)) * s[0] ** -0.5
        for i, s in enumerate(((d, f), (d, f), (f, d))))
    xt = jax.random.normal(jax.random.fold_in(key, 9), (t, d))
    idx = jax.random.randint(jax.random.fold_in(key, 10), (t, k), 0, e)
    flat = idx.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    counts = jnp.zeros((e + 1,), jnp.int32).at[flat].add(1)[:e]
    loop = M._looped_tasks(xt, order, counts, banks, 1, k, 8, act)
    was = X.grouped_swiglu
    X.grouped_swiglu = functools.partial(was, interpret=True)
    try:
        kernel = M._grouped_tasks(xt, flat, order, counts, banks, 1, k, 8, act)
    finally:
        X.grouped_swiglu = was
    fn = X.ACTIVATIONS[act]
    hi = jax.lax.Precision.HIGHEST
    x = xt[jnp.arange(t * k) // k]
    want = jnp.einsum("pf,pfd->pd", fn(jnp.einsum(
        "pd,pdf->pf", x, banks[0][1][flat], precision=hi)) * jnp.einsum(
        "pd,pdf->pf", x, banks[1][1][flat], precision=hi),
        banks[2][1][flat], precision=hi)
    np.testing.assert_allclose(np.asarray(loop), np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(want), atol=2e-5)
    other = M._looped_tasks(
        xt, order, counts, banks, 1, k, 8, "silu" if act == "relu" else "relu")
    assert np.abs(np.asarray(other) - np.asarray(want)).max() > 1e-2


def live_tail_pages(mini, pos, poison=1e4):
    """`mini` (one row, contiguous, `pos` keys) as a paged cache of two
    kinds as the host keeps it: every block of the full layers mapped,
    of the window layers only those a query at `pos` can still read;
    the window arena's other pages hold `poison`."""
    s_max = mini.k.shape[2]
    width = s_max // PAGE
    paged = llama.PagedKVCache.create(
        CFG, 1, s_max, width, PAGE, window_pages=width)
    first = max(0, pos - W + 1) // PAGE
    table = jnp.arange(width, dtype=jnp.int32)[None]
    wtable = jnp.where(jnp.arange(width) >= first, table, width)
    gone = (jnp.arange(width) < first)[None, :, None, None, None]
    out = {}
    for name in ("k", "v"):
        full, win = llama.split_kinds(CFG, getattr(mini, name))
        out[name] = full.reshape(full.shape[0], width, PAGE, *full.shape[3:])
        win = win.reshape(win.shape[0], width, PAGE, *win.shape[3:])
        out["w" + name] = jnp.where(gone, poison, win)
    return paged._replace(
        k=out["k"], v=out["v"], table=table, length=mini.length,
        window=llama.WindowArena(out["wk"], out["wv"], wtable))


def test_prefill_then_decode_past_the_window_then_a_readmission(params, ref):
    """Chunked prefill into the contiguous mini; decode through pages
    of two kinds with the window layers' pages behind the window
    unmapped and POISONED, let go a step at a time as the host's free
    rule does; then the row's pages gathered back into a mini (the
    window layers' live tail only, junk behind it) and a suffix run on
    it: logits equal the reference's at every position."""
    ids = ids_of(140, salt=2)
    want = ref_logits(ref, ids)
    mini = llama.KVCache.create(CFG, 1, 256)
    got = []
    for at in range(0, 64, 32):
        lg, mini = S.forward(params, CFG, jnp.asarray([ids[at:at + 32]]), mini)
        got.append(lg[0])
    np.testing.assert_allclose(np.concatenate(got), want[:64], atol=ATOL)

    paged = live_tail_pages(mini, 64)
    step = jax.jit(lambda tok, cache: S.forward(params, CFG, tok, cache))
    width = paged.table.shape[1]
    for pos in range(64, 120):
        # the host's release before the step: blocks wholly behind the
        # window of the query at `pos` are unmapped and their pages may
        # hold anything
        first = max(0, pos - W + 1) // PAGE
        gone = jnp.arange(width) < first
        win = paged.window
        paged = paged._replace(window=win._replace(
            table=jnp.where(gone[None], width, win.table),
            k=jnp.where(gone[None, :, None, None, None], -1e4, win.k),
            v=jnp.where(gone[None, :, None, None, None], 1e4, win.v)))
        lg, paged = step(jnp.asarray([[ids[pos]]]), paged)
        np.testing.assert_allclose(
            np.asarray(lg[0, 0]), want[pos], atol=ATOL, err_msg=str(pos))
    # what is unmapped by now: the blocks behind the last query's window
    assert int((paged.window.table[0] == width).sum()) == (119 - W + 1) // PAGE

    # re-admission: both kinds' views joined in the model's layer order
    views = [llama.join_kinds(CFG, [
        llama.paged_view_layers(a, t) for a, t in (
            (full, paged.table), (tail, paged.window.table))])
        for full, tail in ((paged.k, paged.window.k),
                           (paged.v, paged.window.v))]
    again = llama.KVCache(views[0], views[1], jnp.asarray([120], jnp.int32))
    lg, _ = S.forward(params, CFG, jnp.asarray([ids[120:140]]), again)
    np.testing.assert_allclose(np.asarray(lg[0]), want[120:140], atol=ATOL)


def test_split_and_join_kinds_are_inverse_and_in_arena_order():
    plane = jnp.arange(8 * 3).reshape(8, 3)
    full, win = llama.split_kinds(CFG, plane)
    np.testing.assert_array_equal(np.asarray(full[:, 0]) // 3, [0, 4])
    np.testing.assert_array_equal(
        np.asarray(win[:, 0]) // 3, [1, 2, 3, 5, 6, 7])
    np.testing.assert_array_equal(
        np.asarray(llama.join_kinds(CFG, [full, win])), np.asarray(plane))


# ---------------------------------------------------------------------------
# The paged-decode kernel's window walk
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("lengths", [(200, 37, 0, 96), (129, 128, 127, 33)])
def test_the_kernels_window_walk_never_reads_an_unmapped_page(lengths):
    """`paged_decode_attention` (the interpreter) with a window of 64
    over rows whose table entries behind the window are unmapped and
    whose freed pages are poisoned, against `attention_xla` on the
    whole contiguous K/V: a row's liveness is read off the page of its
    NEWEST key (entry 0 is unmapped in a long row), and a row of length
    0 returns zeros."""
    window, page, kvh, h, d, s_max = 64, 16, 2, 4, 128, 256
    b, width = len(lengths), s_max // page
    key = jax.random.PRNGKey(3)
    k = jax.random.normal(key, (b, s_max, kvh, d))
    v = jax.random.normal(jax.random.fold_in(key, 1), (b, s_max, kvh, d))
    q = jax.random.normal(jax.random.fold_in(key, 2), (b, 1, h, d))
    kv_len = jnp.asarray(lengths, jnp.int32)
    want = A.attention_xla(
        q, k, v, causal=True, q_offset=kv_len - 1, kv_len=kv_len,
        window=window)
    n_pages = b * width
    first = jnp.maximum(kv_len - window, 0) // page  # the query is at len - 1
    block = jnp.arange(width)[None]
    mapped = (block >= first[:, None]) & (kv_len[:, None] > 0)
    table = jnp.where(
        mapped, jnp.arange(n_pages).reshape(b, width), n_pages).astype(jnp.int32)
    poison = (~mapped).reshape(n_pages)[None, :, None, None, None]

    def arena(t):
        pages = t.reshape(1, n_pages, page, kvh, d)
        return jnp.where(poison, 1e4, pages)

    got = A.paged_decode_attention(
        q, arena(k), arena(v), table, kv_len, jnp.int32(0), window=window,
        interpret=True)
    live = np.asarray(kv_len) > 0
    np.testing.assert_allclose(
        np.asarray(got)[live], np.asarray(want)[live], atol=2e-5)
    assert not np.asarray(got)[~live].any()


# ---------------------------------------------------------------------------
# Through the batcher
# ---------------------------------------------------------------------------


async def _collect(batcher, prompt, max_new, seed=0):
    out = []
    async for ids, _ in batcher.submit(prompt, max_new, GREEDY, seed=seed):
        out.extend(ids)
    return out


def _batcher(engine, **kw):
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("kv_cache_max_seq", 256)
    kw.setdefault("paged_kv", "on")
    kw.setdefault("paged_kv_page_size", 16)
    kw.setdefault("prefill_chunk", 32)
    return ContinuousBatcher(engine, BatchingConfig(**kw))


async def test_every_admission_path_and_a_second_turn_on_two_kinds(engine):
    """Short and long cold prompts in a burst, 40 decode steps each
    (every row passes the window and lets pages go), then a second turn
    of the longest session: prefix pages of both kinds reused, the
    window layers' live tail only. Greedy outputs equal the engine's
    own uncached generate; the allocator's books balance; the counters
    say what happened."""
    prompts = [ids_of(150, 8), ids_of(10, 9), ids_of(70, 7), ids_of(40, 3)]
    expected, _ = engine.generate(prompts, max_new_tokens=40, seed=0)
    batcher = _batcher(engine)
    leaves = jax.tree_util.tree_leaves(batcher.cache)
    # the full layers' arena and table, the lengths, then the window
    # layers': 4 slots x 7 pages ((32 + 32 + 2 mapped ahead) / 16 + 2)
    assert [x.shape for x in leaves] == [
        (2, 64, 16, 4, 16), (2, 64, 16, 4, 16), (4, 16), (4,),
        (6, 28, 16, 4, 16), (6, 28, 16, 4, 16), (4, 16)]
    batcher.start()
    try:
        got = await asyncio.gather(*(
            _collect(batcher, p, 40, i) for i, p in enumerate(prompts)))
        batcher.pages.check_invariants()
        assert got == expected
        first = batcher.counter_stats()
        again = prompts[0] + got[0] + ids_of(12, 33)
        want, _ = engine.generate([again], max_new_tokens=20, seed=0)
        assert await _collect(batcher, again, 20) == want[0]
        batcher.pages.check_invariants()
    finally:
        await batcher.stop()
    stats = batcher.counter_stats()
    assert stats["kv_window_pages_total"] == 28
    assert 0 < stats["kv_window_pages_in_use"] <= 28
    assert stats["kv_pages_total"] == 64  # the arena that keeps everything
    # the second turn let go of the first turn's tail behind its own
    assert stats["paged_window_pages_freed"] > first[
        "paged_window_pages_freed"]
    assert stats["paged_window_pages_mapped"] > first[
        "paged_window_pages_mapped"]
    assert stats["paged_prefix_hits"] == first["paged_prefix_hits"] + 1
    assert stats["paged_window_hits_refused"] == 0
    # 150 // 16 = 9 pages of the first turn's prompt reused
    assert stats["paged_pages_reused"] == first["paged_pages_reused"] + 9
    assert 0 < stats["window_keys_read"] < stats["window_keys_context"]
    assert stats["moe_experts_hit"] > 0
    assert batcher.cache_bytes() >= sum(
        x.nbytes for x in leaves if x.ndim > 1)  # all but the lengths
    # the ledger's arena component owns both arenas
    assert batcher.engine.ledger.component_bytes()[("", "kv_arena")] >= sum(
        x.nbytes for x in leaves if x.ndim > 2)


async def test_the_float8_cache_serves_and_differs(engine):
    """The benchmark's control: pages and the admission mini in
    float8_e4m3fn, read back in the model's dtype. It serves every
    admission path; its tokens are not the sound ones."""
    eng = GenerationEngine(CFG, ServingConfig(
        mesh=MeshConfig(tensor=1, data=1), kv_cache_dtype="fp8"))
    prompts = [ids_of(150, 8), ids_of(40, 3)]
    sound, _ = engine.generate(prompts, max_new_tokens=24, seed=0)
    batcher = _batcher(eng)
    assert batcher.cache.k.dtype == batcher.cache.window.k.dtype == (
        jnp.float8_e4m3fn)
    batcher.start()
    try:
        got = await asyncio.gather(*(
            _collect(batcher, p, 24, i) for i, p in enumerate(prompts)))
        batcher.pages.check_invariants()
    finally:
        await batcher.stop()
    # (a row may end early at the end-of-sequence id)
    assert all(0 < len(g) <= 24 for g in got) and got != sound


@pytest.mark.parametrize("serving, feature", [
    (dict(kv_cache_dtype="int8"), "kv_cache_dtype int8"),
    (dict(batching=BatchingConfig(
        paged_kv="on", paged_kv_host_bytes=1 << 20)),
     "batching.paged_kv_host_bytes (the host tier)"),
    (dict(role="prefill"), "a non-mixed serving.role (KV export/import)"),
    (dict(batching=BatchingConfig(prefill_interleave="on")),
     "batching.prefill_interleave"),
    (dict(kv_ring=True), "kv_ring"),
])
def test_what_moves_pages_of_one_arena_is_refused_by_name(serving, feature):
    assert feature in _UNSUPPORTED["smallthinker"]
    with pytest.raises(ValueError, match="smallthinker family"):
        GenerationEngine(CFG, ServingConfig(
            mesh=MeshConfig(tensor=1, data=1), **serving))


@pytest.mark.parametrize("model", [
    "tiny-llama", "tiny-mistral", "tiny-mla-moe", "tiny-dsv32", "tiny-keye",
    "tiny-jamba"])
def test_every_other_family_keeps_one_arena_and_one_table(model):
    """One kind that keeps everything: the paged cache has no window
    arena (its pytree has the leaves it had), the arena's layer axis is
    the family's caching layers, and `PagedKVCache.create` refuses
    nothing it took."""
    _, cfg = get_model(model)
    assert cfg.cache_kinds == ((cfg.cache_layers, None),)
    paged = llama.PagedKVCache.create(cfg, 2, 32, 4, 8)
    assert paged.window is None
    assert paged.k.shape[:3] == (cfg.cache_layers, 4, 8)
    assert paged.table.shape == (2, 4)
    assert len(jax.tree_util.tree_leaves(paged)) == (
        len(cfg.kv_planes) + 2 + len(cfg.row_state))


def test_mistrals_window_masks_and_its_pages_stay(engine):
    """ROADMAP B1 stands: the dense family's windowed member declares
    the kind "full", so its batcher has no window pages, its allocator
    maps a request's whole lifetime at admission and frees nothing by
    position, and the window counters read 0."""
    _, cfg = get_model("tiny-mistral")
    assert cfg.sliding_window == 16
    eng = GenerationEngine(
        cfg, ServingConfig(mesh=MeshConfig(tensor=1, data=1)))
    batcher = ContinuousBatcher(eng, BatchingConfig(
        max_batch_size=2, kv_cache_max_seq=64, paged_kv="on",
        paged_kv_page_size=8))
    assert batcher._window is None and batcher.pages.window is None
    assert batcher.cache.window is None
    assert [x.shape for x in jax.tree_util.tree_leaves(batcher.cache)] == [
        (4, 16, 8, 4, 32), (4, 16, 8, 4, 32), (2, 8), (2,)]
    adm = batcher.pages.admit(0, list(range(5, 45)), need_len=60)
    assert adm.pages_shared == 0
    assert int((batcher.pages.tables[0] != batcher.pages.sentinel).sum()) == 8
    stats = batcher.counter_stats()
    assert [stats[k] for k in stats if "window" in k] == [0] * 7
    assert stats["kv_pages_total"] == 16 and stats["kv_pages_in_use"] == 8
