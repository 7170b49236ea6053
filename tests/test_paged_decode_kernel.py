"""The paged-decode attention kernel (ops/attention.py
`paged_decode_attention`) and what chooses it (`paged_decode`).

1. VALUES — the kernel, interpreted, against `llama.paged_view` +
   `attention_xla` on the same arena: rows of unequal length, lengths
   on and one past a page and a block boundary, a row of length 0 and a
   freed slot (table all sentinel), rows sharing prefix pages, both
   GQA shapes, a window that binds, a layer other than 0, every query
   count the dispatch admits.
2. THE CHOICE — which inputs take the kernel, which gather the view,
   and that both are counted; through `llama.forward` the kernel path
   gives the gathered path's logits and the same arenas.

The platform rule is steered here, in the test (`_on_tpu` patched):
the program has no option for it. Marker `paged` (tier-1).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ggrmcp_tpu.core.config import MeshConfig
from ggrmcp_tpu.models import llama
from ggrmcp_tpu.ops import attention as A
from ggrmcp_tpu.parallel import mesh as mesh_mod

pytestmark = pytest.mark.paged

PAGE, WIDTH, LAYERS, D = 8, 8, 3, 32  # 64 positions a row
BLOCK = 2  # pages a block: block boundaries at 16, 32, 48


def arena_and_tables(rows, kvh, shared_pages=0, seed=0, dtype=jnp.float32):
    """Random pages behind `rows` tables; the first `shared_pages`
    entries of every row are row 0's (a shared prompt prefix)."""
    n_pages = rows * WIDTH + 1
    key = jax.random.PRNGKey(seed)
    shape = (LAYERS, n_pages, PAGE, kvh, D)
    k = jax.random.normal(key, shape, dtype)
    v = jax.random.normal(jax.random.fold_in(key, 1), shape, dtype)
    table = np.random.default_rng(seed).permutation(n_pages)[
        : rows * WIDTH].reshape(rows, WIDTH)
    table[:, :shared_pages] = table[0, :shared_pages]
    return k, v, table, n_pages


def gathered(q, k, v, table, kv_len, layer, window=None):
    return A.attention_xla(
        q, llama.paged_view(k, table, layer),
        llama.paged_view(v, table, layer),
        causal=True, q_offset=kv_len - q.shape[1], kv_len=kv_len,
        window=window,
    )


CASES = {
    # lens, then what differs from: 8/2 heads, layer 1, s 1, no window
    "unequal": dict(lens=(5, 23, 40, 64)),
    "page_edges": dict(lens=(8, 9, 7, 1)),
    "block_edges": dict(lens=(16, 17, 32, 33)),
    "empty_and_freed": dict(lens=(0, 29, 12, 50), freed=(2,)),
    "all_idle": dict(lens=(0, 0, 9, 30), freed=(2, 3)),
    "shared_prefix": dict(lens=(30, 26, 41, 17), shared_pages=2),
    "gqa_32_8": dict(lens=(5, 23, 40, 64), h=32, kvh=8),
    "gqa_4_2": dict(lens=(5, 23, 40, 64), h=4, kvh=2),
    "mha_4_4": dict(lens=(5, 23, 40, 64), h=4, kvh=4),
    "window_binds": dict(lens=(5, 23, 40, 64), window=12),
    "window_one_block": dict(lens=(9, 33, 48, 64), window=3),
    "layer_0": dict(lens=(5, 23, 40, 64), layer=0),
    "layer_last": dict(lens=(5, 23, 40, 64), layer=LAYERS - 1),
    "one_page_blocks": dict(lens=(5, 23, 40, 64), block_pages=1),
    "whole_row_block": dict(lens=(5, 23, 40, 64), block_pages=WIDTH),
    "default_block": dict(lens=(5, 23, 40, 64), block_pages=None),
    "bf16": dict(lens=(5, 23, 40, 64), dtype=jnp.bfloat16, tol=2e-2),
    "one_row": dict(lens=(37,)),
    **{
        f"s{s}": dict(lens=(s, 23, 40, 64), s=s)
        for s in range(2, A.PAGED_DECODE_MAX_SQ + 1)
    },
    "s4_window": dict(lens=(5, 23, 40, 64), s=4, window=10),
    "s5_overshoots_table": dict(lens=(5, 23, 40, 67), s=5),
}


@pytest.mark.parametrize("case", CASES, ids=list(CASES))
def test_kernel_equals_gathered_view(case):
    c = dict(
        h=8, kvh=2, s=1, layer=1, window=None, freed=(), shared_pages=0,
        block_pages=BLOCK, dtype=jnp.float32, tol=2e-5,
    )
    c.update(CASES[case])
    lens = np.asarray(c["lens"])
    rows = len(lens)
    k, v, table, n_pages = arena_and_tables(
        rows, c["kvh"], c["shared_pages"], dtype=c["dtype"])
    table[list(c["freed"])] = n_pages  # a freed slot keeps its length
    q = jax.random.normal(
        jax.random.PRNGKey(9), (rows, c["s"], c["h"], D), c["dtype"])
    table, kv_len = jnp.asarray(table, jnp.int32), jnp.asarray(lens, jnp.int32)
    layer = jnp.int32(c["layer"])
    got = A.paged_decode_attention(
        q, k, v, table, kv_len, layer, window=c["window"],
        block_pages=c["block_pages"], interpret=True,
    )
    want = gathered(q, k, v, table, kv_len, layer, c["window"])
    assert got.shape == want.shape and got.dtype == q.dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    # A row that walks nothing returns zeros (the gathered form attends
    # clipped junk there; the batcher drops such a row's tokens).
    idle = (lens == 0) | np.isin(np.arange(rows), c["freed"])
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got[idle], 0.0)
    np.testing.assert_allclose(
        got[~idle], want[~idle], atol=c["tol"], rtol=c["tol"])


def test_kernel_per_shard_equals_gathered_view():
    """Heads over `tensor`, manual over every axis of a data x tensor
    mesh: each shard walks the same tables over its own KV heads."""
    mesh = mesh_mod.build_mesh(MeshConfig(data=2, tensor=4))
    k, v, table, _ = arena_and_tables(4, kvh=4)
    q = jax.random.normal(jax.random.PRNGKey(9), (4, 2, 16, D))
    table = jnp.asarray(table, jnp.int32)
    kv_len = jnp.asarray([5, 23, 40, 64], jnp.int32)
    got = jax.jit(functools.partial(
        A.paged_decode_attention_sharded, mesh=mesh, window=20,
        interpret=True,
    ))(q, k, v, table, kv_len, jnp.int32(2))
    want = gathered(q, k, v, table, kv_len, jnp.int32(2), 20)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)
    with pytest.raises(ValueError, match="not divisible"):
        A.paged_decode_attention_sharded(
            q, k[:, :, :, :3], v[:, :, :, :3], table, kv_len, jnp.int32(0),
            mesh,
        )


def test_kernel_compiles_unless_interpret_is_asked_for():
    k, v, table, _ = arena_and_tables(1, kvh=2)
    with pytest.raises(Exception, match="(?i)interpret|tpu|mosaic"):
        A.paged_decode_attention(
            jnp.zeros((1, 1, 8, D)), k, v, jnp.asarray(table, jnp.int32),
            jnp.asarray([5], jnp.int32), jnp.int32(0),
        )


# ---------------------------------------------------------------------------
# The choice
# ---------------------------------------------------------------------------


@pytest.fixture
def on_tpu(monkeypatch):
    """What the dispatch sees on the chip, here: the platform answers
    TPU and the kernel it then picks runs interpreted."""
    kernel = A.paged_decode_attention
    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    monkeypatch.setattr(
        A, "paged_decode_attention",
        lambda *a, **kw: kernel(*a, **{**kw, "interpret": True}),
    )


def dispatch(s=1, kvh=2, d=128, page=PAGE, arena_dtype=jnp.float32, **kw):
    """`paged_decode` on a small arena -> (output or None, the change
    in the kernel and fallback counters)."""
    n_pages = 2 * WIDTH + 1
    shape = (LAYERS, n_pages, page, kvh, d)
    k = jax.random.normal(jax.random.PRNGKey(0), shape).astype(arena_dtype)
    q = jax.random.normal(jax.random.PRNGKey(1), (2, s, 4 * kvh, d))
    table = jnp.arange(2 * WIDTH, dtype=jnp.int32).reshape(2, WIDTH)
    before = dict(A.dispatch_counts)
    out = A.paged_decode(
        q, k, k, table, jnp.asarray([9, 30], jnp.int32), jnp.int32(1), **kw
    )
    took = {
        key: A.dispatch_counts[key] - before.get(key, 0)
        for key in ("paged_decode", "xla_fallback")
    }
    return out, took


KERNEL = {"paged_decode": 1, "xla_fallback": 0}
FALLBACK = {"paged_decode": 0, "xla_fallback": 1}
NOT_ITS_KIND = {"paged_decode": 0, "xla_fallback": 0}

DISPATCH = {
    "decode_step": (dict(), KERNEL),
    "widest_window": (dict(s=A.PAGED_DECODE_MAX_SQ), KERNEL),
    "mistral_window": (dict(window=4096), KERNEL),
    "engine_default": (dict(use_flash=None, flash_mesh=None), KERNEL),
    "prefill_chunk": (dict(s=A.PAGED_DECODE_MAX_SQ + 1), NOT_ITS_KIND),
    "float8_arena": (dict(arena_dtype=jnp.float8_e4m3fn), NOT_ITS_KIND),
    "bf16_arena_f32_queries": (dict(arena_dtype=jnp.bfloat16), NOT_ITS_KIND),
    "kernels_off_for_the_mesh": (dict(use_flash=False), FALLBACK),
    "head_dim_not_lanes": (dict(d=64), NOT_ITS_KIND),
    "page_under_a_tile": (dict(page=2, kvh=2), NOT_ITS_KIND),
}


@pytest.mark.parametrize("case", DISPATCH, ids=list(DISPATCH))
def test_dispatch_by_platform_storage_and_query_count(on_tpu, case):
    kw, want = DISPATCH[case]
    out, took = dispatch(**kw)
    assert took == want
    assert (out is not None) == (want is KERNEL)
    if out is not None:
        assert out.shape == (2, kw.get("s", 1), 8, 128)
    stats = A.dispatch_stats()
    # every kernel's programs: the counters are the process's, and a
    # worker may have run the latent-prefill or expert kernels' tests
    assert stats["attn_kernel_programs"] == sum(
        A.dispatch_counts[name] for name in (
            "flash", "flash_sharded", "paged_decode", "latent_prefill",
            "grouped_experts"))
    assert stats["attn_kernel_fallbacks"] == A.dispatch_counts["xla_fallback"]


def test_off_the_tpu_nothing_is_wanted_or_counted():
    out, took = dispatch()
    assert out is None and took == NOT_ITS_KIND


@pytest.mark.parametrize("tensor,want", [(4, KERNEL), (8, FALLBACK)])
def test_dispatch_on_a_mesh(on_tpu, tensor, want):
    """With the engine's `flash_mesh` the kernel runs per shard; a
    `tensor` axis that does not divide the KV heads is a counted
    fallback, as for the prefill kernel."""
    mesh = mesh_mod.build_mesh(MeshConfig(tensor=tensor, data=0))
    out, took = dispatch(kvh=4, page=16, flash_mesh=mesh)
    assert took == want and (out is not None) == (want is KERNEL)


# ---------------------------------------------------------------------------
# Through the model
# ---------------------------------------------------------------------------

# Heads 128 wide, as the dispatch asks of a page on the chip.
CFG = llama.LlamaConfig(
    name="tiny-wide-head", vocab_size=256, hidden_dim=128, num_layers=3,
    num_heads=4, num_kv_heads=2, head_dim=128, ffn_dim=256,
    max_seq_len=256, sliding_window=24, dtype="float32",
)


def filled_cache(kv_dtype: str, s: int) -> llama.PagedKVCache:
    """Random pages behind a row in mid-sequence, a row about to cross
    a page boundary, a freed slot and a row the window binds in."""
    rng = np.random.default_rng(7)
    n_pages = 4 * WIDTH + 1
    cache = llama.PagedKVCache.create(
        CFG, 4, WIDTH * PAGE, n_pages, PAGE, kv_dtype)

    def rand(a):
        if a.dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, a.shape), jnp.int8)
        return jnp.asarray(rng.standard_normal(a.shape), a.dtype)

    table = rng.permutation(n_pages)[: 4 * WIDTH].reshape(4, WIDTH)
    table[2, :] = n_pages
    return cache._replace(
        k=jax.tree.map(rand, cache.k), v=jax.tree.map(rand, cache.v),
        table=jnp.asarray(table, jnp.int32),
        length=jnp.asarray(
            [11, 2 * PAGE - 1, 40, WIDTH * PAGE - s], jnp.int32),
    )


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.PRNGKey(3), CFG)


def forward(params, tokens, cache, **kw):
    return jax.jit(
        lambda p, t, c: llama.forward(p, CFG, t, c, **kw)
    )(params, tokens, cache)


@pytest.mark.parametrize("s", [1, 5], ids=["step", "window"])
def test_forward_takes_the_kernel_and_matches_the_view(params, on_tpu, s):
    cache = filled_cache("", s)
    tokens = jnp.asarray(
        np.random.default_rng(s).integers(3, CFG.vocab_size, (4, s)),
        jnp.int32,
    )
    before = dict(A.dispatch_counts)
    got_logits, got = forward(params, tokens, cache)
    # One traced call: the layer scan's body is traced once.
    assert A.dispatch_counts["paged_decode"] == before.get(
        "paged_decode", 0) + 1
    want_logits, want = forward(params, tokens, cache, use_flash=False)
    assert A.dispatch_counts["xla_fallback"] == before.get(
        "xla_fallback", 0) + 1
    live = [0, 1, 3]  # the freed slot's logits are junk either way
    np.testing.assert_allclose(
        np.asarray(got_logits)[live], np.asarray(want_logits)[live],
        atol=2e-4, rtol=2e-4)
    # Layer 0 writes the same K/V on both paths, deeper layers what
    # their inputs round to; the freed slot wrote nothing on either.
    np.testing.assert_array_equal(got.k[0], want.k[0])
    np.testing.assert_array_equal(got.length, want.length)
    mapped = np.unique(np.asarray(cache.table)[live])
    for a, b in ((got.k, want.k), (got.v, want.v)):
        np.testing.assert_allclose(
            np.asarray(a)[:, mapped], np.asarray(b)[:, mapped],
            atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize(
    "kv_dtype,use_flash,fell",
    [("int8", None, 0), ("fp8", None, 0), ("", False, 1)],
    ids=["int8_pages", "fp8_pages", "kernels_off"],
)
def test_forward_keeps_the_view(params, on_tpu, kv_dtype, use_flash, fell):
    """Quantized and float8 arenas are not the kernel's kind (the
    benchmark's `int8_kv` control reads the gathered path); with the
    engine's kernels off the step is a counted fallback."""
    cache = filled_cache(kv_dtype, 1)
    before = dict(A.dispatch_counts)
    logits, _ = forward(
        params, jnp.full((4, 1), 5, jnp.int32), cache, use_flash=use_flash)
    assert np.isfinite(np.asarray(logits)).all()
    assert A.dispatch_counts["paged_decode"] == before.get("paged_decode", 0)
    assert A.dispatch_counts["xla_fallback"] == (
        before.get("xla_fallback", 0) + fell)


def test_a_prefill_chunk_keeps_the_view(params, on_tpu):
    cache = filled_cache("", A.PAGED_DECODE_MAX_SQ + 1)
    before = dict(A.dispatch_counts)
    forward(
        params, jnp.full((4, A.PAGED_DECODE_MAX_SQ + 1), 5, jnp.int32), cache)
    assert dict(A.dispatch_counts) == before
