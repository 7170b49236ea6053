"""The indexer's exact selection (ops/indexer.py: `selection_mask` on
the cut `kth_largest` finds by counting) against the same four lines
with the cut read off `lax.top_k`, bit for bit; and the two families'
suffix programs, which must hold no sort: on the chip `lax.top_k` over
a `[512, 32768]` row was a key-and-index sort of all of it, a quarter
of the device's time in the cell that ran it (PERF.md, PR 38)."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ggrmcp_tpu.models import keye as K
from ggrmcp_tpu.models import llama
from ggrmcp_tpu.models import mla_moe as M
from ggrmcp_tpu.ops import attention as A
from ggrmcp_tpu.ops import indexer

TOPK, WIDTH = 16, 256
# the widths the ladder keeps at 256 keys and 16 of them wanted
LADDER = (32, 64, 128, 256)
SHAPES = {"one_row": (1, 24, WIDTH), "rows": (3, 8, WIDTH)}


def sorted_mask(scores, topk):
    """`selection_mask` as it was while it sorted."""
    thr = jax.lax.top_k(scores, topk)[0][..., -1:]
    above = scores > thr
    tied = (scores == thr) & (scores > -jnp.inf)
    need = topk - above.sum(-1, keepdims=True)
    return above | (tied & (jnp.cumsum(tied, axis=-1) <= need))


def scores_of(kind: str, shape) -> np.ndarray:
    rng = np.random.RandomState(len(kind) + shape[0])
    x = rng.randn(*shape).astype(np.float32)
    if kind == "random":
        return x * 1e3 ** rng.randn(*shape[:-1], 1).astype(np.float32)
    if kind == "tied_at_the_cut":  # ~25 distinct values a row
        return np.round(x * 4) / 4
    if kind == "signed_zeros":  # the cut is a zero, of either sign
        x = np.where(rng.rand(*shape) < 0.03, np.abs(x), -np.abs(x))
        zeros = rng.rand(*shape) < 0.3
        return np.where(
            zeros, np.where(rng.rand(*shape) < 0.5, 0.0, -0.0), x
        ).astype(np.float32)
    if kind == "short_rows":  # queries that see 0, 1, .. keys; some < topk
        seen = np.arange(shape[-2])[:, None] * 3
        return np.where(np.arange(shape[-1])[None] < seen, x, -np.inf)
    if kind == "nothing_seen":  # whole rows of -inf, and whole +inf ones
        x[..., ::2, :] = -np.inf
        x[..., 1, :] = np.inf
        return x
    raise AssertionError(kind)


KINDS = ("random", "tied_at_the_cut", "signed_zeros", "short_rows",
         "nothing_seen")
REACHES = (None, 0, 1) + tuple(
    w + d for w in LADDER for d in (-1, 0, 1) if w + d <= WIDTH)


@functools.lru_cache(maxsize=None)
def programs(shape):
    """One compile a shape: `reach` is traced, as in the models."""
    return (
        jax.jit(lambda s: indexer.selection_mask(s, TOPK)),
        jax.jit(lambda s, r: indexer.selection_mask(s, TOPK, reach=r)),
        jax.jit(lambda s: sorted_mask(s, TOPK)),
    )


@pytest.mark.parametrize("reach", REACHES)
@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("kind", KINDS)
def test_the_counted_selection_is_the_sorted_one_bit_for_bit(
        kind, shape, reach):
    scores = scores_of(kind, SHAPES[shape])
    if reach is not None:  # what the caller promises of `reach`
        scores[..., reach:] = -np.inf
    scores = jnp.asarray(scores)
    whole, upto, want = programs(SHAPES[shape])
    got = whole(scores) if reach is None else upto(scores, jnp.int32(reach))
    want = np.asarray(want(scores))
    assert got.dtype == jnp.bool_ and got.shape == scores.shape
    np.testing.assert_array_equal(np.asarray(got), want)
    seen = np.asarray(scores) > -np.inf
    np.testing.assert_array_equal(
        want.sum(-1), np.minimum(seen.sum(-1), TOPK))


@pytest.mark.parametrize("k", [1, 2, 16, 255, 256])
def test_the_cut_is_the_value_top_k_returns(k):
    """Every rank, the first and the last of the row among them, and
    values at the ends of float32's range."""
    rng = np.random.RandomState(k)
    x = rng.randn(4, WIDTH).astype(np.float32)
    x[0, :8] = [3.4e38, -3.4e38, 1e-38, -1e-38, np.inf, -np.inf, 1.0, -1.0]
    x[1] = np.round(x[1])
    x[2, k - 1:] = -np.inf  # k - 1 finite scores: the cut is -inf
    got = jax.jit(lambda s: indexer.kth_largest(s, k))(jnp.asarray(x))
    want = jax.lax.top_k(jnp.asarray(x), k)[0][..., -1:]
    assert got.dtype == jnp.float32 and got.shape == (4, 1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


SORTS = re.compile(r"\b(sort|top_k|TopK|ApproxTopK)\b")


def suffix_program(family):
    """One layer's attention for a suffix of 24 tokens on a past of 40
    in a contiguous cache of 64 keys (`index_topk` 16): what a
    re-admission runs a layer, lowered, with the count of sparse-chunk
    branches its trace took."""
    fam, key = {"keye": (K, "sparse_gqa_chunk"),
                "dsv32": (M, "sparse_chunk")}[family]
    cfg = fam.CONFIGS[f"tiny-{family}"]
    params = jax.eval_shape(
        lambda k: fam.init_params(k, cfg), jax.random.PRNGKey(0))
    lp = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
        {k: v for k, v in params["layers"].items() if not k.startswith("w_")})
    planes = jax.eval_shape(
        lambda: llama.cache_planes(llama.KVCache.create(cfg, 1, 64)))
    x = jax.ShapeDtypeStruct((1, 24, cfg.hidden_dim), cfg.jnp_dtype)

    def layer(x, lp, planes):
        return fam.attention_block(
            x, lp, cfg, 40 + jnp.arange(24)[None], planes, jnp.asarray([40]),
            None, 2)

    before = A.dispatch_counts[key]
    text = jax.jit(layer).lower(x, lp, planes).as_text()
    return text, A.dispatch_counts[key] - before


@pytest.mark.parametrize("family", ["keye", "dsv32"])
def test_a_suffix_program_holds_no_sort(family, monkeypatch):
    text, branches = suffix_program(family)
    assert branches == 1  # the selection was traced
    assert "while" in text  # and its passes are there
    assert not SORTS.search(text), SORTS.search(text).group(0)
    # the same search finds the sort where there is one
    monkeypatch.setattr(
        indexer, "kth_largest",
        lambda scores, k: jax.lax.top_k(scores, k)[0][..., -1:])
    text, branches = suffix_program(family)
    assert branches == 1 and SORTS.search(text)
