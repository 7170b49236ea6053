"""The latent-prefill attention kernel (ops/attention.py
`latent_prefill_attention`) and what chooses it (`latent_prefill`,
called from `mla_moe.attention_block`).

1. VALUES — the kernel, interpreted, against `mla_moe.latent_attention`
   (the XLA walk, absorbed form) on the same plane: rows of unequal
   `kv_len`, a chunk that starts on and one past a key-block boundary,
   a chunk whose tail is padding, a row with no real query (walks
   nothing, returns zeros), one row and several, a layer other than 0,
   the tiny config's plane and one 640-wide plane.
2. THE CHOICE — which inputs take the kernel and which keep the walk,
   and that both are counted; through `mla_moe.forward`, chunked
   prefill then decode gives the walk's logits.

The platform rule is steered by tests/conftest.py's
`latent_prefill_on_tpu` (`_on_tpu` patched, the kernel interpreted):
the program has no option for it. Marker `paged` (tier-1).
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ggrmcp_tpu.core.config import MeshConfig
from ggrmcp_tpu.models import llama
from ggrmcp_tpu.models import mla_moe as M
from ggrmcp_tpu.ops import attention as A
from ggrmcp_tpu.ops.quant import QuantizedArray
from ggrmcp_tpu.parallel import mesh as mesh_mod

pytestmark = pytest.mark.paged

TINY = M.CONFIGS["tiny-mla-moe"]  # latent 48 in a 128-wide plane
# The published attention widths (latent 576 in a 640-wide plane), few
# heads: the kernel is interpreted here.
WIDE = dataclasses.replace(
    TINY, name="wide-latent", num_heads=2, kv_lora_rank=512,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
)
LAYERS, S_MAX, S = 3, 128, 32
BLOCK_Q, BLOCK_K = 8, 16  # key-block boundaries at 16, 32, 48, ...


def operands(cfg, q_off, s, dtype, seed=0):
    """A plane of random latents (zeros past the latent, as the model
    writes it), a chunk's queries and an up-projection."""
    b, h, w = len(q_off), cfg.num_heads, cfg.kv_planes[0][0]
    keys = [jax.random.fold_in(jax.random.PRNGKey(seed), i) for i in range(4)]
    plane = jax.random.normal(keys[0], (LAYERS, b, S_MAX, w), dtype)
    plane = plane.at[..., cfg.latent_dim:].set(0)
    q_nope = jax.random.normal(keys[1], (b, s, h, cfg.qk_nope_head_dim), dtype)
    q_rope = jax.random.normal(keys[2], (b, s, h, cfg.qk_rope_head_dim), dtype)
    wkv_b = 0.2 * jax.random.normal(
        keys[3],
        (cfg.kv_lora_rank, h, cfg.qk_nope_head_dim + cfg.v_head_dim), dtype)
    return plane, q_nope, q_rope, wkv_b


def walked(cfg, plane, q_nope, q_rope, wkv_b, layer, q_off, kv_len, n_real,
           allowed=None, block_k=BLOCK_K):
    """`latent_attention` as `attention_block` calls it on a contiguous
    plane: the walk stops at the last key a real query may see, masked
    by the queries' selection `allowed` [B, S, S_max] if they have one."""
    s = q_nope.shape[1]
    positions = q_off[:, None] + jnp.arange(s)[None, :]
    last = jnp.where(jnp.arange(s)[None, :] < n_real[:, None], positions, -1)
    n_blocks = jnp.clip(
        (jnp.max(last) + block_k) // block_k, 0, plane.shape[2] // block_k)

    def fetch(i):
        return jax.lax.dynamic_slice_in_dim(
            plane[layer], i * block_k, block_k, 1)

    return M.latent_attention(
        q_nope, q_rope, fetch, n_blocks, block_k, wkv_b, positions, kv_len,
        cfg, absorbed=True,
        allowed=None if allowed is None else (
            lambda i: jax.lax.dynamic_slice_in_dim(
                allowed, i * block_k, block_k, 2)))


def kernel(cfg, plane, q_nope, q_rope, wkv_b, layer, q_off, kv_len, n_real,
           allowed=None, **blocks):
    """The kernel inside the folding `attention_block` does around it."""
    nope = q_nope.shape[-1]
    out = A.latent_prefill_attention(
        M.absorbed_queries(q_nope, q_rope, wkv_b[..., :nope], plane.shape[-1]),
        plane, jnp.int32(layer), q_off, kv_len, q_off + n_real - 1, allowed,
        value_width=cfg.kv_lora_rank,
        scale=(nope + cfg.qk_rope_head_dim) ** -0.5, interpret=True, **blocks)
    return jnp.einsum("bshc,chd->bshd", out, wkv_b[..., nope:])


CASES = {
    # q_off, then what differs from: every query real, layer 1, the
    # tiny config in float32, tiles of 8 queries and blocks of 16 keys
    "unequal_rows": dict(q_off=(40, 17, 3, 96)),
    "starts_on_a_key_block": dict(q_off=(32, 48)),
    "starts_one_past_a_key_block": dict(q_off=(33, 49)),
    "first_chunk": dict(q_off=(0, 0)),
    "fills_the_plane": dict(q_off=(S_MAX - S, 40)),
    "tail_is_padding": dict(q_off=(40, 17), n_real=(20, 5)),
    "padding_from_a_tile_edge": dict(q_off=(40, 17), n_real=(16, 24)),
    "ends_inside_a_key_block": dict(q_off=(41, 18), s=24),
    "a_row_with_no_real_query": dict(q_off=(40, 17), n_real=(0, S)),
    "no_real_query_at_all": dict(q_off=(40, 17), n_real=(0, 0)),
    "kv_len_binds": dict(q_off=(40, 17), kv_short=7),
    "one_row": dict(q_off=(37,)),
    "layer_0": dict(q_off=(40, 17), layer=0),
    "layer_last": dict(q_off=(40, 17), layer=LAYERS - 1),
    "one_tile": dict(q_off=(40, 17), block_q=S),
    "one_query_tiles": dict(q_off=(40, 17), block_q=2),
    "one_key_block": dict(q_off=(40, 17), block_k=S_MAX),
    "its_own_blocks": dict(q_off=(40, 17), block_q=None, block_k=None),
    "heads_32": dict(q_off=(40, 17), heads=32),
    "bf16": dict(q_off=(40, 17), dtype=jnp.bfloat16, tol=3e-2),
    "plane_640": dict(q_off=(40, 17), cfg=WIDE, s=16),
    "plane_640_bf16": dict(
        q_off=(33, 5), cfg=WIDE, s=16, dtype=jnp.bfloat16, tol=1e-1),
}


@pytest.mark.parametrize("case", CASES, ids=list(CASES))
def test_kernel_equals_the_walk(case):
    c = dict(
        cfg=TINY, s=S, layer=1, n_real=None, kv_short=0, heads=None,
        block_q=BLOCK_Q, block_k=BLOCK_K, dtype=jnp.float32, tol=2e-5,
    )
    c.update(CASES[case])
    cfg, s = c["cfg"], c["s"]
    if c["heads"]:
        cfg = dataclasses.replace(cfg, num_heads=c["heads"])
    q_off = jnp.asarray(c["q_off"], jnp.int32)
    n_real = jnp.asarray(c["n_real"] or (s,) * len(q_off), jnp.int32)
    kv_len = q_off + s - c["kv_short"]
    plane, q_nope, q_rope, wkv_b = operands(cfg, q_off, s, c["dtype"])
    args = (cfg, plane, q_nope, q_rope, wkv_b, c["layer"], q_off, kv_len,
            n_real)
    got = kernel(*args, block_q=c["block_q"], block_k=c["block_k"])
    # The walk in float32 on the same (rounded) operands: the CPU has
    # no bf16 x bf16 -> f32 batched matmul to run it in.
    want = walked(cfg, *(a.astype(jnp.float32) for a in args[1:5]), *args[5:])
    assert got.shape == want.shape and got.dtype == q_nope.dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    # A padding query's output is undefined (finite) on both paths; a
    # row with no real query walks nothing and returns zeros.
    real = np.arange(s)[None, :] < np.asarray(n_real)[:, None]
    np.testing.assert_array_equal(got[np.asarray(n_real) == 0], 0.0)
    assert float(np.abs(want[real]).max(initial=1.0)) > 0.1
    np.testing.assert_allclose(
        got[real], want[real], atol=c["tol"], rtol=c["tol"])


# With a selection a query (`allowed`): what the indexer family's chunks
# and suffixes bring. Index scores drawn on a few levels, so that the
# threshold is tied; `selection_mask` makes the set as the model does.
TOPK = 8

SELECTED = {
    # heads, queries, plane, then what differs from: the kernel's own
    # blocks, every query real, the past a whole number of key blocks
    "heads_128_block_q_4": dict(h=128, s=512, s_max=1024, q_off=(384,)),
    "heads_32_block_q_16": dict(h=32, s=512, s_max=1024, q_off=(384,)),
    "real_queries_end_mid_tile": dict(q_off=(40, 17), n_real=(21, 5)),
    "a_row_with_no_real_query": dict(q_off=(40, 17), n_real=(0, S)),
    "row_shorter_than_topk": dict(q_off=(0, 3), s=8, topk=16, block_q=8),
    "ties_at_the_threshold": dict(q_off=(40, 17), levels=2),
    "past_ends_mid_block": dict(q_off=(41, 23), s=24),
    "kv_len_binds": dict(q_off=(40, 17), kv_short=7),
    "unequal_rows_layer_2": dict(q_off=(40, 17, 3, 96), layer=2),
    "bf16": dict(q_off=(40, 17), dtype=jnp.bfloat16, tol=3e-2),
    "plane_640": dict(q_off=(40, 17), cfg=WIDE, s=16),
}


@pytest.mark.parametrize("case", SELECTED, ids=list(SELECTED))
def test_kernel_with_a_selection_equals_the_masked_walk(case):
    c = dict(
        cfg=TINY, h=None, s=S, s_max=S_MAX, layer=1, n_real=None, kv_short=0,
        topk=TOPK, levels=4, block_q=BLOCK_Q, block_k=BLOCK_K,
        dtype=jnp.float32, tol=2e-5,
    )
    c.update(SELECTED[case])
    cfg, s, s_max = c["cfg"], c["s"], c["s_max"]
    if c["h"]:  # the served head counts: the kernel's own tile
        cfg = dataclasses.replace(cfg, num_heads=c["h"])
        c.update(block_q=None, block_k=None)
    q_off = jnp.asarray(c["q_off"], jnp.int32)
    n_real = jnp.asarray(c["n_real"] or (s,) * len(q_off), jnp.int32)
    kv_len = q_off + s - c["kv_short"]
    plane, q_nope, q_rope, wkv_b = operands(cfg, q_off, s, c["dtype"])
    plane = jnp.tile(plane, (1, 1, s_max // S_MAX, 1))
    positions = q_off[:, None] + jnp.arange(s)[None, :]
    k_pos = jnp.arange(s_max)[None, None, :]
    seen = (k_pos <= positions[:, :, None]) & (k_pos < kv_len[:, None, None])
    scores = jnp.where(seen, jax.random.randint(
        jax.random.PRNGKey(7), seen.shape, 0, c["levels"]).astype(jnp.float32),
        -jnp.inf)
    allowed = M.selection_mask(scores, c["topk"])
    picked, sees = np.asarray(allowed), np.asarray(seen)
    np.testing.assert_array_equal(
        picked.sum(-1), np.minimum(sees.sum(-1), c["topk"]))
    block_k = c["block_k"] or 512
    by_block = picked.reshape(*picked.shape[:2], -1, block_k).any(-1)
    if case != "row_shorter_than_topk":
        # some query sees keys of a block and selected none of them
        assert (sees.reshape(by_block.shape + (-1,)).any(-1) & ~by_block).any()
    else:
        np.testing.assert_array_equal(picked, sees)

    args = (cfg, plane, q_nope, q_rope, wkv_b, c["layer"], q_off, kv_len,
            n_real)
    blocks = dict(block_q=c["block_q"], block_k=c["block_k"])
    got = kernel(*args, allowed, **blocks)
    # (in float32 on the same rounded operands, as above)
    want = walked(
        cfg, *(a.astype(jnp.float32) for a in args[1:5]), *args[5:], allowed,
        block_k=block_k)
    assert got.shape == want.shape and got.dtype == q_nope.dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    real = np.arange(s)[None, :] < np.asarray(n_real)[:, None]
    np.testing.assert_array_equal(got[np.asarray(n_real) == 0], 0.0)
    assert float(np.abs(want[real]).max()) > 0.1
    np.testing.assert_allclose(
        got[real], want[real], atol=c["tol"], rtol=c["tol"])
    # and the selection binds: the unmasked kernel answers otherwise
    if case != "row_shorter_than_topk":
        dense = np.asarray(kernel(*args, **blocks), np.float32)
        assert np.abs(dense[real] - want[real]).max() > 0.5


def pallas_eqn(fn, *args):
    """The `pallas_call` equation of a traced call."""
    def find(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                return eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                if (found := find(sub)) is not None:
                    return found
    return find(jax.make_jaxpr(fn)(*args).jaxpr)


@pytest.mark.parametrize("selected", [False, True], ids=["without", "with"])
def test_the_program_without_a_selection_is_the_parents(selected):
    """32 heads on a 16,384-key plane at the published widths (the
    kanana cell's chunk): without the operand, the operands, scratch
    and VMEM figure the kernel had before it took one (numbers from the
    parent commit, 39237e8); with it, one operand, its slab buffer and
    its semaphores more."""
    q = jnp.zeros((1, 512, 32, 640), jnp.bfloat16)
    plane = jnp.zeros((6, 1, 16384, 640), jnp.bfloat16)
    one = jnp.zeros((1,), jnp.int32)
    args = [q, plane, jnp.int32(0), one, one, one]
    if selected:
        args.append(jnp.ones((1, 512, 16384), bool))
    eqn = pallas_eqn(
        functools.partial(
            A.latent_prefill_attention, value_width=512, scale=0.1,
            interpret=True),
        *args)
    mapping = eqn.params["grid_mapping"]
    vmem = eqn.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes
    assert mapping.grid == (1, 32) and mapping.num_index_operands == 4
    if not selected:
        assert (len(eqn.invars), mapping.num_scratch_operands, vmem) == (
            7, 3, 13107200)
        assert [tuple(v.aval.shape) for v in eqn.invars[4:]] == [
            (1, 16384, 640), (512, 1), (6, 1, 16384, 640)]
    else:
        assert (len(eqn.invars), mapping.num_scratch_operands) == (8, 5)
        assert eqn.invars[-1].aval.shape == (1, 32, 16, 16384)
        assert 13107200 < vmem < 16 << 20


@pytest.mark.parametrize("selected", [False, True], ids=["all", "selected"])
def test_kernel_per_shard_equals_the_walk(selected):
    """Rows over `data`, heads over `tensor`, manual over every axis of
    a data x tensor mesh: each shard walks its rows' whole latents, and
    a selection goes with its rows, whole for every shard of heads."""
    mesh = mesh_mod.build_mesh(MeshConfig(data=2, tensor=4))
    q_off = jnp.asarray([40, 17, 3, 96], jnp.int32)
    n_real = jnp.asarray([S, 20, 0, S], jnp.int32)
    plane, q_nope, q_rope, wkv_b = operands(TINY, q_off, S, jnp.float32)
    nope = TINY.qk_nope_head_dim
    q = M.absorbed_queries(q_nope, q_rope, wkv_b[..., :nope], 128)
    kw = dict(
        value_width=TINY.kv_lora_rank,
        scale=(nope + TINY.qk_rope_head_dim) ** -0.5, interpret=True,
    )
    if selected:
        kw["allowed"] = jax.random.bernoulli(
            jax.random.PRNGKey(9), 0.3, (4, S, S_MAX))
    got = jax.jit(functools.partial(
        A.latent_prefill_attention_sharded, mesh=mesh, **kw,
    ))(q, plane, jnp.int32(2), q_off, q_off + S, q_off + n_real - 1)
    want = A.latent_prefill_attention(
        q, plane, jnp.int32(2), q_off, q_off + S, q_off + n_real - 1, **kw)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)
    with pytest.raises(ValueError, match="not divisible"):
        A.latent_prefill_attention_sharded(
            q[:3], plane[:, :3], jnp.int32(0), q_off[:3], q_off[:3] + S,
            q_off[:3], mesh, **kw)


def test_kernel_compiles_unless_interpret_is_asked_for():
    plane, q_nope, _, _ = operands(TINY, (5,), S, jnp.float32)
    with pytest.raises(Exception, match="(?i)interpret|tpu|mosaic"):
        A.latent_prefill_attention(
            jnp.zeros((1, S, 4, 128)), plane, jnp.int32(0),
            jnp.asarray([5], jnp.int32), jnp.asarray([5 + S], jnp.int32),
            jnp.asarray([4 + S], jnp.int32), value_width=32, scale=1.0,
        )


def test_the_vmem_count_at_the_published_widths():
    """512 score rows on 640-wide keys and 512-wide values in bf16:
    under the chip's 16 MiB scoped default, headroom included."""
    block_q, block_k = A._latent_prefill_blocks(512, 32, 16384)
    assert (block_q * 32, block_k) == (512, 512)
    need = A._latent_prefill_vmem_bytes(512, 640, 512, block_k, 2)
    assert 8 << 20 < need < 16 << 20


# ---------------------------------------------------------------------------
# The choice
# ---------------------------------------------------------------------------


def counted(before):
    return {
        key: A.dispatch_counts[key] - before.get(key, 0)
        for key in ("latent_prefill", "xla_fallback")
    }


def dispatch(s=32, h=4, w=256, v=128, rows=2, plane_dtype=jnp.float32,
             s_max=S_MAX, selected=False, **kw):
    """`latent_prefill` on a small plane -> (output or None, the change
    in the kernel and fallback counters)."""
    plane = jax.random.normal(
        jax.random.PRNGKey(0), (LAYERS, rows, s_max, w)).astype(plane_dtype)
    q = jax.random.normal(jax.random.PRNGKey(1), (rows, s, h, w))
    q_off = jnp.arange(rows, dtype=jnp.int32) * 9 + 20
    before = dict(A.dispatch_counts)
    out = A.latent_prefill(
        q, plane, jnp.int32(1), q_off, q_off + s, q_off + s - 1,
        jnp.ones((rows, s, s_max), bool) if selected else None,
        value_width=v, scale=0.1, **kw)
    return out, counted(before)


KERNEL = {"latent_prefill": 1, "xla_fallback": 0}
FALLBACK = {"latent_prefill": 0, "xla_fallback": 1}
NOT_ITS_KIND = {"latent_prefill": 0, "xla_fallback": 0}

DISPATCH = {
    "prefill_chunk": (dict(), KERNEL),
    "engine_default": (dict(use_flash=None, flash_mesh=None), KERNEL),
    "published_widths": (dict(s=16, h=2, w=640, v=512), KERNEL),
    "float8_plane": (dict(plane_dtype=jnp.float8_e4m3fn), NOT_ITS_KIND),
    "bf16_plane_f32_queries": (dict(plane_dtype=jnp.bfloat16), NOT_ITS_KIND),
    "kernels_off_for_the_mesh": (dict(use_flash=False), FALLBACK),
    "plane_not_lanes": (dict(w=192), NOT_ITS_KIND),
    "value_not_lanes": (dict(v=96), NOT_ITS_KIND),
    "tile_under_a_sublane_group": (dict(s=6, h=4), NOT_ITS_KIND),
    "with_a_selection": (dict(selected=True), KERNEL),
    "selection_kernels_off": (dict(selected=True, use_flash=False), FALLBACK),
    "key_block_of_64": (dict(s_max=64, s=16), KERNEL),
    "selection_slab_not_lanes": (
        dict(s_max=64, s=16, selected=True), NOT_ITS_KIND),
}


@pytest.mark.parametrize("case", DISPATCH, ids=list(DISPATCH))
def test_dispatch_by_platform_storage_and_widths(latent_prefill_on_tpu, case):
    kw, want = DISPATCH[case]
    out, took = dispatch(**kw)
    assert took == want
    assert (out is not None) == (want is KERNEL)
    if out is not None:
        assert out.shape == (
            2, kw.get("s", 32), kw.get("h", 4), kw.get("v", 128))
    stats = A.dispatch_stats()
    assert stats["attn_kernel_programs"] == (
        A.dispatch_counts["flash"] + A.dispatch_counts["flash_sharded"]
        + A.dispatch_counts["paged_decode"]
        + A.dispatch_counts["latent_prefill"]
    )
    assert stats["attn_kernel_fallbacks"] == A.dispatch_counts["xla_fallback"]


def test_off_the_tpu_nothing_is_wanted_or_counted():
    out, took = dispatch()
    assert out is None and took == NOT_ITS_KIND


@pytest.mark.parametrize(
    "mesh,rows,want",
    [(dict(tensor=4, data=0), 2, KERNEL), (dict(tensor=8, data=0), 2, FALLBACK),
     (dict(tensor=2, data=4), 2, FALLBACK)],
    ids=["heads_over_tensor", "tensor_over_heads", "data_over_rows"],
)
def test_dispatch_on_a_mesh(latent_prefill_on_tpu, mesh, rows, want):
    """With the engine's `flash_mesh` the kernel runs per shard; a
    mesh that divides neither the heads nor the rows is a counted
    fallback, as for the prefill kernel."""
    out, took = dispatch(
        rows=rows, flash_mesh=mesh_mod.build_mesh(MeshConfig(**mesh)))
    assert took == want and (out is not None) == (want is KERNEL)


# ---------------------------------------------------------------------------
# Through the model
# ---------------------------------------------------------------------------

# A latent of whole lanes (rank 128 in a 256-wide plane), as the
# dispatch asks on the chip; chunks of 256 queries, more than
# ABSORBED_MAX_QUERIES.
CFG = dataclasses.replace(
    TINY, name="tiny-lane-latent", num_layers=2, kv_lora_rank=128,
    max_seq_len=512,
)
CHUNK = 2 * M.ABSORBED_MAX_QUERIES


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: M.init_params(k, CFG))(jax.random.PRNGKey(3))


def prefill_then_decode(params, cache, n_prompt=CHUNK + 150, **kw):
    """Two chunks (the second's tail is padding) and one decode step:
    every real position's logits."""
    ids = np.random.default_rng(4).integers(3, CFG.vocab_size, 2 * CHUNK + 1)
    step = jax.jit(
        lambda p, t, c, v: M.forward(p, CFG, t, c, valid=v, **kw))
    out = []
    for off in (0, CHUNK):
        valid = (off + np.arange(CHUNK) < n_prompt)[None, :]
        logits, cache = step(
            params, jnp.asarray(ids[None, off:off + CHUNK]), cache,
            jnp.asarray(valid))
        out.append(np.asarray(logits)[0, valid[0]])
    # As the batcher merges a chunked prompt: the padding's positions
    # are past the row's length.
    cache = cache._replace(length=jnp.asarray([n_prompt], jnp.int32))
    logits, cache = step(
        params, jnp.asarray(ids[None, -1:]), cache, jnp.asarray([[True]]))
    return np.concatenate(out + [np.asarray(logits)[0]])


def test_forward_takes_the_kernel_and_matches_the_walk(
        params, latent_prefill_on_tpu):
    cache = llama.KVCache.create(CFG, 1, 512)
    before = dict(A.dispatch_counts)
    got = prefill_then_decode(params, cache)
    # The chunk program's two layer scans trace their bodies once
    # each; the decode step (1 query) is not the kernel's.
    assert counted(before) == {"latent_prefill": 2, "xla_fallback": 0}
    before = dict(A.dispatch_counts)
    want = prefill_then_decode(params, cache, use_flash=False)
    assert counted(before) == {"latent_prefill": 0, "xla_fallback": 2}
    assert got.shape == (CHUNK + 150 + 1, CFG.vocab_size)
    assert float(np.abs(want).max()) > 0.5
    # The walk attends a chunk in the expanded form: the tolerance of
    # test_absorbed_and_expanded_are_one_attention.
    np.testing.assert_allclose(got, want, atol=2e-4)


KEEPS_THE_WALK = {
    "int8_plane": dict(kv_dtype="int8"),
    "fp8_plane": dict(kv_dtype="fp8"),
    "a_short_suffix": dict(s=M.ABSORBED_MAX_QUERIES),
    "a_decode_step": dict(s=1),
    "the_paged_arena": dict(paged=True),
    "no_cache": dict(cache=False),
}


@pytest.mark.parametrize("case", KEEPS_THE_WALK, ids=list(KEEPS_THE_WALK))
def test_forward_keeps_the_walk(params, latent_prefill_on_tpu, case):
    """Quantized and float8 planes (the benchmark's `fp8_kv` control
    reads the walk), 128 queries or fewer and a cache-free forward are
    not the kernel's kind: nothing is counted."""
    c = dict(kv_dtype="", s=CHUNK, cache=True, paged=False)
    c.update(KEEPS_THE_WALK[case])
    if c["paged"]:
        cache = llama.PagedKVCache.create(CFG, 1, 512, 32, 16)
        cache = cache._replace(table=jnp.arange(32, dtype=jnp.int32)[None])
    else:
        cache = llama.KVCache.create(CFG, 1, 512, c["kv_dtype"])
    assert isinstance(cache.k, QuantizedArray) == (c["kv_dtype"] == "int8")
    before = dict(A.dispatch_counts)
    logits, _ = jax.jit(lambda p, t, c: M.forward(p, CFG, t, c))(
        params, jnp.full((1, c["s"]), 5, jnp.int32),
        cache if c["cache"] else None)
    assert np.isfinite(np.asarray(logits)).all()
    assert counted(before) == NOT_ITS_KIND


@pytest.mark.parametrize(
    "queries,asked", [(M.ABSORBED_MAX_QUERIES, 0), (M.ABSORBED_MAX_QUERIES + 1, 2)],
    ids=["128_is_a_suffix", "129_is_a_chunk"])
def test_the_query_count_that_makes_a_chunk(params, monkeypatch, queries, asked):
    """`attention_block` asks `latent_prefill` for the kernel from 129
    queries a row on (once a layer scan), never for 128 or fewer; what
    it is told (here None: the CPU) decides the rest."""
    calls = []
    ask = A.latent_prefill
    monkeypatch.setattr(
        A, "latent_prefill", lambda *a, **kw: calls.append(a) or ask(*a, **kw))
    logits, _ = jax.jit(lambda p, t, c: M.forward(p, CFG, t, c))(
        params, jnp.full((1, queries), 5, jnp.int32),
        llama.KVCache.create(CFG, 1, 512))
    assert np.isfinite(np.asarray(logits)).all()
    assert len(calls) == asked


def test_off_the_tpu_forward_keeps_the_walk(params):
    before = dict(A.dispatch_counts)
    jax.jit(lambda p, t, c: M.forward(p, CFG, t, c))(
        params, jnp.full((1, CHUNK), 5, jnp.int32),
        llama.KVCache.create(CFG, 1, 512))
    assert counted(before) == NOT_ITS_KIND
