"""Attention ops: XLA-fused reference path, a Pallas flash-attention
TPU kernel for prefill, a Pallas paged-decode kernel and a Pallas
latent-prefill kernel.

Two implementations with one contract, a third for paged decode and a
fourth for the latent family's prefill chunks:

- `attention_xla` — einsum + masked softmax. XLA fuses this well and it
  is the correct choice for short sequences, decode steps (q_len == 1),
  and CPU tests.
- `flash_attention` — blockwise online-softmax Pallas kernel (the
  standard FlashAttention recurrence) that never materializes the
  [S, S] score matrix, keeping HBM traffic linear in sequence length.
  Grid: (batch, kv_heads, q_blocks); the kernel loops over k blocks with
  running max/denominator carried in float32. Per-batch `q_offset`
  (absolute position of q[0], for cached prefill) and `kv_len` (valid
  cache prefix) ride in SMEM, so the SERVING prefill path — where the
  KV cache supplies both — can use the kernel, not just the cache-free
  training/scoring forward. GQA is native: K/V keep their (fewer) KV
  heads and a grid cell takes the whole query group of one KV head,
  stacked on the row axis, so repeated K/V never hit HBM and a key
  block is read once for the group. The products take the operands in
  the dtype they arrive in. Causal masking skips fully masked k blocks
  and builds a mask only for the blocks it can cut; `kv_len` bounds
  the k loop per batch. Compiled by default;
  `interpret=True` (CPU tests) runs the same kernel body in the Pallas
  interpreter and must be asked for.
- `paged_decode_attention` — the decode tick's read of a paged K/V
  arena [L, N, P, KVH, D]: each row walks its own block table up to
  its own length, page blocks DMA'd out of HBM two deep, instead of a
  full-width [B, W*P] view being gathered every layer. `paged_decode`
  picks it from platform, storage dtype and query count, or returns
  None and the caller gathers the view for `attention`.
- `latent_prefill_attention` — a prefill chunk of the latent-attention
  family (models/mla_moe.py) over its contiguous latent plane
  [L, B, S_max, W]: one key a position shared by every head, its first
  columns the value, so a tile's (query, head) pairs are the rows of
  one score block against one key block DMA'd out of the plane in
  place; scores, running maximum and sum and the accumulator stay in
  VMEM. `latent_prefill` picks it from platform, storage dtype and
  widths, or returns None and the caller walks the plane in XLA. It
  shares no body with the two kernels above: K and V are one plane,
  the value a column slice of the key, one KV head for every query
  head.

`attention` picks per call: flash for long prefill on TPU (crossover
threshold FLASH_MIN_SEQ — an op-count estimate, not yet measured),
XLA otherwise. Every choice that involves the kernel is recorded
(`dispatch_counts`): a call that wanted the kernel and fell to XLA
because its shapes do not shard is logged and counted, never silent.
Shapes are
[batch, seq, heads, head_dim]; K/V may carry fewer (KV) heads — the
flash kernel reads them in place, and attention_xla contracts them
grouped for decode-shaped queries (repeating only for long ones).
"""

from __future__ import annotations

import collections
import functools
import logging
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

logger = logging.getLogger("ggrmcp.ops.attention")

NEG_INF = -1e30

# Decode-shaped GQA calls (sq at or below this) take the grouped
# einsum in attention_xla; longer queries repeat K/V (see its
# docstring). 8 covers fused decode ticks, the jump tick's forced-run
# windows, and small prefill chunks (configs with
# prefill_chunk <= 8 run their chunk steps grouped too — numerically
# identical either way).
GQA_GROUPED_MAX_SQ = 8


# ---------------------------------------------------------------------------
# XLA path
# ---------------------------------------------------------------------------


def attention_xla(
    q: jnp.ndarray,  # [B, Sq, H, D]
    k: jnp.ndarray,  # [B, Sk, H or KVH, D]
    v: jnp.ndarray,  # [B, Sk, H or KVH, D]
    causal: bool = True,
    q_offset: Optional[jnp.ndarray] = None,  # [B] absolute pos of q[0]
    kv_len: Optional[jnp.ndarray] = None,  # [B] valid kv length
    window: Optional[int] = None,  # sliding window (Mistral): each query
    # attends to at most the `window` most recent keys (incl. itself)
    k_positions: Optional[jnp.ndarray] = None,  # [B, Sk] absolute key
    # positions (ring-buffer caches); None = contiguous arange layout.
    # Slots with NEGATIVE positions are invalid (never written).
) -> jnp.ndarray:
    """Masked softmax attention; scores in float32 for stability.

    GQA (KVH < H): K/V may arrive with their KV heads. Short-query
    calls (decode ticks, the bandwidth-bound case) use a GROUPED einsum
    — queries reshaped to [B, Sq, KVH, G, D] contract directly against
    the un-repeated K/V, so the cache is read once instead of being
    materialized at H heads first (measured 2.3x on a 512-cap decode
    tick, CPU). Long-query calls repeat K/V: there the scores matmul
    dominates and XLA lowers the flat layout better (long prefill on
    TPU takes the flash kernel anyway, which reads shared heads in
    place natively)."""
    assert window is None or causal, "sliding window requires causal"
    assert k_positions is None or (causal and q_offset is not None), (
        "k_positions (ring layout) requires causal + q_offset"
    )
    b, sq = q.shape[0], q.shape[1]
    h, kvh = q.shape[2], k.shape[2]
    grouped = kvh != h and sq <= GQA_GROUPED_MAX_SQ
    if kvh != h and not grouped:
        k = jnp.repeat(k, h // kvh, axis=2)
        v = jnp.repeat(v, h // kvh, axis=2)
    scale = q.shape[-1] ** -0.5
    if grouped:
        g = h // kvh
        qg = q.reshape(b, sq, kvh, g, q.shape[-1])
        scores = jnp.einsum(
            "bqhgd,bkhd->bhgqk", qg, k,
            preferred_element_type=jnp.float32,
        ).reshape(b, h, sq, k.shape[1]) * scale
    else:
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
        ) * scale
    sk = k.shape[1]
    mask = None
    if causal:
        q_pos = jnp.arange(sq)[:, None]  # [Sq, 1]
        if q_offset is not None:
            q_pos = q_offset[:, None, None] + q_pos[None]  # [B, Sq, 1]
        if k_positions is not None:
            k_pos = k_positions[:, None, :]  # [B, 1, Sk]
            causal_mask = (q_pos >= k_pos) & (k_pos >= 0)
        else:
            k_pos = jnp.arange(sk)[None, :]  # [1, Sk]
            causal_mask = q_pos >= k_pos  # [Sq, Sk] or [B, Sq, Sk]
        if window is not None:
            causal_mask &= k_pos > q_pos - window
        mask = causal_mask if causal_mask.ndim == 3 else causal_mask[None]
    if kv_len is not None:
        if k_positions is not None:
            valid = k_positions[:, None, :] < kv_len[:, None, None]
        else:
            valid = (
                jnp.arange(sk)[None, None, :] < kv_len[:, None, None]
            )  # [B,1,Sk]
        mask = valid if mask is None else mask & valid
    if mask is not None:
        scores = jnp.where(mask[:, None, :, :], scores, NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1)
    if grouped:
        g = h // kvh
        wg = weights.astype(v.dtype).reshape(b, kvh, g, sq, sk)
        out = jnp.einsum(
            "bhgqk,bkhd->bqhgd", wg, v,
            preferred_element_type=jnp.float32,
        ).reshape(b, sq, h, q.shape[-1])
    else:
        out = jnp.einsum(
            "bhqk,bkhd->bqhd", weights.astype(v.dtype), v,
            preferred_element_type=jnp.float32,
        )
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas flash attention
# ---------------------------------------------------------------------------


# What `flash_attention` aims a block at where the caller names none:
# score rows a query block (a KV head's whole query group stacked, `reps
# x block_q`) and keys a key block; their product bounds the float32
# score block. Read off the chip at the served shapes
# (scripts/flash_form_bench.py; docs/perf_attention.md has the table).
_FLASH_ROWS = 512
_FLASH_BLOCK_K = 512


def _flash_blocks(sq: int, sk: int, reps: int) -> tuple[int, int]:
    """(queries a block, keys a block) from the shapes: the targets
    halved until they divide the sequence and the keys and the score
    block holds no more than `_FLASH_ROWS x _FLASH_BLOCK_K` elements,
    never under 128 (a shorter sequence is one block)."""
    block_q = max(128, _FLASH_ROWS // reps)
    while block_q > 128 and sq % block_q:
        block_q //= 2
    block_q = min(block_q, sq)
    block_k = _FLASH_BLOCK_K
    while block_k > 128 and (
        sk % block_k or reps * block_q * block_k > _FLASH_ROWS * _FLASH_BLOCK_K
    ):
        block_k //= 2
    return block_q, min(block_k, sk)


def _flash_kernel(
    q_off_ref,  # SMEM [B] int32 — absolute position of q[0] per batch
    kv_len_ref,  # SMEM [B] int32 — valid kv prefix per batch
    q_ref,  # [block_q, reps * D] — the query heads of one KV head
    k_ref,  # [Sk, D]
    v_ref,  # [Sk, D]
    o_ref,  # [block_q, reps * D]
    acc_ref,  # VMEM scratch [reps * block_q, D] float32
    *,
    block_k: int,
    sk: int,
    causal: bool,
    block_q: int,
    window: Optional[int] = None,
):
    """One (batch, kv head, q_block) cell: online-softmax over k blocks.
    The `reps` query heads that share the KV head are stacked on the row
    axis (score row r is head r // block_q at query r % block_q), so a
    K / V block is read once for the group. Both products take their
    operands in the dtype they arrive in and accumulate in float32;
    scores, running maximum, sum and accumulator are float32. Key blocks
    every query of the block sees whole take no mask."""
    b_idx = pl.program_id(0)
    limit = kv_len_ref[b_idx]  # keys at position >= limit are invalid
    first_pos = q_off_ref[b_idx] + pl.program_id(2) * block_q
    last_pos = first_pos + block_q - 1
    rows, d = acc_ref.shape
    reps = rows // block_q
    q = jnp.concatenate(
        [q_ref[:, r * d:(r + 1) * d] for r in range(reps)], axis=0)
    scale = d ** -0.5
    q_pos = first_pos + jnp.concatenate(
        [jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)] * reps, axis=0)

    # Number of k blocks that can contain a valid key for this q block:
    # bounded by the batch's kv_len, and under causality by the last
    # query's absolute position. Of them the first `whole` hold only
    # keys every query sees: under kv_len, at or below the FIRST query.
    kv_limit = jnp.minimum(limit, sk)
    whole = kv_limit
    if causal:
        kv_limit = jnp.minimum(kv_limit, last_pos + 1)
        whole = jnp.minimum(whole, first_pos + 1)
    # Sliding window: k blocks entirely below the FIRST query's window
    # hold no visible key for any row of this q block — skip them (the
    # work saved is what makes windowed prefill O(S·W) not O(S²)).
    # Blocks below `edge` may still be cut by the LAST query's window.
    start_iter = edge = 0
    if window is not None:
        start_iter = jnp.maximum(first_pos - window + 1, 0) // block_k
        edge = pl.cdiv(jnp.maximum(last_pos - window + 1, 0), block_k)
    num_iters = jnp.maximum(pl.cdiv(kv_limit, block_k), start_iter)
    edge = jnp.clip(edge, start_iter, num_iters)
    whole = jnp.clip(whole // block_k, edge, num_iters)

    def block(kb, carry, masked):
        m_prev, l_prev = carry
        k_start = pl.multiple_of(kb * block_k, block_k)
        k_blk = k_ref[pl.ds(k_start, block_k), :]
        v_blk = v_ref[pl.ds(k_start, block_k), :]
        scores = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [rows, bk]
        if masked:
            k_pos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            mask = k_pos < limit
            if causal:
                mask &= q_pos >= k_pos
                if window is not None:
                    mask &= k_pos > q_pos - window
            scores = jnp.where(mask, scores, NEG_INF)
        m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v_blk.dtype), v_blk, preferred_element_type=jnp.float32
        )
        return m_new, l_new

    # The accumulator in VMEM and not in the loop's carry: a sixth off
    # the kernel at the served chunk (docs/perf_attention.md).
    acc_ref[...] = jnp.zeros_like(acc_ref)
    carry = (
        jnp.full((rows, 1), NEG_INF, dtype=jnp.float32),
        jnp.zeros((rows, 1), dtype=jnp.float32),
    )
    cut = functools.partial(block, masked=True)
    if window is not None:
        carry = jax.lax.fori_loop(start_iter, edge, cut, carry)
    carry = jax.lax.fori_loop(
        edge, whole, functools.partial(block, masked=False), carry)
    m, l = jax.lax.fori_loop(whole, num_iters, cut, carry)
    # Fully masked rows have l == 0 when the loop never ran; emit
    # zeros. A row whose PROCESSED blocks are all masked (possible only
    # for out-of-window pad queries — serving rows always see their own
    # key) keeps m == NEG_INF with p == exp(0) == 1 accumulating
    # garbage; zero those rows explicitly rather than emit it.
    live = m > NEG_INF / 2
    out = jnp.where(
        live, acc_ref[...] / jnp.maximum(l, 1e-30), 0.0
    ).astype(o_ref.dtype)
    for r in range(reps):
        o_ref[:, r * d:(r + 1) * d] = out[r * block_q:(r + 1) * block_q]


def _flash_vmem_bytes(
    sk: int, d: int, rows: int, block_k: int, itemsize: int
) -> int:
    """VMEM the kernel needs, told to the compiler instead of leaving
    it to the 16 MiB scoped default: each grid cell holds one KV head's
    whole [Sk, D] K and V, double-buffered by the pipeline, so the need
    grows with the cache length (8 MiB at Sk 8192, D 128, bf16 — the
    default refuses Sk 16384). Plus the q/o blocks of `rows` (a query
    group's heads x block_q; double-buffered) and the loop's float32
    working set (accumulator, a key and a value block, four score
    blocks: scores, weights, mask and the weights cast); 4 MiB of
    headroom for Mosaic's own scratch."""
    kv = 2 * 2 * sk * d * itemsize
    qo = 2 * 2 * rows * d * itemsize
    work = 4 * (3 * rows * d + 2 * block_k * d + 4 * rows * block_k)
    return kv + qo + work + (4 << 20)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret", "window"),
)
def flash_attention(
    q: jnp.ndarray,  # [B, Sq, H, D]
    k: jnp.ndarray,  # [B, Sk, KVH, D] — KVH may divide H (GQA)
    v: jnp.ndarray,  # [B, Sk, KVH, D]
    causal: bool = True,
    q_offset: Optional[jnp.ndarray] = None,  # [B] absolute pos of q[0]
    kv_len: Optional[jnp.ndarray] = None,  # [B] valid kv prefix
    block_q: Optional[int] = None,  # None: from the shapes
    block_k: Optional[int] = None,
    interpret: bool = False,
    window: Optional[int] = None,  # sliding window (causal only)
) -> jnp.ndarray:
    """FlashAttention over [B, S, H, D]; S must be a multiple of the
    block sizes (pad upstream; padded keys are masked out via kv_len).
    K/V keep their KV heads — a grid cell takes the H // KVH query
    heads of one KV head together, so GQA costs no HBM repeat and a key
    block is read once for the group. The products run in the operands'
    dtype (bf16 in serving, float32 in the CPU tests) and accumulate in
    float32. Block sizes come from the shapes (`_flash_blocks`) unless
    the caller names them. Compiled for the TPU unless `interpret=True`
    (CPU tests) asks for the interpreter."""
    b, sq, h, d = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    assert h % kvh == 0, f"q heads {h} not a multiple of kv heads {kvh}"
    reps = h // kvh
    auto_q, auto_k = _flash_blocks(sq, sk, reps)
    block_q = min(block_q or auto_q, sq)
    block_k = min(block_k or auto_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, (
        f"seq lens ({sq},{sk}) must be multiples of blocks ({block_q},{block_k})"
    )

    if q_offset is None:
        q_offset = jnp.zeros((b,), jnp.int32)
    if kv_len is None:
        kv_len = jnp.full((b,), sk, jnp.int32)

    assert window is None or causal, "sliding window requires causal"
    kernel = functools.partial(
        _flash_kernel, block_k=block_k, sk=sk, causal=causal,
        block_q=block_q, window=window,
    )
    # KV heads major, a head's query group `reps * d` columns of a query
    # row: Mosaic wants the squeezed (blocked-to-1) dims major, and a
    # block's minor two dims (block_q, reps * d) and (sk, d) then meet
    # the (8, 128)-or-full tiling rule at any head width. Inside a
    # program XLA folds the transposes into the producers and the
    # consumer (PERF.md section 5, PR 46).
    operands = (
        q.reshape(b, sq, kvh, reps * d).transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
    )
    q_spec = pl.BlockSpec(
        (None, None, block_q, reps * d), lambda bi, gi, qb: (bi, gi, qb, 0))
    kv_spec = pl.BlockSpec(
        (None, None, sk, d), lambda bi, gi, qb: (bi, gi, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid=(b, kvh, sq // block_q),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # q_offset [B]
            pl.BlockSpec(memory_space=pltpu.SMEM),  # kv_len [B]
            q_spec, kv_spec, kv_spec,
        ],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((reps * block_q, d), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct(operands[0].shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_flash_vmem_bytes(
                sk, d, reps * block_q, block_k, k.dtype.itemsize
            ),
        ),
        interpret=interpret,
    )(q_offset.astype(jnp.int32), kv_len.astype(jnp.int32), *operands)
    return out.transpose(0, 2, 1, 3).reshape(b, sq, h, d)


# ---------------------------------------------------------------------------
# Multi-device flash: shard_map over batch/head axes
# ---------------------------------------------------------------------------


def _flash_shardable(mesh, batch: int, kv_heads: int) -> tuple[bool, str]:
    """ONE predicate for whether flash can run per shard on `mesh` for
    these shapes — shared by the dispatcher (recorded XLA fallback) and
    flash_attention_sharded (error), so they cannot diverge."""
    d_ax = mesh.shape.get("data", 1) * mesh.shape.get("fsdp", 1)
    t_ax = mesh.shape.get("tensor", 1)
    if batch % d_ax != 0:
        return False, f"batch {batch} not divisible by data axes {d_ax}"
    if kv_heads % t_ax != 0:
        return False, f"kv heads {kv_heads} not divisible by tensor axis {t_ax}"
    return True, ""


def flash_attention_sharded(
    q: jnp.ndarray,  # [B, Sq, H, D]
    k: jnp.ndarray,  # [B, Sk, KVH, D]
    v: jnp.ndarray,
    mesh,
    causal: bool = True,
    q_offset: Optional[jnp.ndarray] = None,
    kv_len: Optional[jnp.ndarray] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """`flash_attention` on a multi-device mesh: the kernel is a custom
    call GSPMD cannot partition, so shard manually — batch over
    `data`/`fsdp`, heads over `tensor` — and run the single-device
    kernel per shard. Attention is embarrassingly parallel over batch
    and heads, so no collectives are needed inside.

    Constraints (checked): the data axes divide B; `tensor` divides the
    KV head count (each shard keeps whole GQA groups). The sequence
    dims stay local — long-sequence sharding is ring/Ulysses territory
    (ops/ring_attention.py). The shard_map is manual over EVERY mesh
    axis: Mosaic refuses a kernel under any auto-partitioned axis, even
    one of size 1. Axes the specs do not name (sequence/expert/stage)
    see replicated operands and compute the same result."""
    from jax.sharding import PartitionSpec as P

    b = q.shape[0]
    ok, why = _flash_shardable(mesh, b, k.shape[2])
    if not ok:
        raise ValueError(why)

    if q_offset is None:
        q_offset = jnp.zeros((b,), jnp.int32)
    if kv_len is None:
        kv_len = jnp.full((b,), k.shape[1], jnp.int32)

    bspec = P(("data", "fsdp"), None, "tensor", None)
    sspec = P(("data", "fsdp"))

    def local(q, k, v, qo, kl):
        return flash_attention(
            q, k, v, causal=causal, q_offset=qo, kv_len=kl,
            block_q=block_q, block_k=block_k, interpret=interpret,
            window=window,
        )

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(bspec, bspec, bspec, sspec, sspec),
        out_specs=bspec,
        check_vma=False,
    )(q, k, v, q_offset.astype(jnp.int32), kv_len.astype(jnp.int32))


# ---------------------------------------------------------------------------
# Paged decode: walk each row's block table inside the kernel
# ---------------------------------------------------------------------------

# Query positions a step the paged-decode kernel takes: the decode tick
# (1) and the small static window of the jump tick.
# A prefill chunk's queries keep the gathered view.
PAGED_DECODE_MAX_SQ = GQA_GROUPED_MAX_SQ

# Pages a block of the walk holds at most (one DMA a page, K and V,
# two blocks in flight), and the score elements a block may have: a
# wider query window walks narrower blocks.
PAGED_DECODE_BLOCK_PAGES = 16
_PAGED_DECODE_BLOCK_SCORES = 64 * 1024


def _paged_block_pages(rows: int, page_cols: int, width: int) -> int:
    fit = _PAGED_DECODE_BLOCK_SCORES // (rows * page_cols)
    return max(1, min(PAGED_DECODE_BLOCK_PAGES, fit, width))


def _paged_decode_kernel(
    table_ref,  # SMEM [B, W] int32 — page ids; n_pages = unmapped
    len_ref,  # SMEM [B] int32 — keys a row holds, this step's included
    layer_ref,  # SMEM [1] int32
    q_ref,  # VMEM [B, S*H, D] — queries, (position, head) major
    bias_ref,  # VMEM [S*H, cols] f32 — 0 where a block's column is of
    # the row's KV group, NEG_INF elsewhere
    tok_ref,  # VMEM [1, cols] int32 — a column's token within a block
    qi_ref,  # VMEM [S*H, 1] int32 — a row's query position in the step
    k_hbm,  # HBM [L, N, P*KVH, D] — the whole arena, never copied
    v_hbm,
    o_ref,  # VMEM [B, S*H, D]
    k_buf,  # VMEM [2, block_pages, P*KVH, D]
    v_buf,
    sems,  # DMA [2 (k, v), 2 (slot)]
    state,  # SMEM [2] int32 — slot of the next block; whether the
    # previous row already started this row's first block
    *,
    sq: int,
    page: int,
    block_pages: int,
    window: Optional[int],
):
    """One row of the batch a grid step: online softmax over the row's
    own pages, `block_pages` at a time, the next block (or the next
    row's first) in flight while this one is computed. All of a row's
    heads are contracted against every (token, kv head) column of a
    block at once; columns of another KV group carry a bias of NEG_INF.
    Blocks every query of the row sees whole take no position mask."""
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    width = table_ref.shape[1]
    n_pages = k_hbm.shape[1]
    layer = layer_ref[0]
    rows, cols = bias_ref.shape
    block_tokens = block_pages * page

    @pl.when(b == 0)
    def _():
        # Pages a short last block does not fetch keep what the buffer
        # held; their weights are exactly 0, so what they hold has to
        # be finite. From here on it is arena pages.
        v_buf[...] = jnp.zeros_like(v_buf)
        state[0] = 0
        state[1] = 0

    def walk(row):  # -> (first block, end block, pages)
        kv_len = len_ref[row]
        # A freed slot keeps its length and has its table unmapped.
        if window is None:
            live = (kv_len > 0) & (table_ref[row, 0] < n_pages)
        pages = jnp.minimum((kv_len + page - 1) // page, width)
        if window is not None:
            # A live row's table may be unmapped behind its window
            # (serving/pages.py lets those pages go): the page of its
            # newest key says whether it is mapped at all.
            live = (kv_len > 0) & (
                table_ref[row, jnp.maximum(pages - 1, 0)] < n_pages)
        end = (pages + block_pages - 1) // block_pages
        first = 0
        if window is not None:
            first = jnp.maximum(kv_len - sq - window + 1, 0) // block_tokens
        return first, jnp.maximum(jnp.where(live, end, first), first), pages

    def copies(row, blk, slot, pages, wait=False):
        base = blk * block_pages

        def one(j):
            pg = jnp.minimum(table_ref[row, base + j], n_pages - 1)
            for kv, (hbm, buf) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                cp = pltpu.make_async_copy(
                    hbm.at[layer, pg], buf.at[slot, j], sems.at[kv, slot]
                )
                (cp.wait if wait else cp.start)()

        @pl.when(base + block_pages <= pages)
        def _():  # a whole block: no test a page
            for j in range(block_pages):
                one(j)

        @pl.when(base + block_pages > pages)
        def _():
            for j in range(block_pages):
                pl.when(base + j < pages)(functools.partial(one, j))

    first, end, pages = walk(b)
    slot0 = state[0]

    @pl.when((end > first) & (state[1] == 0))
    def _():
        copies(b, first, slot0, pages)

    nxt = jnp.minimum(b + 1, n_rows - 1)
    n_first, n_end, n_pages_row = walk(nxt)
    chain = (b + 1 < n_rows) & (n_end > n_first) & (end > first)

    q = q_ref[b]
    scale = q.shape[-1] ** -0.5
    q_pos = len_ref[b] - sq + qi_ref[...]  # [rows, 1]

    def block(i, carry, masked):
        m_prev, l_prev, acc_prev = carry
        slot = (slot0 + i - first) % 2

        @pl.when(i + 1 < end)
        def _():
            copies(b, i + 1, 1 - slot, pages)

        @pl.when((i + 1 == end) & chain)
        def _():
            copies(nxt, n_first, 1 - slot, n_pages_row)

        copies(b, i, slot, pages, wait=True)
        k_blk = k_buf[slot].reshape(cols, k_buf.shape[-1])
        v_blk = v_buf[slot].reshape(cols, v_buf.shape[-1])
        scores = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale + bias_ref[...]  # [rows, cols]
        if masked:
            k_pos = i * block_tokens + tok_ref[...]  # [1, cols]
            mask = k_pos <= q_pos
            if window is not None:
                mask &= k_pos > q_pos - window
            scores = jnp.where(mask, scores, NEG_INF)
        m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        if masked:  # a query may see no key of this block at all
            p = jnp.where(scores > NEG_INF / 2, p, 0.0)
        l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
        acc_new = acc_prev * alpha + jnp.dot(
            p.astype(v_blk.dtype), v_blk, preferred_element_type=jnp.float32
        )
        return m_new, l_new, acc_new

    # Blocks below the first query's position are whole for every
    # query of the row; with a window that can bind, none is.
    whole = first if window is not None else jnp.clip(
        (len_ref[b] - sq) // block_tokens, first, end
    )
    carry = (
        jnp.full((rows, 1), NEG_INF, jnp.float32),
        jnp.zeros((rows, 1), jnp.float32),
        jnp.zeros((rows, q.shape[-1]), jnp.float32),
    )
    carry = jax.lax.fori_loop(
        first, whole, functools.partial(block, masked=False), carry)
    _, l, acc = jax.lax.fori_loop(
        whole, end, functools.partial(block, masked=True), carry)
    # A row that walked nothing (freed slot, length 0) emits zeros.
    o_ref[b] = jnp.where(
        l > 0.0, acc / jnp.maximum(l, 1e-30), 0.0
    ).astype(o_ref.dtype)
    state[0] = (slot0 + end - first) % 2
    state[1] = chain.astype(jnp.int32)


def _paged_decode_vmem_bytes(
    batch: int, rows: int, d: int, block_pages: int, page_rows: int,
    itemsize: int,
) -> int:
    """VMEM the paged-decode kernel needs, told to the compiler: the K
    and V block buffers, two slots each; q and o for every row
    (double-buffered by the pipeline); the float32 scores, weights,
    bias and masks of one block and the accumulator; 4 MiB of headroom
    for Mosaic's own scratch. 3.9 MiB + headroom at mistral's widths."""
    cols = block_pages * page_rows
    bufs = 2 * 2 * block_pages * page_rows * d * itemsize
    qo = 2 * 2 * batch * rows * d * itemsize
    work = 4 * (6 * rows * cols + 2 * rows * d)
    return bufs + qo + work + (4 << 20)


@functools.partial(
    jax.jit, static_argnames=("window", "block_pages", "interpret")
)
def paged_decode_attention(
    q: jnp.ndarray,  # [B, S, H, D] — S <= PAGED_DECODE_MAX_SQ
    k_arena: jnp.ndarray,  # [L, N, P, KVH, D] — every layer's pages
    v_arena: jnp.ndarray,
    table: jnp.ndarray,  # [B, W] int32 page ids (N = unmapped)
    kv_len: jnp.ndarray,  # [B] keys a row holds, this step's included
    layer: jnp.ndarray,  # scalar layer index
    window: Optional[int] = None,
    block_pages: Optional[int] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Causal attention of the last S positions of every row against
    that row's own pages, read in place out of the whole arena: the
    block table, the lengths and the layer index are scalar-prefetched,
    the arenas stay in HBM, and each row's walk stops at its own
    `kv_len` (a row of length 0 or with an unmapped table walks
    nothing and returns zeros). Query i of a row sits at position
    kv_len - S + i. What `llama.paged_view` + `attention_xla` compute
    over the [B, W*P] view, without the view. Compiled for the TPU
    unless `interpret=True` (CPU tests) asks for the interpreter."""
    b, sq, h, d = q.shape
    n_layers, n_pages, page, kvh, _ = k_arena.shape
    width = table.shape[1]
    assert h % kvh == 0, f"q heads {h} not a multiple of kv heads {kvh}"
    if window is not None and window >= width * page:
        window = None  # cannot bind inside a row's W*P positions
    rows, page_rows = sq * h, page * kvh
    if block_pages is None:
        block_pages = _paged_block_pages(rows, page_rows, width)
    kernel = functools.partial(
        _paged_decode_kernel, sq=sq, page=page, block_pages=block_pages,
        window=window,
    )
    # What a row or a column of a block's scores stands for, as
    # constants of the program (integer division of whole score blocks
    # inside the kernel cost more than the walk): column c of a block
    # is (token c // KVH, kv head c % KVH), row r (query r // H, head
    # r % H).
    col, row = np.arange(block_pages * page_rows), np.arange(rows)
    same_group = (row % h // (h // kvh))[:, None] == (col % kvh)[None, :]
    bias = np.where(same_group, 0.0, NEG_INF).astype(np.float32)
    tok = (col // kvh).astype(np.int32)[None, :]
    qi = (row // h).astype(np.int32)[:, None]

    def whole(shape):  # in VMEM once for every row: fetched once
        return pl.BlockSpec(shape, lambda bi, *_: (0,) * len(shape))

    any_space = pl.BlockSpec(memory_space=pl.ANY)
    row_block = whole((b, rows, d))
    buf = pltpu.VMEM((2, block_pages, page_rows, d), k_arena.dtype)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[
                row_block, whole(bias.shape), whole(tok.shape),
                whole(qi.shape), any_space, any_space,
            ],
            out_specs=row_block,
            scratch_shapes=[
                buf, buf, pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((2,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, rows, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # Rows in order on one core: a row starts the next row's
            # first block, and the buffers' slot carries over.
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_paged_decode_vmem_bytes(
                b, rows, d, block_pages, page_rows, k_arena.dtype.itemsize
            ),
        ),
        name="paged_decode_attention",
        interpret=interpret,
    )(
        table.astype(jnp.int32), kv_len.astype(jnp.int32),
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        q.reshape(b, rows, d), bias, tok, qi,
        k_arena.reshape(n_layers, n_pages, page_rows, d),
        v_arena.reshape(n_layers, n_pages, page_rows, d),
    )
    return out.reshape(b, sq, h, d)


def paged_decode_attention_sharded(
    q: jnp.ndarray,  # [B, S, H, D]
    k_arena: jnp.ndarray,  # [L, N, P, KVH, D]
    v_arena: jnp.ndarray,
    table: jnp.ndarray,
    kv_len: jnp.ndarray,
    layer: jnp.ndarray,
    mesh,
    window: Optional[int] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """`paged_decode_attention` on a multi-device mesh, one kernel per
    shard: heads over `tensor` as `llama.paged_cache_specs` shards the
    arena (a shard's query heads are exactly the groups of its KV
    heads), tables and lengths replicated; pages are shared by every
    slot, so nothing shards over a batch axis. Manual over EVERY mesh
    axis, as `flash_attention_sharded` is; `tensor` must divide the KV
    head count. VMEM a shard: `_paged_decode_vmem_bytes` at its local
    head counts."""
    from jax.sharding import PartitionSpec as P

    t_ax = mesh.shape.get("tensor", 1)
    if k_arena.shape[3] % t_ax != 0:
        raise ValueError(
            f"kv heads {k_arena.shape[3]} not divisible by tensor axis {t_ax}"
        )
    qspec = P(None, None, "tensor", None)
    aspec = P(None, None, None, "tensor", None)

    def local(q, ka, va, tb, kl, ly):
        return paged_decode_attention(
            q, ka, va, tb, kl, ly, window=window, interpret=interpret
        )

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(qspec, aspec, aspec, P(), P(), P()),
        out_specs=qspec,
        check_vma=False,
    )(q, k_arena, v_arena, table, kv_len, layer)


# ---------------------------------------------------------------------------
# Latent prefill: a chunk's queries over one shared latent plane
# ---------------------------------------------------------------------------

# Score rows (queries x heads) a tile of the latent-prefill kernel
# holds, and keys a block of its walk: a [512, 512] float32 score block
# (1 MiB) and a [512, V] float32 accumulator stay in VMEM, and every
# 128 x 128 tile of a key block meets 512 query rows on the MXU.
_LATENT_PREFILL_ROWS = 512
_LATENT_PREFILL_BLOCK_K = 512


def _latent_prefill_blocks(s: int, h: int, s_keys: int) -> tuple[int, int]:
    """(queries a tile, keys a block): the largest powers of two under
    the targets that divide the chunk and the plane."""
    block_q = max(1, _LATENT_PREFILL_ROWS // h)
    while s % block_q:
        block_q //= 2
    block_k = _LATENT_PREFILL_BLOCK_K
    while s_keys % block_k:
        block_k //= 2
    return block_q, block_k


def _latent_prefill_kernel(
    layer_ref,  # SMEM [1] int32
    q_off_ref,  # SMEM [B] int32 — position of a row's first query
    len_ref,  # SMEM [B] int32 — keys a row may see
    last_ref,  # SMEM [B] int32 — position of a row's last real query
    # (-1: the row has none)
    q_ref,  # VMEM [block_q*H, W] — one tile's queries, (query, head) major
    qi_ref,  # VMEM [block_q*H, 1] int32 — a score row's query in the tile
    plane_hbm,  # HBM [L, B, S_max, W] — every layer's latents, never copied
    *rest,  # with a selection: sel_hbm, HBM [B, S/block_q, block_q, S_max]
    # int32, then as without one: o_ref, k_buf, sems, acc_ref; and last
    # sel_buf, VMEM [2, block_q, block_k] int32, and sel_sems, DMA [2]
    block_q: int,
    block_k: int,
    scale: float,
    selected: bool,
):
    """One (row, query tile) a grid step: online softmax over the row's
    latents in the plane, `block_k` keys at a time, the next block in
    flight while this one is computed. Every head of every query of the
    tile is a score row against the one shared key block; the block's
    first V columns are its values. The walk ends at the last key a
    REAL query of the tile may see: a tile past the row's last real
    query walks nothing and emits zeros. Key blocks every query of the
    tile sees whole take no causal mask. Where the queries bring a
    selection (`selected`, another program), a block's `[block_q,
    block_k]` slab of it travels beside the block's keys and masks
    every block, a query's slab row over its H score rows."""
    if selected:
        sel_hbm, o_ref, k_buf, sems, acc_ref, sel_buf, sel_sems = rest
    else:
        o_ref, k_buf, sems, acc_ref = rest
    b, t = pl.program_id(0), pl.program_id(1)
    layer = layer_ref[0]
    kv_len = len_ref[b]
    v_width = o_ref.shape[-1]
    first_pos = q_off_ref[b] + t * block_q  # the tile's first query
    last_pos = jnp.minimum(first_pos + block_q - 1, last_ref[b])
    # Keys [0, seen) are walked: none where the tile has no real query.
    seen = jnp.where(
        first_pos <= last_pos, jnp.minimum(last_pos + 1, kv_len), 0)
    n_plane = plane_hbm.shape[2] // block_k
    end = jnp.clip((seen + block_k - 1) // block_k, 0, n_plane)
    whole = jnp.clip(jnp.minimum(first_pos + 1, kv_len) // block_k, 0, end)

    def copies(i, slot):
        """Key block i into `slot`, and its slab of the selection."""
        pair = [pltpu.make_async_copy(
            plane_hbm.at[layer, b, pl.ds(i * block_k, block_k)],
            k_buf.at[slot], sems.at[slot],
        )]
        if selected:
            pair.append(pltpu.make_async_copy(
                sel_hbm.at[b, t, :, pl.ds(i * block_k, block_k)],
                sel_buf.at[slot], sel_sems.at[slot],
            ))
        return pair

    @pl.when(end > 0)
    def _():
        for copy in copies(0, 0):
            copy.start()

    q = q_ref[...]
    q_pos = first_pos + qi_ref[...]  # [rows, 1]
    acc_ref[...] = jnp.zeros_like(acc_ref)
    heads = q.shape[0] // block_q

    def block(i, carry, causal):
        m_prev, l_prev = carry
        slot = i % 2

        @pl.when(i + 1 < end)
        def _():
            for copy in copies(i + 1, 1 - slot):
                copy.start()

        for copy in copies(i, slot):
            copy.wait()
        k_blk = k_buf[slot]  # [block_k, W]
        scores = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [rows, block_k]
        mask = None
        if causal:
            k_pos = i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            mask = (k_pos <= q_pos) & (k_pos < kv_len)
        if selected:
            sel = sel_buf[slot] != 0  # [block_q, block_k]
            sel = jnp.concatenate([
                jnp.broadcast_to(sel[j:j + 1], (heads, block_k))
                for j in range(block_q)])
            mask = sel if mask is None else mask & sel
        if mask is not None:
            scores = jnp.where(mask, scores, NEG_INF)
        m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        if mask is not None:  # a query may see no key of this block at all
            p = jnp.where(mask, p, 0.0)
        l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(k_blk.dtype), k_blk[:, :v_width],
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new

    rows = q.shape[0]
    carry = (
        jnp.full((rows, 1), NEG_INF, jnp.float32),
        jnp.zeros((rows, 1), jnp.float32),
    )
    carry = jax.lax.fori_loop(
        0, whole, functools.partial(block, causal=False), carry)
    _, l = jax.lax.fori_loop(
        whole, end, functools.partial(block, causal=True), carry)
    o_ref[...] = jnp.where(
        l > 0.0, acc_ref[...] / jnp.maximum(l, 1e-30), 0.0
    ).astype(o_ref.dtype)


def _latent_prefill_vmem_bytes(
    rows: int, w: int, v: int, block_k: int, itemsize: int,
    selected_q: int = 0,
) -> int:
    """VMEM the latent-prefill kernel needs, told to the compiler: the
    key block buffer, two slots; a tile's queries and its output
    (double-buffered by the pipeline); the float32 accumulator; the
    float32 scores, weights and mask of one block; 4 MiB of headroom
    for Mosaic's own scratch. 8.5 MiB + headroom at the published
    widths (512 rows, 640-wide keys, 512-wide values, bf16). With a
    selection (`selected_q` queries a tile): its slab buffer, two
    slots, and the slab spread over the score rows, 1 MiB more."""
    bufs = 2 * block_k * w * itemsize
    qo = 2 * rows * (w + v) * itemsize
    work = 4 * (rows * v + 4 * rows * block_k)
    if selected_q:
        work += 4 * (2 * max(selected_q, 8) * block_k + rows * block_k)
    return bufs + qo + work + (4 << 20)


@functools.partial(
    jax.jit,
    static_argnames=("value_width", "scale", "block_q", "block_k", "interpret"),
)
def latent_prefill_attention(
    q: jnp.ndarray,  # [B, S, H, W] — queries folded into the latent space
    plane: jnp.ndarray,  # [L, B, S_max, W] — every layer's latents
    layer: jnp.ndarray,  # scalar layer index
    q_offset: jnp.ndarray,  # [B] position of a row's first query
    kv_len: jnp.ndarray,  # [B] keys a row may see
    last_q: jnp.ndarray,  # [B] position of a row's last REAL query (-1:
    # none): queries past it are padding, and their output is undefined
    # (finite)
    allowed: Optional[jnp.ndarray] = None,  # [B, S, S_max] bool — each
    # query's selection (`ops.indexer.selection_mask`); None: it sees all
    *,
    value_width: int,  # a key's first `value_width` columns are its value
    scale: float,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Causal attention of a prefill chunk over the latents its row
    holds in a contiguous plane, read in place: one key per position
    shared by all H heads (a plane row is the key, its first
    `value_width` columns the value), the score block, the running
    maximum and sum and the accumulator in VMEM (float32), the weights
    cast to the plane's dtype before the value matmul. Query i of row b
    sits at position q_offset[b] + i and sees keys [0, min(position + 1,
    kv_len[b])), of them those `allowed` names where it is given. What
    `mla_moe.latent_attention` computes in its absorbed form, for the
    real queries, without a score block in HBM. Without a selection the
    program is the one it was before there was one: the operand, its
    buffer and its mask exist only in the program that is given it.
    Returns [B, S, H, value_width]. Compiled for the TPU unless
    `interpret=True` (CPU tests) asks for the interpreter."""
    b, s, h, w = q.shape
    s_keys = plane.shape[2]
    assert plane.shape[1] == b and plane.shape[3] == w, (q.shape, plane.shape)
    auto_q, auto_k = _latent_prefill_blocks(s, h, s_keys)
    block_q, block_k = block_q or auto_q, block_k or auto_k
    assert s % block_q == 0 and s_keys % block_k == 0, (
        f"chunk {s} / plane {s_keys} not multiples of blocks "
        f"({block_q},{block_k})"
    )
    rows = block_q * h
    selected = allowed is not None
    kernel = functools.partial(
        _latent_prefill_kernel, block_q=block_q, block_k=block_k, scale=scale,
        selected=selected,
    )
    # Score row r of a tile is (query r // H, head r % H): a constant
    # of the program, as in the paged-decode kernel.
    qi = (np.arange(rows) // h).astype(np.int32)[:, None]
    operands = [q.reshape(b, s * h, w), qi, plane]
    in_specs = [
        pl.BlockSpec((None, rows, w), lambda bi, ti, *_: (bi, ti, 0)),
        pl.BlockSpec((rows, 1), lambda *_: (0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    scratch_shapes = [
        pltpu.VMEM((2, block_k, w), plane.dtype),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.VMEM((rows, value_width), jnp.float32),
    ]
    if selected:
        # A tile's queries on an axis of their own, so that a slab is
        # whole in the sublanes whatever `block_q` is (4 at 128 heads);
        # 32-bit, which Mosaic tiles at any such count.
        assert allowed.shape == (b, s, s_keys), (allowed.shape, q.shape)
        operands.append(
            allowed.astype(jnp.int32).reshape(b, s // block_q, block_q, s_keys))
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        scratch_shapes += [
            pltpu.VMEM((2, block_q, block_k), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
        ]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, s // block_q),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (None, rows, value_width), lambda bi, ti, *_: (bi, ti, 0)),
            scratch_shapes=scratch_shapes,
        ),
        out_shape=jax.ShapeDtypeStruct((b, s * h, value_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_latent_prefill_vmem_bytes(
                rows, w, value_width, block_k, plane.dtype.itemsize,
                block_q if selected else 0,
            ),
        ),
        name="latent_attention_prefill",
        interpret=interpret,
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        q_offset.astype(jnp.int32), kv_len.astype(jnp.int32),
        last_q.astype(jnp.int32), *operands,
    )
    return out.reshape(b, s, h, value_width)


def latent_prefill_attention_sharded(
    q: jnp.ndarray,  # [B, S, H, W]
    plane: jnp.ndarray,  # [L, B, S_max, W]
    layer: jnp.ndarray,
    q_offset: jnp.ndarray,
    kv_len: jnp.ndarray,
    last_q: jnp.ndarray,
    mesh,
    allowed: Optional[jnp.ndarray] = None,  # [B, S, S_max] bool
    *,
    value_width: int,
    scale: float,
    interpret: bool = False,
) -> jnp.ndarray:
    """`latent_prefill_attention` on a multi-device mesh, one kernel
    per shard: rows over `data`/`fsdp` as `mla_moe.cache_specs` shards
    the plane, heads over `tensor` (every shard reads the whole latent:
    it is shared by all heads, and so is a query's selection, which
    goes with the rows). Manual over EVERY mesh axis, as
    `flash_attention_sharded` is; the data axes must divide the rows
    and `tensor` the heads."""
    from jax.sharding import PartitionSpec as P

    ok, why = _flash_shardable(mesh, q.shape[0], q.shape[2])
    if not ok:
        raise ValueError(why)
    qspec = P(("data", "fsdp"), None, "tensor", None)
    rspec = P(("data", "fsdp"))
    selection = () if allowed is None else (allowed,)

    def local(q, pln, ly, qo, kl, lq, *sel):
        return latent_prefill_attention(
            q, pln, ly, qo, kl, lq, *sel, value_width=value_width,
            scale=scale, interpret=interpret,
        )

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            qspec, P(None, ("data", "fsdp"), None, None), P(),
            rspec, rspec, rspec, *(rspec for _ in selection),
        ),
        out_specs=qspec,
        check_vma=False,
    )(q, plane, layer, q_offset, kv_len, last_q, *selection)


# ---------------------------------------------------------------------------
# Dispatcher
# ---------------------------------------------------------------------------

# Prefill sequences at least this long go through the Pallas kernel on
# TPU; below it the fused XLA path wins (kernel launch + padding costs).
# PROVENANCE: op-count estimate, not measured on the chip
# (docs/perf_attention.md).
FLASH_MIN_SEQ = 256

# Which implementation each kernel-eligible call took, counted at TRACE
# time (the dispatcher is Python that runs once a traced program, so
# this counts programs, not executions). "flash" / "flash_sharded": the
# Pallas prefill kernel is in the program; "paged_decode": the
# paged-decode kernel is (one a forward call a tick program holds: a
# layer scan traces its body once); "latent_prefill": the latent
# family's prefill kernel is (two a forward call, its dense and its
# expert layers being a scan each); "grouped_experts": the routed
# experts' grouped SwiGLU kernel is (ops/experts.py, one a forward
# call); "xla_fallback": the call wanted a kernel and its shapes did
# not shard over the mesh, or the engine runs none on its mesh. The
# sidecar exports the sums below beside mesh_spec_downgrades, and by
# them chip_smoke.py knows that a prefill took the compiled kernel.
# The latent family also counts, once a traced program, which
# sparse-attention path a layer took (models/mla_moe.py: "sparse_decode"
# the gather by token index, "sparse_chunk" a selection a query);
# neither is in the sums: a sparse chunk that runs the kernel counts
# under "latent_prefill" too, one that walks under "xla_fallback".
dispatch_counts: collections.Counter = collections.Counter()


def dispatch_stats() -> dict:
    """ServingStats view of `dispatch_counts`."""
    return {
        "attn_kernel_programs": (
            dispatch_counts["flash"] + dispatch_counts["flash_sharded"]
            + dispatch_counts["paged_decode"]
            + dispatch_counts["latent_prefill"]
            + dispatch_counts["grouped_experts"]
        ),
        "attn_kernel_fallbacks": dispatch_counts["xla_fallback"],
    }


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def attention(
    q: jnp.ndarray,  # [B, Sq, H, D]
    k: jnp.ndarray,  # [B, Sk, KVH, D] — KVH == H or divides it (GQA)
    v: jnp.ndarray,  # [B, Sk, KVH, D]
    causal: bool = True,
    q_offset: Optional[jnp.ndarray] = None,
    kv_len: Optional[jnp.ndarray] = None,
    use_flash: Optional[bool] = None,
    flash_mesh=None,
    window: Optional[int] = None,
    k_positions: Optional[jnp.ndarray] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Pick the right implementation for the shapes at hand. GQA:
    the flash kernel reads the shared KV heads in place; attention_xla
    contracts grouped for decode-shaped queries and repeats K/V only
    for long ones (see its docstring).

    `use_flash=None` means auto: flash for long prefill on a TPU.
    On multi-device meshes the kernel is a custom call GSPMD cannot
    partition: engines either pass False (XLA path) or supply
    `flash_mesh` and the kernel runs per shard via shard_map —
    batch over data/fsdp, heads over tensor (flash_attention_sharded).
    Per-call shapes that do not shard fall to XLA, logged and counted
    (`dispatch_counts`).

    `window` (sliding-window / Mistral-style attention) is supported by
    both paths; the kernel additionally SKIPS k blocks below the
    window, making long windowed prefill O(S·W).

    `k_positions` (ring-buffer cache layout) always takes the XLA
    path. `interpret` reaches the kernel (CPU tests only)."""
    sq, sk = q.shape[1], k.shape[1]
    if k_positions is not None:
        use_flash = False
    if use_flash is None:
        use_flash = (
            _on_tpu()
            and sq >= FLASH_MIN_SEQ
            and sq % 128 == 0
            and sk % 128 == 0
        )
    if use_flash and flash_mesh is not None:
        ok, why = _flash_shardable(flash_mesh, q.shape[0], k.shape[2])
        if ok:
            dispatch_counts["flash_sharded"] += 1
            logger.info(
                "attention: Pallas kernel per shard for q%s k%s window=%s",
                tuple(q.shape), tuple(k.shape), window,
            )
            return flash_attention_sharded(
                q, k, v, flash_mesh, causal=causal,
                q_offset=q_offset, kv_len=kv_len, window=window,
                interpret=interpret,
            )
        dispatch_counts["xla_fallback"] += 1
        logger.warning(
            "attention: q%s k%s wanted the Pallas kernel but does not "
            "shard over the mesh (%s) — this program takes the XLA path "
            "(watch gauge attn_kernel_fallbacks)",
            tuple(q.shape), tuple(k.shape), why,
        )
        use_flash = False
    if use_flash:
        dispatch_counts["flash"] += 1
        logger.info(
            "attention: Pallas kernel for q%s k%s window=%s",
            tuple(q.shape), tuple(k.shape), window,
        )
        return flash_attention(
            q, k, v, causal=causal, q_offset=q_offset, kv_len=kv_len,
            window=window, interpret=interpret,
        )
    # GQA is attention_xla's problem now: it repeats K/V for long
    # queries and contracts grouped for decode-shaped ones.
    return attention_xla(
        q, k, v, causal=causal, q_offset=q_offset, kv_len=kv_len,
        window=window, k_positions=k_positions,
    )


def paged_decode(
    q: jnp.ndarray,  # [B, S, H, D]
    k_arena: jnp.ndarray,  # [L, N, P, KVH, D]
    v_arena: jnp.ndarray,
    table: jnp.ndarray,  # [B, W]
    kv_len: jnp.ndarray,  # [B] — the step's keys included
    layer: jnp.ndarray,
    window: Optional[int] = None,
    use_flash: Optional[bool] = None,
    flash_mesh=None,
) -> Optional[jnp.ndarray]:
    """The paged-decode kernel where the step is its kind, else None
    and the caller gathers its view (`llama.paged_view`) for
    `attention`. Chosen from what the call can see, no option: a TPU;
    an arena stored in the queries' own dtype (quantized arenas never
    get here; a float8 arena is turned away here); at most
    PAGED_DECODE_MAX_SQ query positions (a prefill chunk keeps the
    view); a page Mosaic can slice out of the arena (a head of whole
    128-lane rows, 8 or more (token, kv head) rows a page). On a
    multi-device mesh the kernel runs per shard (`flash_mesh`, as for
    the prefill kernel). A step of that kind on a mesh that cannot
    run the kernel — the engine turned kernels off because the mesh
    does not shard them (`use_flash=False`), or `tensor` does not
    divide the KV heads — takes the view too, logged and counted as a
    fallback."""
    (_, sq, _, d), (_, _, page, kvh, _) = q.shape, k_arena.shape
    t_ax = 1 if flash_mesh is None else flash_mesh.shape.get("tensor", 1)
    if (
        not _on_tpu()
        or k_arena.dtype != q.dtype
        or sq > PAGED_DECODE_MAX_SQ
        or d % 128 != 0
        or (page * kvh) % (8 * t_ax) != 0
    ):
        return None
    why = ""
    if use_flash is False:
        why = "the engine runs no attention kernel on this mesh"
    elif kvh % t_ax != 0:
        why = f"kv heads {kvh} not divisible by tensor axis {t_ax}"
    if why:
        dispatch_counts["xla_fallback"] += 1
        logger.warning(
            "attention: paged decode q%s arena%s wanted the Pallas kernel "
            "(%s) — this program gathers the full-width view instead "
            "(watch gauge attn_kernel_fallbacks)",
            tuple(q.shape), tuple(k_arena.shape), why,
        )
        return None
    dispatch_counts["paged_decode"] += 1
    logger.info(
        "attention: paged-decode Pallas kernel%s for q%s arena%s table%s "
        "window=%s",
        " per shard" if flash_mesh is not None else "",
        tuple(q.shape), tuple(k_arena.shape), tuple(table.shape), window,
    )
    if flash_mesh is not None:
        return paged_decode_attention_sharded(
            q, k_arena, v_arena, table, kv_len, layer, flash_mesh,
            window=window,
        )
    return paged_decode_attention(
        q, k_arena, v_arena, table, kv_len, layer, window=window
    )


def latent_prefill(
    q: jnp.ndarray,  # [B, S, H, W] — queries folded into the latent space
    plane,  # [L, B, S_max, W] latents (an array in some dtype)
    layer: jnp.ndarray,
    q_offset: jnp.ndarray,  # [B]
    kv_len: jnp.ndarray,  # [B]
    last_q: jnp.ndarray,  # [B] position of a row's last real query
    allowed: Optional[jnp.ndarray] = None,  # [B, S, S_max] bool: each
    # query's selection, where the model has an indexer
    *,
    value_width: int,
    scale: float,
    use_flash: Optional[bool] = None,
    flash_mesh=None,
) -> Optional[jnp.ndarray]:
    """The latent-prefill kernel where the call is its kind, else None
    and the caller walks the plane in XLA (`mla_moe.latent_attention`).
    Chosen from what the call can see, no option: a TPU; a plane stored
    in the queries' own dtype (a float8 plane is turned away here, a
    quantized one never gets here); keys and values of whole 128-lane
    rows; a chunk that tiles into whole sublane groups of score rows;
    with a selection, key blocks of whole 128-lane slabs of it. A call
    with a selection and one without are the same kind and two
    programs (`latent_prefill_attention`).
    Which calls are chunks (by query count) is the caller's to say. On
    a multi-device mesh the kernel runs per shard (`flash_mesh`, as for
    the prefill kernel). A call of that kind on a mesh that cannot run
    the kernel — the engine turned kernels off (`use_flash=False`), or
    the mesh divides neither the rows nor the heads — walks in XLA too,
    logged and counted as a fallback."""
    b, s, h, w = q.shape
    t_ax = 1 if flash_mesh is None else flash_mesh.shape.get("tensor", 1)
    h_shard = max(1, h // t_ax)
    block_q, block_k = _latent_prefill_blocks(s, h_shard, plane.shape[2])
    if (
        not _on_tpu()
        or plane.dtype != q.dtype
        or w % 128 != 0
        or value_width % 128 != 0
        or (block_q * h_shard) % 16 != 0
        or (allowed is not None and block_k % 128 != 0)
    ):
        return None
    why = ""
    if use_flash is False:
        why = "the engine runs no attention kernel on this mesh"
    elif flash_mesh is not None:
        _, why = _flash_shardable(flash_mesh, b, h)
    if why:
        dispatch_counts["xla_fallback"] += 1
        logger.warning(
            "attention: latent prefill q%s plane%s wanted the Pallas kernel "
            "(%s) — this program walks the plane in XLA instead (watch "
            "gauge attn_kernel_fallbacks)",
            tuple(q.shape), tuple(plane.shape), why,
        )
        return None
    dispatch_counts["latent_prefill"] += 1
    logger.info(
        "attention: latent-prefill Pallas kernel%s for q%s plane%s%s",
        " per shard" if flash_mesh is not None else "",
        tuple(q.shape), tuple(plane.shape),
        "" if allowed is None else ", a selection a query",
    )
    if flash_mesh is not None:
        return latent_prefill_attention_sharded(
            q, plane, layer, q_offset, kv_len, last_q, flash_mesh, allowed,
            value_width=value_width, scale=scale,
        )
    return latent_prefill_attention(
        q, plane, layer, q_offset, kv_len, last_q, allowed,
        value_width=value_width, scale=scale,
    )
