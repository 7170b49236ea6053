"""Attention ops: XLA-fused reference path and a Pallas flash-attention
TPU kernel.

Two implementations with one contract:

- `attention_xla` — einsum + masked softmax. XLA fuses this well and it
  is the correct choice for short sequences, decode steps (q_len == 1),
  and CPU tests.
- `flash_attention` — blockwise online-softmax Pallas kernel (the
  standard FlashAttention recurrence) that never materializes the
  [S, S] score matrix, keeping HBM traffic linear in sequence length.
  Grid: (batch, q_heads, q_blocks); the kernel loops over k blocks with
  running max/denominator carried in registers. Per-batch `q_offset`
  (absolute position of q[0], for cached prefill) and `kv_len` (valid
  cache prefix) ride in SMEM, so the SERVING prefill path — where the
  KV cache supplies both — can use the kernel, not just the cache-free
  training/scoring forward. GQA is native: K/V keep their (fewer) KV
  heads and the grid's head index maps onto the shared KV head, so
  repeated K/V never hit HBM. Causal masking skips fully masked
  k blocks; `kv_len` bounds the k loop per batch. Compiled by default;
  `interpret=True` (CPU tests) runs the same kernel body in the Pallas
  interpreter and must be asked for.

`attention` picks per call: flash for long prefill on TPU (crossover
threshold FLASH_MIN_SEQ — an op-count estimate, not yet measured;
scripts/bench_attention.py measures it), XLA otherwise. Every choice
that involves the kernel is recorded (`dispatch_counts`): a call that
wanted the kernel and fell to XLA because its shapes do not shard is
logged and counted, never silent. Shapes are
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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

logger = logging.getLogger("ggrmcp.ops.attention")

NEG_INF = -1e30

# Decode-shaped GQA calls (sq at or below this) take the grouped
# einsum in attention_xla; longer queries repeat K/V (see its
# docstring). 8 covers fused decode ticks, speculative gamma-step
# verification windows, and small prefill chunks (configs with
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


def _flash_kernel(
    q_off_ref,  # SMEM [B] int32 — absolute position of q[0] per batch
    kv_len_ref,  # SMEM [B] int32 — valid kv prefix per batch
    q_ref,  # [block_q, D]
    k_ref,  # [Sk, D]
    v_ref,  # [Sk, D]
    o_ref,  # [block_q, D]
    *,
    block_k: int,
    sk: int,
    causal: bool,
    block_q: int,
    window: Optional[int] = None,
):
    """One (batch, head, q_block) cell: online-softmax over k blocks."""
    b_idx = pl.program_id(0)
    q_start = pl.program_id(2) * block_q
    q_off = q_off_ref[b_idx]
    limit = kv_len_ref[b_idx]  # keys at position >= limit are invalid

    q = q_ref[:].astype(jnp.float32)  # [bq, D]
    scale = q.shape[-1] ** -0.5
    q = q * scale

    m0 = jnp.full((block_q, 1), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((block_q, 1), dtype=jnp.float32)
    acc0 = jnp.zeros_like(q)

    # Number of k blocks that can contain a valid key for this q block:
    # bounded by the batch's kv_len, and under causality by the last
    # query's absolute position.
    kv_limit = limit
    if causal:
        kv_limit = jnp.minimum(kv_limit, q_off + q_start + block_q)
    kv_limit = jnp.minimum(kv_limit, sk)
    num_iters = (kv_limit + block_k - 1) // block_k
    # Sliding window: k blocks entirely below the FIRST query's window
    # hold no visible key for any row of this q block — skip them (the
    # work saved is what makes windowed prefill O(S·W) not O(S²)).
    start_iter = 0
    if window is not None:
        win_lo = jnp.maximum(q_off + q_start - window + 1, 0)
        start_iter = win_lo // block_k

    def body(kb, carry):
        m_prev, l_prev, acc_prev = carry
        k_start = pl.multiple_of(kb * block_k, block_k)
        k_blk = k_ref[pl.ds(k_start, block_k), :].astype(jnp.float32)
        v_blk = v_ref[pl.ds(k_start, block_k), :].astype(jnp.float32)
        scores = jnp.dot(
            q, k_blk.T, preferred_element_type=jnp.float32
        )  # [bq, bk]
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        mask = k_pos < limit
        if causal:
            q_pos = q_off + q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            mask &= q_pos >= k_pos
            if window is not None:
                mask &= k_pos > q_pos - window
        scores = jnp.where(mask, scores, NEG_INF)
        m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
        acc_new = acc_prev * alpha + jnp.dot(
            p, v_blk, preferred_element_type=jnp.float32
        )
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(start_iter, num_iters, body, (m0, l0, acc0))
    # Fully masked rows have l == 0 when the loop never ran; emit
    # zeros. A row whose PROCESSED blocks are all masked (possible only
    # for out-of-window pad queries — serving rows always see their own
    # key) keeps m == NEG_INF with p == exp(0) == 1 accumulating
    # garbage; zero those rows explicitly rather than emit it.
    live = m > NEG_INF / 2
    o_ref[:] = jnp.where(
        live, acc / jnp.maximum(l, 1e-30), 0.0
    ).astype(o_ref.dtype)


def _flash_vmem_bytes(
    sk: int, d: int, block_q: int, block_k: int, itemsize: int
) -> int:
    """VMEM the kernel needs, told to the compiler instead of leaving
    it to the 16 MiB scoped default: each grid cell holds one head's
    whole [Sk, D] K and V, double-buffered by the pipeline, so the need
    grows with the cache length (8 MiB at Sk 8192, D 128, bf16 — the
    default refuses Sk 16384). Plus the q/o blocks (double-buffered)
    and the loop's float32 working set; 4 MiB of headroom for Mosaic's
    own scratch."""
    kv = 2 * 2 * sk * d * itemsize
    qo = 2 * 2 * block_q * d * itemsize
    work = 4 * (3 * block_q * d + 2 * block_k * d + 4 * block_q * block_k)
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
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    window: Optional[int] = None,  # sliding window (causal only)
) -> jnp.ndarray:
    """FlashAttention over [B, S, H, D]; S must be a multiple of the
    block sizes (pad upstream; padded keys are masked out via kv_len).
    K/V keep their KV heads — the grid maps query head h onto KV head
    h // (H // KVH), so GQA costs no HBM repeat. Compiled for the TPU
    unless `interpret=True` (CPU tests) asks for the interpreter."""
    b, sq, h, d = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    assert h % kvh == 0, f"q heads {h} not a multiple of kv heads {kvh}"
    reps = h // kvh
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, (
        f"seq lens ({sq},{sk}) must be multiples of blocks ({block_q},{block_k})"
    )

    if q_offset is None:
        q_offset = jnp.zeros((b,), jnp.int32)
    if kv_len is None:
        kv_len = jnp.full((b,), sk, jnp.int32)

    # [B, S, H, D] → [B, H, S, D]: Mosaic wants the squeezed (blocked-
    # to-1) dims major; the minor two block dims (block_q, d) then meet
    # the (8, 128)-or-full tiling rule.
    qh = q.transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)  # [B, KVH, Sk, D]
    vh = v.transpose(0, 2, 1, 3)

    assert window is None or causal, "sliding window requires causal"
    kernel = functools.partial(
        _flash_kernel, block_k=block_k, sk=sk, causal=causal,
        block_q=block_q, window=window,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b, h, sq // block_q),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # q_offset [B]
            pl.BlockSpec(memory_space=pltpu.SMEM),  # kv_len [B]
            pl.BlockSpec(
                (None, None, block_q, d), lambda bi, hi, qb: (bi, hi, qb, 0)
            ),
            pl.BlockSpec(
                (None, None, sk, d), lambda bi, hi, qb: (bi, hi // reps, 0, 0)
            ),
            pl.BlockSpec(
                (None, None, sk, d), lambda bi, hi, qb: (bi, hi // reps, 0, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (None, None, block_q, d), lambda bi, hi, qb: (bi, hi, qb, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_flash_vmem_bytes(
                sk, d, block_q, block_k, k.dtype.itemsize
            ),
        ),
        interpret=interpret,
    )(
        q_offset.astype(jnp.int32), kv_len.astype(jnp.int32), qh, kh, vh
    )
    return out.transpose(0, 2, 1, 3)


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
    block_q: int = 128,
    block_k: int = 128,
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
# Dispatcher
# ---------------------------------------------------------------------------

# Prefill sequences at least this long go through the Pallas kernel on
# TPU; below it the fused XLA path wins (kernel launch + padding costs).
# PROVENANCE: op-count estimate, not measured — scripts/bench_attention.py
# measures the crossover on the chip (docs/perf_attention.md).
FLASH_MIN_SEQ = 256

# Which implementation each kernel-eligible call took, counted at TRACE
# time (the dispatcher is Python that runs once per traced program, so
# this counts programs, not executions): "flash" / "flash_sharded" =
# the Pallas kernel is in the program; "xla_fallback" = the call wanted
# the kernel and its shapes did not shard over the mesh. Process-wide,
# like the compile watcher: the sidecar exports it beside
# mesh_spec_downgrades (attn_kernel_programs / attn_kernel_fallbacks),
# and it is how chip_smoke.py knows a prefill took the compiled kernel.
dispatch_counts: collections.Counter = collections.Counter()


def dispatch_stats() -> dict:
    """ServingStats view of `dispatch_counts`."""
    return {
        "attn_kernel_programs": (
            dispatch_counts["flash"] + dispatch_counts["flash_sharded"]
        ),
        "attn_kernel_fallbacks": dispatch_counts["xla_fallback"],
    }


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
            jax.devices()[0].platform == "tpu"
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
