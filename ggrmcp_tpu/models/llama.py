"""Llama-family decoder: pure functional, scan-over-layers, GQA + RoPE,
tensor-parallel sharding specs for pjit over the device mesh.

Design (TPU-first, not a port — the reference has no model code):

- Per-layer weights are stacked [L, ...] and the decoder is one
  `lax.scan` over layers: a single compiled block, minimal XLA compile
  time, and the natural substrate for pipeline staging.
- KV cache is part of the functional state: `(k, v)` arrays of shape
  [L, B, S_max, KVH, Dh] threaded through scan; prefill and decode are
  the same `forward` with different sequence lengths — one compiled
  graph per (B, S) bucket.
- Tensor parallelism is expressed as `PartitionSpec`s over the `tensor`
  mesh axis (column-split QKV/gate/up, row-split O/down). XLA inserts
  the all-reduces over ICI; nothing is hand-rolled.
- Long-context: activations can be sequence-sharded with the `sequence`
  axis (see param/activation specs); ring attention lives in
  ops/ring_attention.py.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ggrmcp_tpu.models import common
from ggrmcp_tpu.ops.attention import attention, paged_decode
from ggrmcp_tpu.ops.quant import (
    QuantizedArray,
    dequantize,
    embed_lookup,
    kv_map,
    quantize,
)
from ggrmcp_tpu.ops.quant import matmul as qmatmul
from ggrmcp_tpu.ops.rope import apply_rope

Params = common.Params


@dataclasses.dataclass(frozen=True)
class LlamaConfig(common.ModelConfig):
    name: str = "llama"
    vocab_size: int = 32000
    hidden_dim: int = 512
    num_layers: int = 4
    num_heads: int = 8
    num_kv_heads: int = 4
    head_dim: int = 64
    ffn_dim: int = 1408
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    # Llama-3-style long-context RoPE scaling as a hashable 4-tuple
    # (factor, low_freq_factor, high_freq_factor,
    # original_max_position_embeddings); None = unscaled (ops/rope.py).
    rope_scaling: Optional[tuple] = None
    # Sliding-window attention (Mistral): each query attends to at most
    # this many most recent keys. None = full causal attention.
    sliding_window: Optional[int] = None
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"

    @property
    def kv_planes(self) -> tuple:
        """What one token keeps in each plane of the cache, the shape
        that follows `[layer, row or page, position]`: here K and V,
        (heads, width) each. A family that caches something else
        overrides this (models/mla_moe.py: one latent plane, an empty
        or an indexer-key V plane; models/keye.py: K, V and a third
        plane of indexer keys); every cache constructor sizes the
        planes from it, two at least."""
        return ((self.num_kv_heads, self.head_dim),) * 2

    @property
    def cache_layers(self) -> int:
        """Layers that keep K/V: the layer axis of every cache. A stack
        of which only some layers attend overrides this
        (models/jamba.py)."""
        return self.num_layers

    @property
    def cache_kinds(self) -> tuple:
        """The kinds of caching layer, (layers, window) each, in the
        order the paged cache holds them: an arena and a block table a
        kind (`PagedKVCache`), and a free rule a kind in
        serving/pages.py. `None` keeps every position; a window W keeps
        what a query can still read and lets go of the pages behind it.
        One kind that keeps everything, here and in every family but
        models/smallthinker.py (mistral's window masks, and its cache
        still keeps every page: ROADMAP B1)."""
        return ((self.cache_layers, None),)

    @property
    def row_state(self) -> tuple:
        """What a ROW keeps that no position addresses, a leaf each:
        (shape after `[layer, entry]`, dtype name). Nothing here; a
        state-space family names its recurrent state (models/jamba.py)
        and `zero_state` sizes the pool from it."""
        return ()


# Known configurations. llama3-8b mirrors the published Llama-3-8B
# architecture (the BASELINE.md target model on v5e-8).
CONFIGS: dict[str, LlamaConfig] = {
    "tiny-llama": LlamaConfig(
        name="tiny-llama", vocab_size=512, hidden_dim=256, num_layers=4,
        num_heads=8, num_kv_heads=4, head_dim=32, ffn_dim=704,
        max_seq_len=1024, dtype="float32",
    ),
    # Registry entries are STABLE once published — numerics for a
    # checkpoint saved/served under a name must never silently change
    # (round-4 advisory). Context-extended variants get NEW names (the
    # tiny-llama-8k pattern below).
    "llama-1b": LlamaConfig(
        name="llama-1b", vocab_size=32000, hidden_dim=2048, num_layers=16,
        num_heads=32, num_kv_heads=8, head_dim=64, ffn_dim=5632,
        max_seq_len=4096, rope_theta=10000.0,
    ),
    # Long-context variant: 2x context with rope_theta raised to keep
    # the longest-period frequencies useful at 8k positions (NTK-style
    # extension; 3.2x theta for 2x context is deliberately
    # conservative, not proportional).
    "llama-1b-8k": LlamaConfig(
        name="llama-1b-8k", vocab_size=32000, hidden_dim=2048,
        num_layers=16, num_heads=32, num_kv_heads=8, head_dim=64,
        ffn_dim=5632, max_seq_len=8192, rope_theta=32000.0,
    ),
    "llama3-8b": LlamaConfig(
        name="llama3-8b", vocab_size=128256, hidden_dim=4096, num_layers=32,
        num_heads=32, num_kv_heads=8, head_dim=128, ffn_dim=14336,
        max_seq_len=8192, rope_theta=500000.0,
    ),
    # Mistral-7B-v0.1: Llama-shaped with sliding-window attention —
    # the same decoder with a 4096-key window mask.
    "mistral-7b": LlamaConfig(
        name="mistral-7b", vocab_size=32000, hidden_dim=4096, num_layers=32,
        num_heads=32, num_kv_heads=8, head_dim=128, ffn_dim=14336,
        max_seq_len=8192, rope_theta=10000.0, sliding_window=4096,
    ),
    "tiny-mistral": LlamaConfig(
        name="tiny-mistral", vocab_size=512, hidden_dim=256, num_layers=4,
        num_heads=8, num_kv_heads=4, head_dim=32, ffn_dim=704,
        max_seq_len=1024, sliding_window=16, dtype="float32",
    ),
    # Long-context exercise configs (SURVEY §5.7): tiny dims keep an
    # 8k-position prompt CPU-feasible while the serving geometry —
    # chunked prefill, length tiers, ring KV — runs at REAL lengths
    # (tests/test_long_context.py).
    "tiny-llama-8k": LlamaConfig(
        name="tiny-llama-8k", vocab_size=512, hidden_dim=256, num_layers=4,
        num_heads=8, num_kv_heads=4, head_dim=32, ffn_dim=704,
        max_seq_len=8192, dtype="float32",
    ),
    "tiny-mistral-8k": LlamaConfig(
        name="tiny-mistral-8k", vocab_size=512, hidden_dim=256,
        num_layers=4, num_heads=8, num_kv_heads=4, head_dim=32, ffn_dim=704,
        max_seq_len=8192, sliding_window=1024, dtype="float32",
    ),
}


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def init_params(key: jax.Array, cfg: LlamaConfig) -> Params:
    dtype = cfg.jnp_dtype
    keys = jax.random.split(key, 10)
    d, l = cfg.hidden_dim, cfg.num_layers
    qkv_out = (cfg.num_heads + 2 * cfg.num_kv_heads) * cfg.head_dim
    scale = d**-0.5
    return {
        "embed": common.init_dense(keys[0], cfg.vocab_size, d, dtype, scale=0.02),
        "layers": {
            "attn_norm": jnp.ones((l, d), dtype),
            "wqkv": common.init_stacked(keys[1], l, (d, qkv_out), dtype, scale),
            "wo": common.init_stacked(
                keys[2], l, (cfg.num_heads * cfg.head_dim, d), dtype,
                scale=(cfg.num_heads * cfg.head_dim) ** -0.5,
            ),
            "mlp_norm": jnp.ones((l, d), dtype),
            "w_gate": common.init_stacked(keys[3], l, (d, cfg.ffn_dim), dtype, scale),
            "w_up": common.init_stacked(keys[4], l, (d, cfg.ffn_dim), dtype, scale),
            "w_down": common.init_stacked(
                keys[5], l, (cfg.ffn_dim, d), dtype, scale=cfg.ffn_dim**-0.5
            ),
        },
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": common.init_dense(keys[6], d, cfg.vocab_size, dtype, scale),
    }


def param_specs(cfg: LlamaConfig) -> Params:
    """PartitionSpecs matching init_params' structure: TP over `tensor`
    (column-parallel in-projections, row-parallel out-projections),
    embedding/lm_head vocab-sharded."""
    return {
        "embed": P("tensor", None),
        "layers": {
            "attn_norm": P(None, None),
            "wqkv": P(None, None, "tensor"),
            "wo": P(None, "tensor", None),
            "mlp_norm": P(None, None),
            "w_gate": P(None, None, "tensor"),
            "w_up": P(None, None, "tensor"),
            "w_down": P(None, "tensor", None),
        },
        "final_norm": P(None),
        "lm_head": P(None, "tensor"),
    }


def activation_spec() -> P:
    """[B, S, D] activations: batch over data/fsdp, sequence over the
    sequence axis (long-context SP)."""
    return P(("data", "fsdp"), "sequence", None)


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------


def _zero_planes(cfg: LlamaConfig, lead: tuple, kv_dtype: str) -> tuple:
    """The planes of an empty cache, `lead + plane` each as
    `cfg.kv_planes` sizes them; "int8" = values int8 with
    per-position/head scales in the model dtype; "fp8" = plain
    float8_e4m3fn planes (4 significant bits, no scales)."""
    if kv_dtype not in ("", "int8", "fp8"):
        raise ValueError(f"unknown kv_dtype {kv_dtype!r}")
    dtype = jnp.float8_e4m3fn if kv_dtype == "fp8" else cfg.jnp_dtype

    def plane(heads_width):
        shape = lead + tuple(heads_width)
        if kv_dtype == "int8":
            return QuantizedArray(
                q=jnp.zeros(shape, jnp.int8),
                scale=jnp.zeros(shape[:-1] + (1,), dtype),
            )
        return jnp.zeros(shape, dtype)

    return tuple(plane(p) for p in cfg.kv_planes)


def zero_state(cfg: LlamaConfig, entries: int) -> tuple:
    """An empty pool of `entries` row states, a leaf `[layers, entries,
    ...]` for each that `cfg.row_state` names: () for a family whose
    rows keep none, so its caches have the leaves they always had."""
    if not cfg.row_state:
        return ()
    layers = cfg.num_layers - cfg.cache_layers
    return tuple(
        jnp.zeros((layers, entries, *shape), dtype)
        for shape, dtype in cfg.row_state)


# Pool entries a slot: its own and four snapshots' (docs/paged_kv.md
# "State beside pages").
STATE_ENTRIES_PER_SLOT = 5


class KVCache(NamedTuple):
    k: jnp.ndarray  # [L, B, S_max, KVH, Dh]
    v: jnp.ndarray  # [L, B, S_max, KVH, Dh]
    length: jnp.ndarray  # [B] int32 — valid prefix length
    # The planes past the second that `cfg.kv_planes` names (keye: the
    # indexer keys), same leading axes. Empty for a two-plane family,
    # whose pytree then has the leaves it always had.
    extra: tuple = ()
    # The rows' state pool (`cfg.row_state`: a leaf `[layers, entries,
    # ...]` each) and the entry each batch row reads and writes, [B]
    # int32; None: row b owns entry b. Empty for a family without one.
    state: tuple = ()
    state_rows: Any = None

    @classmethod
    def create(
        cls, cfg: LlamaConfig, batch: int, max_len: int, kv_dtype: str = ""
    ) -> "KVCache":
        """kv_dtype "" = model dtype; "int8" = quantized KV (values
        int8, per-position/head scales in the model dtype — halves KV
        HBM and decode KV bandwidth; serving.kv_cache_dtype)."""
        k, v, *extra = _zero_planes(
            cfg, (cfg.cache_layers, batch, max_len), kv_dtype)
        return cls(
            k=k, v=v, length=jnp.zeros((batch,), jnp.int32),
            extra=tuple(extra), state=zero_state(cfg, batch))


def cache_specs() -> KVCache:
    """KV cache sharding: batch over data, heads over tensor."""
    spec = P(None, ("data", "fsdp"), None, "tensor", None)
    return KVCache(k=spec, v=spec, length=P(("data", "fsdp")))


class WindowArena(NamedTuple):
    """The pages of the caching layers of a second kind
    (`cfg.cache_kinds[1]`: layers that attend a window), beside the
    arena that keeps everything: their own K and V `[Lw, n_pages_w, page,
    KVH, Dh]` and their own block table, whose entries behind a row's
    window are unmapped (docs/paged_kv.md "Two kinds of page")."""

    k: jnp.ndarray
    v: jnp.ndarray
    table: jnp.ndarray  # [B, S_max // page] int32; n_pages_w = unmapped


class PagedKVCache(NamedTuple):
    """Paged KV plane (batching.paged_kv, docs/paged_kv.md): one arena
    of fixed-size pages per layer plus per-slot block tables. Positions
    are still absolute — position j of slot b lives at
    (table[b, j // P], j % P) — so attention semantics (RoPE, causal
    mask, length mask) are identical to the contiguous cache; only the
    STORAGE is indirected, which is what lets any number of slots
    reference the pages of a shared prompt prefix. Table entries equal
    to n_pages are the unmapped SENTINEL: gathers clip (the junk is
    masked by `length`), scatters drop (mode="drop").

    Inside `forward` the arena is LOOP-CARRIED through the layer scan
    and updated in place, indexed [layer, page, offset]: one scatter
    writes the step's K/V, then either the paged-decode kernel walks
    each slot's pages in place (a decode-shaped step on the TPU) or
    one gather reads the slots' views, and no layer's
    [n_pages, page, KVH, Dh] plane is ever sliced out or stacked back
    (docs/paged_kv.md "Inside the jitted tick")."""

    k: jnp.ndarray  # [L, n_pages, page, KVH, Dh] (or QuantizedArray)
    v: jnp.ndarray
    table: jnp.ndarray  # [B, S_max // page] int32 page ids
    length: jnp.ndarray  # [B] int32 — valid prefix length
    extra: tuple = ()  # further planes of every page (KVCache.extra)
    # The rows' state pool and who reads which entry (KVCache.state):
    # the slots' entries first, then the snapshots'.
    state: tuple = ()
    state_rows: Any = None
    # The second kind's arena and table (`cfg.cache_kinds`); None for a
    # config of one kind, whose pytree has the leaves it always had.
    # `k`, `v` and `table` above are then the first kind's alone.
    window: Any = None

    @classmethod
    def create(
        cls, cfg: LlamaConfig, batch: int, max_len: int, n_pages: int,
        page_size: int, kv_dtype: str = "", window_pages: int = 0,
    ) -> "PagedKVCache":
        assert max_len % page_size == 0, "page_size must divide max_len"
        width = max_len // page_size
        kinds = cfg.cache_kinds
        k, v, *extra = _zero_planes(
            cfg, (kinds[0][0], n_pages, page_size), kv_dtype)
        window = None
        if len(kinds) > 1:
            assert len(kinds) == 2 and not extra and window_pages > 0, kinds
            window = WindowArena(
                *_zero_planes(
                    cfg, (kinds[1][0], window_pages, page_size), kv_dtype),
                jnp.full((batch, width), window_pages, jnp.int32))
        return cls(
            k=k, v=v,
            table=jnp.full((batch, width), n_pages, jnp.int32),
            length=jnp.zeros((batch,), jnp.int32),
            extra=tuple(extra),
            state=zero_state(cfg, STATE_ENTRIES_PER_SLOT * batch),
            window=window,
        )


def paged_cache_specs() -> PagedKVCache:
    """Paged arena sharding: heads over tensor only — pages are shared
    across slots, so the page axis cannot shard over a batch axis."""
    spec = P(None, None, None, "tensor", None)
    return PagedKVCache(k=spec, v=spec, table=P(), length=P())


def cache_planes(cache) -> tuple:
    """Every plane of a KVCache or PagedKVCache, in `cfg.kv_planes`
    order."""
    return (cache.k, cache.v, *cache.extra)


def with_planes(cache, planes, **fields):
    """`cache` holding `planes` (as `cache_planes` lists them) and any
    other field given."""
    k, v, *extra = planes
    return cache._replace(k=k, v=v, extra=tuple(extra), **fields)


def map_planes(fn, cache, *others, **fields):
    """`fn(plane, *the others' same plane)` over every plane of
    `cache`, a quantized plane's values and scales alike (`kv_map`):
    the one form of every bookkeeping op that indexes the leading
    [layer, row or page, position] axes only (row merges, page puts,
    gathers), whatever the planes are and however many."""
    return with_planes(cache, tuple(
        kv_map(fn, *each) for each in zip(
            cache_planes(cache), *(cache_planes(o) for o in others))
    ), **fields)


def _kind_layers(cfg) -> list:
    """The model's layer indices of each kind, in `cfg.cache_kinds`
    order (`cfg.layer_kinds` names every layer's: "full" | "window")."""
    return [
        [i for i, kind in enumerate(cfg.layer_kinds) if kind == name]
        for name in ("full", "window")]


def split_kinds(cfg, plane):
    """A `[layers, ...]` plane in the model's layer order (a contiguous
    cache's) as its kinds' `[layers of the kind, ...]` planes."""
    return tuple(
        kv_map(lambda a, at=np.asarray(at): a[at], plane)
        for at in _kind_layers(cfg))


def join_kinds(cfg, planes):
    """`split_kinds` undone: the kinds' planes back in the model's
    layer order."""
    order = np.argsort(np.concatenate(_kind_layers(cfg)))
    return kv_map(lambda *parts: jnp.concatenate(parts)[order], *planes)


def paged_view(arena, table: jnp.ndarray, layer: jnp.ndarray):
    """Gather one layer's contiguous per-slot view straight out of the
    whole paged arena: [L, N, P, KVH, Dh] pages + [B, W] tables + a
    scalar layer index → [B, W·P, KVH, Dh], where view position j is
    absolute position j (W·P == S_max). ONE gather indexed
    [layer, page]: the layer's [N, P, KVH, Dh] plane is never sliced
    out, so inside the tick the arena stays a loop-carried buffer that
    is only scattered into and gathered from. Sentinel entries clip to
    a real page; the junk is masked by the caller's kv_len exactly like
    a contiguous cache's tail garbage. Works on QuantizedArray arenas
    (values + scales gather alike)."""
    def gather(a):
        v = a[layer, jnp.minimum(table, a.shape[1] - 1)]  # [B, W, P, ...]
        return v.reshape(  # sizes spelt out: a plane may be empty
            table.shape[0], table.shape[1] * a.shape[2], *a.shape[3:])

    return kv_map(gather, arena)


def paged_view_layers(arena, table: jnp.ndarray, by_layer: bool = False):
    """`paged_view` for a full [L, N, P, KVH, Dh] arena (batcher-side
    admission gathers): → [L, B, W·P, KVH, Dh]. `by_layer`: a layer at
    a time, indexed [layer, page] as the tick reads the arena. Where a
    page row is one vector (the latent family's [L, N, P, width]), the
    one gather makes the layer axis part of each slice, for which XLA
    first re-lays the whole arena out (a copy of it) and then
    transposes the result; the dense family's arena has no such copy
    and its 32 layers are faster in one gather (PERF.md, PR 33)."""
    def gather(a):
        pages = jnp.minimum(table, a.shape[1] - 1)
        shape = (table.shape[0], table.shape[1] * a.shape[2], *a.shape[3:])
        if not by_layer:
            return a[:, pages].reshape(a.shape[0], *shape)  # [L, B, W·P, ...]

        def layer(i, out):
            return jax.lax.dynamic_update_slice_in_dim(
                out, a[i, pages].reshape(1, *shape), i, 0)

        return jax.lax.fori_loop(
            0, a.shape[0], layer, jnp.zeros((a.shape[0], *shape), a.dtype))

    return kv_map(gather, arena)


class PlaneIO(NamedTuple):
    """One layer's writes and reads of the whole, loop-carried cache
    planes, for the families that walk the cache block of keys by
    block (`plane_io`)."""

    s_keys: int  # key positions a row of the cache can hold
    p_sz: int  # tokens a page (1: contiguous)
    quantized: bool
    put: Any  # (arena, values [B, S, ...]) -> arena
    read_block: Any  # (arena, i, row, block) -> [B or 1, block, ...]
    read_at: Any  # (arena, positions [B, K]) -> [B, K, ...]


def plane_io(planes, page_table, layer, cache_len, s: int, dtype) -> PlaneIO:
    """What an attention block needs of cache planes of any width and
    number: `[L, B, S_max, ...]` (contiguous) or `[L, N, P, ...]` with
    `page_table` [B, W] (paged), values or QuantizedArray, written and
    read at `[layer, ...]` in place. `put` writes this step's `s`
    tokens of every row at `cache_len`.. (a position past the table's
    width goes to the sentinel page and is dropped); `read_block`
    reads keys `i * block`.. of every row, or of row `row` alone;
    `read_at` reads the tokens at given positions. Reads come back
    dequantized, in `dtype`."""
    quantized = isinstance(planes[0], QuantizedArray)
    ref = planes[0].q if quantized else planes[0]
    b = cache_len.shape[0]
    write_pos = cache_len[:, None] + jnp.arange(s)[None, :]  # [B, S]
    if page_table is not None:
        n_pg, p_sz = ref.shape[1:3]
        width = page_table.shape[1]
        s_keys = width * p_sz
        w_idx = write_pos // p_sz
        # Past the table's width is the sentinel, as in attention_block.
        i0 = jnp.where(
            w_idx < width,
            jnp.take_along_axis(
                page_table, jnp.minimum(w_idx, width - 1), axis=1),
            n_pg,
        )
        i1 = write_pos % p_sz
    else:
        p_sz, s_keys = 1, ref.shape[2]
        i0 = jnp.broadcast_to(jnp.arange(b)[:, None], (b, s))
        i1 = write_pos

    def write(arena, val):
        return arena.at[layer, i0, i1].set(
            val.astype(arena.dtype), mode="drop")

    def put(arena, val):
        if quantized:
            return kv_map(write, arena, quantize(val, axis=-1))
        return write(arena, val)

    def reader(arena, read):
        blk = kv_map(read, arena)
        return dequantize(blk) if quantized else blk.astype(dtype)

    def read_block(arena, i, row, block):
        r0, nb = (0, b) if row is None else (row, 1)
        if page_table is not None:
            per = block // p_sz
            pages = jax.lax.dynamic_slice(
                page_table, (r0, i * per), (nb, per))

            def read(a):
                got = a[layer, jnp.minimum(pages, a.shape[1] - 1)]
                return got.reshape(nb, block, *a.shape[3:])
        else:
            def read(a):
                got = jax.lax.dynamic_slice(
                    a, (layer, r0, i * block) + (0,) * (a.ndim - 3),
                    (1, nb, block, *a.shape[3:]))
                return got.reshape(nb, block, *a.shape[3:])

        return reader(arena, read)

    def read_at(arena, pos):
        if page_table is not None:
            pages = jnp.take_along_axis(page_table, pos // p_sz, axis=1)

            def read(a):
                return a[
                    layer, jnp.minimum(pages, a.shape[1] - 1), pos % p_sz]
        else:
            def read(a):
                return a[layer, jnp.arange(b)[:, None], pos]

        return reader(arena, read)

    return PlaneIO(s_keys, p_sz, quantized, put, read_block, read_at)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def attention_block(
    x: jnp.ndarray,  # [B, S, D]
    layer_params: Params,  # one layer's slice (no leading L)
    cfg: LlamaConfig,
    positions: jnp.ndarray,  # [B, S]
    cache_k: Optional[jnp.ndarray],  # [B, S_max, KVH, Dh]
    cache_v: Optional[jnp.ndarray],
    cache_len: Optional[jnp.ndarray],  # [B]
    use_flash: Optional[bool] = None,
    flash_mesh: Any = None,
    attn_impl: Optional[Any] = None,
    ring: bool = False,
    lora_idx: Optional[jnp.ndarray] = None,  # [B] adapter ids
    page_table: Optional[jnp.ndarray] = None,  # [B, W] paged block table
    layer: Optional[jnp.ndarray] = None,  # scalar layer index (paged)
):
    """Pre-norm GQA attention with residual; shared by the dense and MoE
    decoder families. Returns (x + attn, (cache_k, cache_v) or None).
    K/V keep their KV heads — GQA lives in ops.attention (the flash
    kernel reads shared heads in place; the XLA path contracts
    grouped for decode and repeats only for long queries).

    `page_table` (paged KV, docs/paged_kv.md): cache_k/v are the WHOLE
    page arena [L, N, P, KVH, Dh] — every layer's pages, not this
    layer's slice — and `layer` is this layer's index into it. One
    scatter indexed [layer, page, offset] writes the step's K/V through
    the table (position j → page table[b, j // P], offset j % P;
    sentinel entries drop). The read is chosen by `ops.attention.
    paged_decode` from platform, storage and query count: a
    decode-shaped step (S <= 8) over a plain arena on the TPU runs the
    paged-decode kernel, which walks each row's pages in place up to
    its own length; anything else (int8/fp8 pages, a prefill chunk,
    the CPU) does one gather indexed [layer, page] into a [B, W·P] view
    (`paged_view`) for the XLA attention path. Either way the arena the
    caller carries through its layer loop is updated in place and no
    layer's plane is ever materialised. Positions and masks are
    identical to the contiguous cache; on the gathered path (every CPU
    run) so are the numerics, and paged-on/off greedy outputs are
    bit-identical; the kernel sums the softmax block by block, so on
    the chip its bf16 roundings differ, as the prefill kernel's do. The
    returned (cache_k, cache_v) are the whole arenas.
    Shared (refcounted) pages are never written: the host allocator
    guarantees every write position ≥ the owner's prompt length lands
    in pages it owns exclusively (serving/pages.py invariants).

    `ring=True` (sliding-window serving): the cache's sequence dim is a
    RING of capacity C — writes land at `pos % C` and attention masks
    by each slot's absolute position (ops/attention.py k_positions), so
    total length may exceed C. Callers must keep every step's write
    span clear of live window keys: C >= window + step_len - 1
    (docs/kv_ring_design.md — the engine validates this).

    `attn_impl`: optional attention callable
    `(q, k, v, causal, window=None) -> out` over the CURRENT chunk's
    keys only — the sequence-parallel (ring/Ulysses) prefill hook. Valid ONLY for fresh prefill
    (cache_len == 0 and the cache sized exactly to this chunk): then
    cache attention over the written prefix equals plain causal
    attention over the chunk, and per-row pad keys only influence pad
    queries whose outputs are discarded. The engine gates this
    (serving/engine.py::prefill_forward)."""
    b, s, d = x.shape
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    normed = common.rms_norm(x, layer_params["attn_norm"], cfg.norm_eps)
    qkv = qmatmul(normed, layer_params["wqkv"])  # [B, S, (H+2KVH)*Dh]
    if lora_idx is not None and "lora_qkv_a" in layer_params:
        # Multi-LoRA: per-row adapter delta on the fused qkv projection
        # (ops/lora.py — row 0 is the base no-op adapter).
        from ggrmcp_tpu.ops import lora as lora_mod

        qkv = qkv + lora_mod.lora_delta(
            normed, layer_params["lora_qkv_a"],
            layer_params["lora_qkv_b"], lora_idx,
        )
    q, kv = jnp.split(qkv, [h * hd], axis=-1)
    k, v = jnp.split(kv, 2, axis=-1)
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, kvh, hd)
    v = v.reshape(b, s, kvh, hd)
    if cfg.rope_theta:  # 0: a model without rotary (models/jamba.py)
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_scaling)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_scaling)

    paged_out = k_all = v_all = None
    if cache_k is not None and page_table is not None:
        # Paged arena: scatter the step's K/V through the block table
        # into this layer's pages of the whole arena and attend a
        # table-gathered contiguous view. Sentinel table entries
        # (parked slots, unmapped tail) drop the write; active rows
        # only ever write pages they own exclusively.
        assert not ring, "paged KV does not compose with kv_ring"
        assert layer is not None, "paged KV needs the layer index"
        _, n_pg, p_sz = cache_k.shape[:3]
        width = page_table.shape[1]
        write_pos = cache_len[:, None] + jnp.arange(s)[None, :]  # [B, S]
        w_idx = write_pos // p_sz
        # Positions past the table width map to the sentinel, NOT to a
        # clipped last entry: a multi-position window (jump tick,
        # chunked prefill tail) can overshoot a full-width row's table,
        # and clipping would land junk in that row's last REAL page.
        # Sentinel writes drop (mode="drop"), same as unmapped entries.
        w_page = jnp.where(
            w_idx < width,
            jnp.take_along_axis(
                page_table, jnp.minimum(w_idx, width - 1), axis=1
            ),
            n_pg,
        )
        w_off = write_pos % p_sz

        def write(arena, new):  # in place on the carried arena
            return arena.at[layer, w_page, w_off].set(
                new.astype(arena.dtype), mode="drop"
            )

        if isinstance(cache_k, QuantizedArray):
            # Int8 pages: same value+scale scatter as the contiguous
            # int8 cache, indirected through the table.
            cache_k = kv_map(write, cache_k, quantize(k, axis=-1))
            cache_v = kv_map(write, cache_v, quantize(v, axis=-1))
            k_all = dequantize(paged_view(cache_k, page_table, layer))
            v_all = dequantize(paged_view(cache_v, page_table, layer))
        else:
            cache_k, cache_v = write(cache_k, k), write(cache_v, v)
            if attn_impl is None:
                # A decode-shaped step on the TPU reads the written
                # arena in place, each row's pages up to its own
                # length; anything else (None) gathers the view.
                paged_out = paged_decode(
                    q, cache_k, cache_v, page_table, cache_len + s, layer,
                    window=cfg.sliding_window, use_flash=use_flash,
                    flash_mesh=flash_mesh,
                )
            if paged_out is None:  # (a float8 arena: read in q's dtype)
                k_all = paged_view(cache_k, page_table, layer).astype(q.dtype)
                v_all = paged_view(cache_v, page_table, layer).astype(q.dtype)
        kv_len = cache_len + s
        q_offset = cache_len
        k_positions = None
        k_step, v_step = k, v
        use_flash = False  # a gathered view takes the XLA path: the
        # prefill kernel is never auto-picked here
    elif cache_k is not None:
        # Write new K/V at each sequence's current length, then attend
        # over the full cache prefix. Scatter via one-hot matmul-free
        # dynamic update: positions are per-batch, so use advanced
        # indexing with explicit batch indices (compiles to scatter).
        batch_idx = jnp.arange(b)[:, None]  # [B, 1]
        write_pos = cache_len[:, None] + jnp.arange(s)[None, :]  # [B, S]
        capacity = (
            cache_k.q.shape[1]
            if isinstance(cache_k, QuantizedArray) else cache_k.shape[1]
        )
        k_positions = None
        if ring:
            # Trace-time contract: a windowed model, a step that fits
            # the ring, and enough capacity that this step's writes
            # cannot destroy any in-window key before the queries
            # attend (docs/kv_ring_design.md).
            assert cfg.sliding_window is not None, "ring needs a window"
            assert s <= capacity, f"step {s} exceeds ring capacity {capacity}"
            assert capacity >= cfg.sliding_window + s - 1, (
                f"ring capacity {capacity} < window "
                f"{cfg.sliding_window} + step {s} - 1 (clobber)"
            )
            write_pos = write_pos % capacity
        if isinstance(cache_k, QuantizedArray):
            # Int8 KV: quantize the step's K/V per position+head and
            # scatter values + scales. Reads dequantize lazily — XLA
            # fuses the s8→bf16 cast and the scale multiply into the
            # attention matmuls, so HBM traffic stays int8 (the whole
            # point: decode streams the cache every step). The current
            # step's K/V also round-trip through int8, keeping prefill
            # and decode numerics consistent.
            qk = quantize(k, axis=-1)
            qv = quantize(v, axis=-1)
            cache_k = QuantizedArray(
                q=cache_k.q.at[batch_idx, write_pos].set(qk.q),
                scale=cache_k.scale.at[batch_idx, write_pos].set(
                    qk.scale.astype(cache_k.scale.dtype)
                ),
            )
            cache_v = QuantizedArray(
                q=cache_v.q.at[batch_idx, write_pos].set(qv.q),
                scale=cache_v.scale.at[batch_idx, write_pos].set(
                    qv.scale.astype(cache_v.scale.dtype)
                ),
            )
            k_all, v_all = dequantize(cache_k), dequantize(cache_v)
            # The current step's K/V as the cache will replay them:
            # a sequence-parallel prefill (attn_impl) must attend these
            # round-tripped values, not the raw bf16 ones, so sp and
            # XLA prefill of the same prompt carry identical
            # quantization error into identical decode.
            k_step, v_step = dequantize(qk), dequantize(qv)
            use_flash = False  # materializing bf16 KV for the Pallas
            # kernel would forfeit the int8 bandwidth win
        else:
            cache_k = cache_k.at[batch_idx, write_pos].set(
                k.astype(cache_k.dtype))
            cache_v = cache_v.at[batch_idx, write_pos].set(
                v.astype(cache_v.dtype))
            # (a float8 cache, models/smallthinker.py: read in q's dtype)
            k_all, v_all = cache_k.astype(q.dtype), cache_v.astype(q.dtype)
            k_step, v_step = k, v
        kv_len = cache_len + s
        q_offset = cache_len
        if ring:
            # Absolute position currently held by each ring slot j: the
            # largest p < kv_len with p ≡ j (mod C); negative = slot
            # never written (ops/attention.py masks those out).
            slots = jnp.arange(capacity)[None, :]  # [1, C]
            total = kv_len[:, None]  # [B, 1]
            k_positions = slots + capacity * (
                (total - 1 - slots) // capacity
            )
    else:
        k_all, v_all, kv_len, q_offset = k, v, None, None
        k_step, v_step = k, v
        k_positions = None

    if paged_out is not None:
        attn_out = paged_out
    elif attn_impl is not None:
        # Sequence-parallel fresh-prefill: attend over this chunk's
        # keys (contract above). Ring/Ulysses expect equal head counts;
        # sliding-window models pass the window through (ring masks by
        # global position, Ulysses gathers full sequences — both match
        # the local windowed mask exactly, tests/test_ring_attention).
        if kvh != h:
            reps = h // kvh
            attn_out = attn_impl(
                q,
                jnp.repeat(k_step, reps, axis=2),
                jnp.repeat(v_step, reps, axis=2),
                causal=True,
                window=cfg.sliding_window,
            )
        else:
            attn_out = attn_impl(
                q, k_step, v_step, causal=True, window=cfg.sliding_window
            )
    else:
        attn_out = attention(
            q, k_all, v_all, causal=True, q_offset=q_offset, kv_len=kv_len,
            use_flash=use_flash, flash_mesh=flash_mesh,
            window=cfg.sliding_window, k_positions=k_positions,
        )
    attn_out = qmatmul(attn_out.reshape(b, s, h * hd), layer_params["wo"])
    x = x + attn_out

    if cache_k is not None:
        return x, (cache_k, cache_v)
    return x, None


def _layer(
    x: jnp.ndarray,
    layer_params: Params,
    cfg: LlamaConfig,
    positions: jnp.ndarray,
    cache_k: Optional[jnp.ndarray],
    cache_v: Optional[jnp.ndarray],
    cache_len: Optional[jnp.ndarray],
    use_flash: Optional[bool] = None,
    flash_mesh: Any = None,
    attn_impl: Optional[Any] = None,
    ring: bool = False,
    lora_idx: Optional[jnp.ndarray] = None,
    page_table: Optional[jnp.ndarray] = None,
    layer: Optional[jnp.ndarray] = None,
):
    x, new_cache = attention_block(
        x, layer_params, cfg, positions, cache_k, cache_v, cache_len,
        use_flash=use_flash, flash_mesh=flash_mesh, attn_impl=attn_impl,
        ring=ring, lora_idx=lora_idx, page_table=page_table, layer=layer,
    )

    # SwiGLU MLP
    normed = common.rms_norm(x, layer_params["mlp_norm"], cfg.norm_eps)
    gate = jax.nn.silu(qmatmul(normed, layer_params["w_gate"]))
    up = qmatmul(normed, layer_params["w_up"])
    x = x + qmatmul(gate * up, layer_params["w_down"])

    return x, new_cache


def forward(
    params: Params,
    cfg: LlamaConfig,
    tokens: jnp.ndarray,  # [B, S]
    cache: Optional[KVCache] = None,
    use_flash: Optional[bool] = None,
    flash_mesh: Any = None,
    attn_impl: Optional[Any] = None,
    ring: bool = False,
    lora_idx: Optional[jnp.ndarray] = None,
) -> tuple[jnp.ndarray, Optional[KVCache]]:
    """Run the decoder. Without a cache: plain causal forward (training/
    scoring). With a cache: serving — tokens are appended at each
    sequence's cache length (prefill S>1, decode S=1), the cache is
    updated functionally, and logits cover the new positions.

    `use_flash`: None = auto (ops.attention decides per shape/platform);
    False forces the XLA path (multi-device meshes — see ops/attention).
    `attn_impl`: sequence-parallel fresh-prefill hook (attention_block).
    `lora_idx`: [B] per-row adapter ids when `params["layers"]` carries
    stacked LoRA factors (ops/lora.py); None or absent factors = base.

    A `PagedKVCache` (batching.paged_kv) is the one cache the layer
    scan CARRIES instead of scanning in and stacking out: the whole
    [L, N, P, KVH, Dh] arena rides the carry beside `x`, each layer
    scatters into it at [layer, page, offset] and reads it there
    (attention_block `page_table` / `layer`: the paged-decode kernel,
    or a gathered view), and XLA updates it in place — a decode step
    moves the step's own K/V and the live pages (or the gathered
    views), never the arena or a layer's plane. The block table rides
    scan-invariant.

    Returns (logits [B, S, V], updated cache or None).
    """
    b, s = tokens.shape
    x = embed_lookup(params["embed"], tokens, cfg.jnp_dtype)  # [B, S, D]

    if cache is not None:
        positions = cache.length[:, None] + jnp.arange(s)[None, :]
    else:
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))

    layers = params["layers"]

    if cache is None:

        def body(x, layer_params):
            x, _ = _layer(
                x, layer_params, cfg, positions, None, None, None,
                use_flash=use_flash, flash_mesh=flash_mesh,
                attn_impl=attn_impl, lora_idx=lora_idx,
            )
            return x, None

        x, _ = jax.lax.scan(body, x, layers)
        new_cache = None
    elif isinstance(cache, PagedKVCache):

        def body(carry, scanned):
            x, ck, cv = carry
            layer_params, layer = scanned
            x, (ck, cv) = _layer(
                x, layer_params, cfg, positions, ck, cv, cache.length,
                use_flash=use_flash, flash_mesh=flash_mesh,
                attn_impl=attn_impl, ring=ring, lora_idx=lora_idx,
                page_table=cache.table, layer=layer,
            )
            return (x, ck, cv), None

        (x, new_k, new_v), _ = jax.lax.scan(
            body, (x, cache.k, cache.v),
            (layers, jnp.arange(cache.k.shape[0])),
        )
        new_cache = cache._replace(k=new_k, v=new_v, length=cache.length + s)
    else:

        def body(x, scanned):
            layer_params, ck, cv = scanned
            x, (ck, cv) = _layer(
                x, layer_params, cfg, positions, ck, cv, cache.length,
                use_flash=use_flash, flash_mesh=flash_mesh,
                attn_impl=attn_impl, ring=ring, lora_idx=lora_idx,
            )
            return x, (ck, cv)

        x, (new_k, new_v) = jax.lax.scan(body, x, (layers, cache.k, cache.v))
        new_cache = KVCache(k=new_k, v=new_v, length=cache.length + s)

    x = common.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["lm_head"]
    if not isinstance(head, QuantizedArray):
        head = head.astype(cfg.jnp_dtype)
    logits = qmatmul(x, head)  # [B, S, V]
    return logits.astype(jnp.float32), new_cache


def num_params(cfg: LlamaConfig) -> int:
    d, l, v = cfg.hidden_dim, cfg.num_layers, cfg.vocab_size
    qkv = d * (cfg.num_heads + 2 * cfg.num_kv_heads) * cfg.head_dim
    per_layer = (
        qkv + cfg.num_heads * cfg.head_dim * d + 2 * d  # attn + norms
        + 3 * d * cfg.ffn_dim  # mlp
    )
    return v * d * 2 + l * per_layer + d
