"""Latent-attention decoder with sigmoid-routed experts and shared
experts (the published `deepseek_v3` layer equations; registry entries
for kanana-2-30b-a3b).

What differs from the llama and moe families, and why it is a family
of its own:

- **Attention caches one latent per token**, not K and V per head: the
  576 values `[RMSNorm(c_kv) (512) | RoPE(k_rope) (64)]`, shared by all
  heads. `cfg.kv_planes` says so to the cache constructors: the K plane
  is `[.., 576]` (no head axis: a size-1 axis next to the lanes makes
  the TPU compiler re-lay the whole arena out every step), the V plane
  has width 0 and holds no bytes, so every cache bookkeeping op of the
  batcher (row merges, page puts, gathers, CoW: all index the leading
  [layer, row or page, position] axes only) runs unchanged over the
  one latent plane.
- **Two forms of one attention.** Expanded: K/V are rebuilt from the
  latent (`W_kv_b`), scores over 192-wide q.k, values 128 wide; fewer
  operations per query once there are many queries. Absorbed: `W_UK` is
  folded into the query and `W_UV` applied after, so the 32 heads
  attend the shared 576-wide latent directly; nothing is rebuilt, which
  is what a decode step wants. `ABSORBED_MAX_QUERIES` picks by the
  step's query count (PERF.md has the measurement). Both walk the
  cache in blocks of keys with an online softmax and stop at the
  longest live row, so neither a [.., S_max] score tensor nor a
  full-width gathered view is ever materialised. A prefill chunk over
  a contiguous plane takes the absorbed form as one Pallas kernel on a
  TPU (`ops.attention.latent_prefill`), whose score block never
  leaves VMEM; `latent_attention` stays what every other call runs
  and what the tests hold the kernel to.
- **The layer stack is not homogeneous** (leading dense layers, then
  expert layers), so the cache is loop-carried through BOTH layer scans
  and indexed [layer, ...] in place, for the paged arena (as PR 27 made
  it for llama) and for the contiguous admission mini cache alike.
- **Routing is dropless and per token**: `s = sigmoid(h W_r)`, the top
  k of `s + b` are chosen, weights are `s[chosen]` (without b)
  normalised and scaled. Every routed (token, expert) pair is computed:
  pairs are sorted by expert and walked in fixed-size blocks, one
  expert's weights per block, so a token's output cannot depend on who
  shares its batch, and only experts that were hit are read.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ggrmcp_tpu.models import common
from ggrmcp_tpu.models.llama import (  # noqa: F401
    KVCache,
    LlamaConfig,
    PagedKVCache,
    activation_spec,
)
from ggrmcp_tpu.ops import attention as attn_ops
from ggrmcp_tpu.ops.quant import (
    QuantizedArray,
    dequantize,
    embed_lookup,
    kv_map,
    quantize,
)
from ggrmcp_tpu.ops.rope import apply_rope

Params = common.Params

# A step with at most this many queries a row (a decode step, a short
# re-admission suffix) attends in the absorbed form through the XLA
# walk. A longer one is a prefill chunk: over a contiguous plane on a
# TPU it attends in the absorbed form too, as one Pallas kernel
# (ops/attention.py `latent_prefill`: score block and accumulator in
# VMEM); where that kernel is not the call's kind (the CPU, a quantized
# or float8 plane, the paged arena, no cache) the walk attends it in
# the expanded form. Measured on a v5e (scripts/attn_form_bench.py;
# PERF.md section 4): through the walk the absorbed form is as fast or
# faster at every size tried (512 queries on a 12k past 8.42 against
# 8.64 ms, 16 decode rows 1.47 against 16.6 ms), both being bound by
# passes over float32 scores in HBM, and the kernel takes 3.76 ms. The
# walk keeps a chunk expanded for its memory: absorbed, 16 rows x 512
# queries carry a 0.54 GB float32 accumulator in HBM beside a 2 GB
# mini cache; the kernel's accumulator is a tile's, 1 MiB of VMEM.
ABSORBED_MAX_QUERIES = 128

# What the batcher may ask of this family (serving/batching.py reads
# these from the family's module; a family without one gets the llama
# behaviour). `forward` computes the head at one position a row
# (`logit_idx`) and returns a step's routing counts (`with_stats`).
HEAD_AT_INDEX = True
ROUTING_STATS = True
# A cold prompt of more chunks than this runs on a chunk grid rounded
# up to a power of two and is admitted alone (at the default chunk of
# 512: past 2,048 tokens). This family serves contexts of 6k-13k
# tokens, where exact depths are a program each (13..24 chunks); the
# padding chunks are cheap here because `attention_block` stops its
# walk over the keys at the last `valid` one (the XLA walk for the
# whole step; the prefill kernel a query tile, and a tile of padding
# walks nothing). llama's chunk attention
# has no such mask, so llama keeps exact depths and group admission, as
# before this family came (no cell measures llama past 2,048 tokens).
DEEP_GRID_CHUNKS = 4


@dataclasses.dataclass(frozen=True)
class MlaMoeConfig(LlamaConfig):
    """`num_kv_heads` and `head_dim` keep their published values (32,
    64) and size nothing here; `ffn_dim` is the dense layers' width."""

    name: str = "mla-moe"
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    first_dense_layers: int = 1
    num_experts: int = 128
    experts_per_token: int = 6
    expert_ffn_dim: int = 768
    num_shared_experts: int = 2
    routed_scaling: float = 2.448
    norm_eps: float = 1e-6
    rope_theta: float = 1e6

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def kv_planes(self) -> tuple:
        """One latent plane; the V plane is empty. The plane is as wide
        as the next multiple of the TPU's 128 lanes (576 -> 640, zeros
        at the end): at 576 the compiler keeps the arena in a layout
        with the page axis minor-most, and every tick re-lays all of it
        out twice (PERF.md, PR 28)."""
        return ((-(-self.latent_dim // 128) * 128,), (0,))

    @property
    def num_expert_layers(self) -> int:
        return self.num_layers - self.first_dense_layers


_KANANA = dict(
    vocab_size=128256, hidden_dim=2048, num_heads=32, num_kv_heads=32,
    head_dim=64, ffn_dim=6144, max_seq_len=32768,
)

CONFIGS: dict[str, MlaMoeConfig] = {
    # kakaocorp/kanana-2-30b-a3b-instruct-2601, config.json as published.
    "kanana-2-30b-a3b": MlaMoeConfig(
        name="kanana-2-30b-a3b", num_layers=48, **_KANANA),
    # The same widths at the depth one v5e chip holds in bf16 beside
    # its cache: the leading dense layer and 5 expert layers.
    "kanana-2-30b-a3b-6l": MlaMoeConfig(
        name="kanana-2-30b-a3b-6l", num_layers=6, **_KANANA),
    "tiny-mla-moe": MlaMoeConfig(
        name="tiny-mla-moe", vocab_size=512, hidden_dim=128, num_layers=3,
        num_heads=4, num_kv_heads=4, head_dim=16, ffn_dim=256,
        max_seq_len=1024, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=16, v_head_dim=16, num_experts=8,
        experts_per_token=2, expert_ffn_dim=64, num_shared_experts=2,
        dtype="float32",
    ),
}


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def _attn_shapes(cfg: MlaMoeConfig) -> dict:
    d, h = cfg.hidden_dim, cfg.num_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    return {
        "wq": ((d, h * qk), d**-0.5),
        "wkv_a": ((d, cfg.latent_dim), d**-0.5),
        "wkv_b": (
            (cfg.kv_lora_rank, h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            cfg.kv_lora_rank**-0.5,
        ),
        "wo": ((h * cfg.v_head_dim, d), (h * cfg.v_head_dim) ** -0.5),
    }


def leaf_recipe(cfg: MlaMoeConfig) -> list:
    """Every drawn leaf, in draw order: (path, shape, scale, dtype
    name). Leaf i is `truncated_normal(split(key, n)[i], -2, 2, shape,
    float32) * scale` cast to its dtype; the router bias is drawn
    non-zero so that a weight computed from `s + b` shows. Norm
    weights are ones and not drawn. The benchmark's reference repeats
    this recipe from its own copy of the list."""
    d, e = cfg.hidden_dim, cfg.num_experts
    f, fs = cfg.expert_ffn_dim, cfg.num_shared_experts * cfg.expert_ffn_dim
    kd, km = cfg.first_dense_layers, cfg.num_expert_layers
    attn = _attn_shapes(cfg)
    out = [(("embed",), (cfg.vocab_size, d), 0.02, cfg.dtype)]
    for stack, n in (("dense", kd), ("layers", km)):
        out += [
            ((stack, name), (n, *shape), scale, cfg.dtype)
            for name, (shape, scale) in attn.items()
        ]
    out += [
        (("dense", "w_gate"), (kd, d, cfg.ffn_dim), d**-0.5, cfg.dtype),
        (("dense", "w_up"), (kd, d, cfg.ffn_dim), d**-0.5, cfg.dtype),
        (("dense", "w_down"), (kd, cfg.ffn_dim, d), cfg.ffn_dim**-0.5,
         cfg.dtype),
        (("layers", "router"), (km, d, e), d**-0.5, "float32"),
        (("layers", "router_bias"), (km, e), 0.1, "float32"),
        (("layers", "w_gate"), (km, e, d, f), d**-0.5, cfg.dtype),
        (("layers", "w_up"), (km, e, d, f), d**-0.5, cfg.dtype),
        (("layers", "w_down"), (km, e, f, d), f**-0.5, cfg.dtype),
        (("layers", "ws_gate"), (km, d, fs), d**-0.5, cfg.dtype),
        (("layers", "ws_up"), (km, d, fs), d**-0.5, cfg.dtype),
        (("layers", "ws_down"), (km, fs, d), fs**-0.5, cfg.dtype),
        (("lm_head",), (d, cfg.vocab_size), d**-0.5, cfg.dtype),
    ]
    return out


def init_params(key: jax.Array, cfg: MlaMoeConfig) -> Params:
    dtype = cfg.jnp_dtype
    d = cfg.hidden_dim
    recipe = leaf_recipe(cfg)
    params: Params = {"dense": {}, "layers": {}}
    for k, (path, shape, scale, leaf_dtype) in zip(
        jax.random.split(key, len(recipe)), recipe
    ):
        leaf = (
            jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32)
            * scale
        ).astype(leaf_dtype)
        node = params
        for name in path[:-1]:
            node = node[name]
        node[path[-1]] = leaf
    for stack, n in (
        ("dense", cfg.first_dense_layers), ("layers", cfg.num_expert_layers)
    ):
        params[stack]["attn_norm"] = jnp.ones((n, d), dtype)
        params[stack]["kv_norm"] = jnp.ones((n, cfg.kv_lora_rank), dtype)
        params[stack]["mlp_norm"] = jnp.ones((n, d), dtype)
    params["final_norm"] = jnp.ones((d,), dtype)
    return params


def param_specs(cfg: MlaMoeConfig) -> Params:
    """Heads and FFN widths over `tensor`, as the dense family; experts
    stay whole on every chip (sharding them is not built: ROADMAP)."""
    attn = {
        "attn_norm": P(None, None), "kv_norm": P(None, None),
        "mlp_norm": P(None, None),
        "wq": P(None, None, "tensor"), "wkv_a": P(None, None, None),
        "wkv_b": P(None, None, "tensor"), "wo": P(None, "tensor", None),
    }
    return {
        "embed": P("tensor", None),
        "dense": {
            **attn,
            "w_gate": P(None, None, "tensor"), "w_up": P(None, None, "tensor"),
            "w_down": P(None, "tensor", None),
        },
        "layers": {
            **attn,
            "router": P(None, None, None), "router_bias": P(None, None),
            "w_gate": P(None, None, None, "tensor"),
            "w_up": P(None, None, None, "tensor"),
            "w_down": P(None, None, "tensor", None),
            "ws_gate": P(None, None, "tensor"),
            "ws_up": P(None, None, "tensor"),
            "ws_down": P(None, "tensor", None),
        },
        "final_norm": P(None),
        "lm_head": P(None, "tensor"),
    }


def cache_specs() -> KVCache:
    """Latent planes `[L, B, S, latent]`: rows over data, the latent
    whole on every chip (all heads read all of it)."""
    spec = P(None, ("data", "fsdp"), None, None)
    return KVCache(k=spec, v=spec, length=P(("data", "fsdp")))


def paged_cache_specs() -> PagedKVCache:
    spec = P(None, None, None, None)
    return PagedKVCache(k=spec, v=spec, table=P(), length=P())


# ---------------------------------------------------------------------------
# Attention over the latent cache
# ---------------------------------------------------------------------------


def _key_block(b: int, s: int, s_keys: int, page: int) -> int:
    """Keys a block of the XLA walk (the prefill kernel has its own,
    ops/attention.py): bounds the walk's [B, H, S, block] float32
    scores in HBM to a few hundred MB at the published widths (16 rows
    x 512 queries)."""
    block = 2048 if b * s <= 512 else 512
    while block > page and (s_keys % block or block % page):
        block //= 2
    return block if block > page else page


def absorbed_queries(q_nope, q_rope, w_uk, width: int):
    """The step's queries in the latent plane's own space,
    `[q_nope W_UK | q_rope | 0]`, `width` wide: a plane row is their
    key as it is stored (what `ops.attention.latent_prefill` takes)."""
    q = jnp.concatenate(
        [jnp.einsum("bshd,chd->bshc", q_nope, w_uk), q_rope], axis=-1)
    return jnp.pad(q, ((0, 0),) * 3 + ((0, width - q.shape[-1]),))


def latent_attention(
    q_nope, q_rope,  # [B, S, H, nope], [B, S, H, rope] (rotated)
    fetch,  # block index -> [B, block, latent] (normed c | rotated k_rope)
    n_blocks,  # scalar int: blocks to walk
    block: int,
    wkv_b,  # [rank, H, nope + v]
    q_pos,  # [B, S] absolute positions of the queries
    kv_len,  # [B] keys each row may see
    cfg: MlaMoeConfig,
    absorbed: bool,
):
    """Causal softmax attention of the step's queries over the cached
    latents, block of keys by block of keys with a running maximum and
    sum (float32). `absorbed` picks the form; both give the same
    attention up to rounding. Returns [B, S, H, v_head_dim]."""
    b, s, h, nope = q_nope.shape
    rank, vd = cfg.kv_lora_rank, cfg.v_head_dim
    f32 = jnp.float32
    scale = 1.0 / math.sqrt(nope + cfg.qk_rope_head_dim)
    w_uk, w_uv = wkv_b[..., :nope], wkv_b[..., nope:]
    if absorbed:
        q_lat = jnp.einsum("bshd,chd->bshc", q_nope, w_uk)  # [B,S,H,rank]
        width = rank
    else:
        width = vd

    def body(i, carry):
        m, l, acc = carry
        lat = fetch(i)  # [B, block, latent]
        c = lat[..., :rank]
        k_rope = lat[..., rank:rank + cfg.qk_rope_head_dim]
        scores = jnp.einsum(
            "bshr,bkr->bhsk", q_rope, k_rope, preferred_element_type=f32
        )
        if absorbed:
            scores += jnp.einsum(
                "bshc,bkc->bhsk", q_lat, c, preferred_element_type=f32
            )
        else:
            kv = jnp.einsum("bkc,chd->bkhd", c, wkv_b)
            scores += jnp.einsum(
                "bshd,bkhd->bhsk", q_nope, kv[..., :nope],
                preferred_element_type=f32,
            )
        k_pos = i * block + jnp.arange(block)
        seen = (k_pos[None, None, :] <= q_pos[:, :, None]) & (
            k_pos[None, None, :] < kv_len[:, None, None]
        )  # [B, S, block]
        scores = jnp.where(seen[:, None], scores * scale, -1e30)
        m_new = jnp.maximum(m, scores.max(-1))
        p = jnp.exp(scores - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(-1)
        if absorbed:  # the heads share the latent as their values
            pv = jnp.einsum(
                "bhsk,bkc->bshc", p.astype(c.dtype), c,
                preferred_element_type=f32)
        else:
            pv = jnp.einsum(
                "bhsk,bkhd->bshd", p.astype(kv.dtype), kv[..., nope:],
                preferred_element_type=f32)
        acc = acc * alpha.transpose(0, 2, 1)[..., None] + pv
        return m_new, l, acc

    init = (
        jnp.full((b, h, s), -1e30, f32), jnp.zeros((b, h, s), f32),
        jnp.zeros((b, s, h, width), f32),
    )
    _, l, acc = jax.lax.fori_loop(0, n_blocks, body, init)
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    out = out.astype(q_nope.dtype)
    if absorbed:
        out = jnp.einsum("bshc,chd->bshd", out, w_uv)
    return out


def attention_block(
    x, lp, cfg: MlaMoeConfig, positions, cache_k, cache_len,
    page_table, layer, valid=None, use_flash=None, flash_mesh=None,
):
    """Pre-norm latent attention with residual. `cache_k` is the WHOLE
    latent plane, loop-carried: `[L, B, S_max, latent]` (contiguous)
    or `[L, N, P, latent]` with `page_table` (paged); this layer
    writes and reads it at `[layer, ...]` in place. None = no cache
    (the step's own latents are the keys). `valid` [B, S] marks the
    real queries: the walk over the cache stops at the last key any of
    THEM may see, so a padding chunk of a chunk grid, or a tick in
    which the longest rows are parked, attends nothing it does not
    need (an all-padding step walks no block at all).
    Returns (x + attn, plane)."""
    b, s, _ = x.shape
    h, nope, rope = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    rank = cfg.kv_lora_rank

    normed = common.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q = (normed @ lp["wq"]).reshape(b, s, h, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    a = normed @ lp["wkv_a"]  # [B, S, latent]
    c = common.rms_norm(a[..., :rank], lp["kv_norm"], cfg.norm_eps)
    # Published pairs are (2i, 2i+1); de-interleaved to (evens | odds),
    # on q and k alike, they are the half-split pairs ops/rope rotates,
    # and a permutation shared by q and k leaves every score as it was.
    def rot(t):
        t = jnp.concatenate([t[..., 0::2], t[..., 1::2]], axis=-1)
        return apply_rope(t, positions, cfg.rope_theta, cfg.rope_scaling)

    q_rope = rot(q_rope)
    k_rope = rot(a[..., None, rank:])[..., 0, :]
    lat = jnp.concatenate([c, k_rope], axis=-1)  # [B, S, latent]
    lat = jnp.pad(  # to the cache plane's width
        lat, ((0, 0), (0, 0), (0, cfg.kv_planes[0][0] - cfg.latent_dim)))
    wkv_b = lp["wkv_b"].reshape(rank, h, nope + cfg.v_head_dim)

    out = None
    if cache_k is None:
        pad = -s % min(s, 512)
        lat_p = jnp.pad(lat, ((0, 0), (0, pad), (0, 0)))
        block = min(s, 512)
        n_blocks = (s + pad) // block
        kv_len = jnp.full((b,), s, jnp.int32)

        def fetch(i):
            return jax.lax.dynamic_slice_in_dim(lat_p, i * block, block, 1)
    else:
        quantized = isinstance(cache_k, QuantizedArray)
        plane = cache_k.q if quantized else cache_k
        write_pos = cache_len[:, None] + jnp.arange(s)[None, :]  # [B, S]
        if page_table is not None:
            n_pg, p_sz = plane.shape[1:3]
            width = page_table.shape[1]
            s_keys = width * p_sz
            w_idx = write_pos // p_sz
            # Past the table's width is the sentinel, as in llama.
            i0 = jnp.where(
                w_idx < width,
                jnp.take_along_axis(
                    page_table, jnp.minimum(w_idx, width - 1), axis=1),
                n_pg,
            )
            i1 = write_pos % p_sz
        else:
            p_sz, s_keys = 1, plane.shape[2]
            i0 = jnp.broadcast_to(jnp.arange(b)[:, None], (b, s))
            i1 = write_pos

        def write(arena, val):
            return arena.at[layer, i0, i1].set(
                val.astype(arena.dtype), mode="drop")

        if quantized:
            cache_k = kv_map(write, cache_k, quantize(lat, axis=-1))
        else:
            cache_k = write(cache_k, lat)
        block = _key_block(b, s, s_keys, p_sz)
        kv_len = cache_len + s
        last = positions if valid is None else jnp.where(valid, positions, -1)
        n_blocks = jnp.clip(
            (jnp.max(last) + block) // block, 0, s_keys // block)
        arena = cache_k
        if s > ABSORBED_MAX_QUERIES and page_table is None and not quantized:
            # A prefill chunk over a contiguous plane: the absorbed
            # form as one kernel where `latent_prefill` finds its kind
            # (a TPU, the plane in the model's dtype), else None and
            # the walk below.
            out = attn_ops.latent_prefill(
                absorbed_queries(
                    q_nope, q_rope, wkv_b[..., :nope], lat.shape[-1]),
                cache_k, layer, cache_len, kv_len, jnp.max(last, axis=1),
                value_width=rank, scale=1.0 / math.sqrt(nope + rope),
                use_flash=use_flash, flash_mesh=flash_mesh,
            )
            if out is not None:
                out = jnp.einsum("bshc,chd->bshd", out, wkv_b[..., nope:])

        def fetch(i):
            if page_table is not None:
                per = block // p_sz
                pages = jax.lax.dynamic_slice_in_dim(
                    page_table, i * per, per, 1)  # [B, per]

                def read(a):
                    v = a[layer, jnp.minimum(pages, a.shape[1] - 1)]
                    return v.reshape(b, block, a.shape[-1])
            else:
                def read(a):
                    v = jax.lax.dynamic_slice(
                        a, (layer, 0, i * block, 0),
                        (1, b, block, a.shape[-1]))
                    return v.reshape(b, block, a.shape[-1])

            blk = kv_map(read, arena)
            return dequantize(blk) if quantized else blk.astype(lat.dtype)

    if out is None:
        out = latent_attention(
            q_nope, q_rope, fetch, n_blocks, block, wkv_b, positions,
            kv_len, cfg, absorbed=s <= ABSORBED_MAX_QUERIES,
        )
    x = x + out.reshape(b, s, h * cfg.v_head_dim) @ lp["wo"]
    return x, cache_k


# ---------------------------------------------------------------------------
# FFN: dense SwiGLU, and the dropless routed experts
# ---------------------------------------------------------------------------


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def _task_block(pairs: int, experts: int) -> int:
    """Rows of one expert a block task computes: about the mean load of
    an expert, a power of two in [8, 256]."""
    mean = max(1, pairs // experts)
    return min(256, max(8, 1 << (mean - 1).bit_length()))


def task_map(counts, block: int, max_tasks: int):
    """The block tasks of `routed_experts`, known before its loop
    starts: how many there are and, for task i of `max_tasks` (a static
    bound on that number), its expert, the first sorted row it computes
    and how many of its `block` rows are that expert's. An expert with `counts[e]` pairs
    has ceil(counts[e] / block) tasks, in expert order; `task_end`
    holds their running count, and a task's expert is the number of
    entries of it the task's index has reached: one [max_tasks, E]
    compare where a binary search inside the loop took a `while` of
    scalar slices a task. Integers only. Tasks past the last one (the
    loop never runs them) read as the last expert's."""
    e = counts.shape[0]
    starts = jnp.cumsum(counts) - counts
    n_tasks = (counts + block - 1) // block
    task_end = jnp.cumsum(n_tasks)
    i = jnp.arange(max_tasks, dtype=jnp.int32)
    ex = jnp.minimum(
        (task_end[None, :] <= i[:, None]).sum(1, dtype=jnp.int32), e - 1)
    j = i - (task_end[ex] - n_tasks[ex])
    return task_end[-1], ex, starts[ex] + j * block, counts[ex] - j * block


def routed_experts(xt, idx, weight, valid, banks, layer, cfg: MlaMoeConfig):
    """Every routed (token, expert) pair, no capacity and no drops.
    Pairs are sorted by expert; each block task multiplies up to
    `block` rows of ONE expert by that expert's three matrices, so the
    work is pairs/block + at most one task an expert, and an expert no
    valid token chose is never read. Which expert, which rows and how
    many of them a task keeps come from `task_map`, before the loop.
    `banks` are the STACKED expert matrices `[layers, E, ..]`, indexed
    `[layer, expert]` inside the task: sliced out a layer first, XLA
    would copy a layer's whole bank (1.2 GB at the published widths)
    in front of the loop.
    Returns (out [T, D], stats int32 [3]: experts hit, largest load,
    pairs)."""
    t, d = xt.shape
    k, e = idx.shape[1], cfg.num_experts
    pairs = t * k
    block = _task_block(pairs, e)
    flat = idx.reshape(pairs)
    if valid is not None:  # padding and parked rows route nowhere
        flat = jnp.where(jnp.repeat(valid, k), flat, e)
    order = jnp.argsort(flat, stable=True)
    counts = jnp.zeros((e + 1,), jnp.int32).at[flat].add(1)[:e]
    n_tasks, task_ex, task_row0, task_rows = task_map(
        counts, block, pairs // block + e)
    xs = jnp.pad(xt[order // k], ((0, block), (0, 0)))  # [pairs+block, D]
    rows = jnp.arange(block)[:, None]

    def task(i, ys):
        ex, row0 = task_ex[i], task_row0[i]
        xb = jax.lax.dynamic_slice(xs, (row0, 0), (block, d))
        yb = _swiglu(xb, *(
            jax.lax.dynamic_slice(
                w, (layer, ex, 0, 0), (1, 1, *w.shape[2:])
            ).reshape(w.shape[2:])
            for w in banks
        ))
        old = jax.lax.dynamic_slice(ys, (row0, 0), (block, d))
        keep = rows < task_rows[i]  # the next expert's rows stay
        return jax.lax.dynamic_update_slice(
            ys, jnp.where(keep, yb, old), (row0, 0))

    ys = jax.lax.fori_loop(0, n_tasks, task, jnp.zeros_like(xs))
    y = jnp.zeros((pairs, d), xt.dtype).at[order].set(ys[:pairs])
    out = (
        y.reshape(t, k, d).astype(jnp.float32) * weight[..., None]
    ).sum(1).astype(xt.dtype)
    stats = jnp.stack([(counts > 0).sum(), counts.max(), counts.sum()])
    return out, stats.astype(jnp.int32)


def moe_ffn(x, lp, banks, layer, cfg: MlaMoeConfig, valid=None):
    """Sigmoid `noaux_tc` router (one group): choose by `s + b`, weigh
    by `s`; routed experts plus the shared experts on every token."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    scores = jax.nn.sigmoid(xt.astype(jnp.float32) @ lp["router"])
    _, idx = jax.lax.top_k(scores + lp["router_bias"], cfg.experts_per_token)
    weight = jnp.take_along_axis(scores, idx, axis=-1)
    weight = weight / (weight.sum(-1, keepdims=True) + 1e-20)
    weight = weight * cfg.routed_scaling
    routed, stats = routed_experts(
        xt, idx, weight, None if valid is None else valid.reshape(b * s),
        banks, layer, cfg,
    )
    shared = _swiglu(xt, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    return (routed + shared).reshape(b, s, d), stats


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def forward(
    params: Params,
    cfg: MlaMoeConfig,
    tokens: jnp.ndarray,  # [B, S]
    cache: Optional[Any] = None,  # KVCache or PagedKVCache of latents
    valid: Optional[jnp.ndarray] = None,  # [B, S] bool
    logit_idx: Optional[jnp.ndarray] = None,  # [B]: one position a row
    with_stats: bool = False,
    use_flash: Optional[bool] = None,
    flash_mesh: Any = None,
):
    """Same contract as `llama.forward`. `valid` marks real tokens:
    the others route to no expert. `logit_idx` computes the head at one
    position a row only (logits [B, 1, V]): at this vocabulary a
    [16, 512, V] float32 block is 4 GB. `with_stats` also returns the
    routing counts summed over the expert layers (int32 [3]: experts
    hit, largest load of an expert summed over layers, pairs).
    `use_flash` / `flash_mesh`: the engine's word on attention kernels
    for its mesh, as for llama (ops/attention.py `latent_prefill`)."""
    b, s = tokens.shape
    x = embed_lookup(params["embed"], tokens, cfg.jnp_dtype)
    if cache is not None:
        positions = cache.length[:, None] + jnp.arange(s)[None, :]
        plane, length = cache.k, cache.length
    else:
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        plane, length = None, None
    table = cache.table if isinstance(cache, PagedKVCache) else None
    kd = cfg.first_dense_layers

    def dense_body(carry, scanned):
        x, plane = carry
        lp, layer = scanned
        x, plane = attention_block(
            x, lp, cfg, positions, plane, length, table, layer, valid,
            use_flash, flash_mesh)
        n = common.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        x = x + _swiglu(n, lp["w_gate"], lp["w_up"], lp["w_down"])
        return (x, plane), None

    bank_names = ("w_gate", "w_up", "w_down")
    banks = tuple(params["layers"][n] for n in bank_names)
    per_layer = {
        k: v for k, v in params["layers"].items() if k not in bank_names}

    def expert_body(carry, scanned):
        x, plane = carry
        lp, layer = scanned
        x, plane = attention_block(
            x, lp, cfg, positions, plane, length, table, layer, valid,
            use_flash, flash_mesh)
        n = common.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        out, stats = moe_ffn(n, lp, banks, layer - kd, cfg, valid)
        return (x + out, plane), stats

    (x, plane), _ = jax.lax.scan(
        dense_body, (x, plane), (params["dense"], jnp.arange(kd)))
    (x, plane), stats = jax.lax.scan(
        expert_body, (x, plane),
        (per_layer, jnp.arange(kd, cfg.num_layers)))
    new_cache = (
        None if cache is None
        else cache._replace(k=plane, length=cache.length + s)
    )
    if logit_idx is not None:
        x = jnp.take_along_axis(x, logit_idx[:, None, None], axis=1)
    x = common.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = (x @ params["lm_head"].astype(cfg.jnp_dtype)).astype(jnp.float32)
    if with_stats:
        return logits, new_cache, stats.sum(0)
    return logits, new_cache


def num_params(cfg: MlaMoeConfig) -> int:
    return sum(math.prod(shape) for _, shape, _, _ in leaf_recipe(cfg)) + (
        cfg.num_layers * (2 * cfg.hidden_dim + cfg.kv_lora_rank)
        + cfg.hidden_dim
    )
