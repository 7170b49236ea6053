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
  leaves VMEM, for both members (an indexer's selection a query is an
  operand of it); `latent_attention` stays what every other call runs
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
  shares its batch, and only experts that were hit are read. On a TPU
  the blocks are one Pallas kernel a layer (`ops/experts.py`: each
  expert hit streamed out of the stacked banks once, the next in
  flight while this one multiplies); elsewhere a `fori_loop` of three
  sliced matmuls a block, which the tests hold the kernel to.
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
    plane_io,
)
from ggrmcp_tpu.ops import attention as attn_ops
from ggrmcp_tpu.ops import experts as experts_ops
from ggrmcp_tpu.ops import indexer
from ggrmcp_tpu.ops.indexer import index_scores, selection_mask  # noqa: F401
from ggrmcp_tpu.ops.quant import embed_lookup
from ggrmcp_tpu.ops.rope import apply_rope, yarn_softmax_gain

Params = common.Params

# A step with at most this many queries a row (a decode step, a short
# re-admission suffix) attends in the absorbed form through the XLA
# walk. A longer one is a prefill chunk: over a contiguous plane on a
# TPU it attends in the absorbed form too, as one Pallas kernel
# (ops/attention.py `latent_prefill`: score block and accumulator in
# VMEM; with an indexer the queries' selection masks the score block
# there); where that kernel is not the call's kind (the CPU, a
# quantized or float8 plane, the paged arena, no cache) the walk
# attends it in the expanded form, masked likewise. Measured on a v5e
# (scripts/attn_form_bench.py;
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
# The admission programs scatter into and gather from the whole arena
# a layer at a time (batching._paged_put, llama.paged_view_layers): a
# page row of this family is one vector, and one scatter or gather
# over the layer axis makes XLA re-lay the whole arena out and back,
# two copies alive beside it (found on the chip, PERF.md, PR 33; the
# dense family's [.., KVH, Dh] rows have no such copy, and its 32
# layers read 5% slower a call a layer at a time).
ARENA_BY_LAYER = True
# The counts a step returns (`with_stats`), by name, each summed over
# its layers: distinct experts hit, the largest load of an expert,
# routed pairs computed here, routed pairs whose expert another chip
# holds; and what the sparse paths of `attention_block` saw themselves:
# entries of the selections that name a key, keys the indexer scored
# for those queries, and the queries that selected, a layer each.
ROUTING_STATS = (
    "experts_hit", "load_max", "pairs", "pairs_absent",
    "sparse_selected", "sparse_visible", "sparse_layer_steps",
)
# A cold prompt of more chunks than this is admitted alone, in arrival
# order (at the default chunk of 512: past 2,048 tokens). This family
# serves contexts of 6k-13k tokens, a whole prefill each: a group's
# rows all finish with its deepest, so whether two such prompts that
# arrived a millisecond apart were popped together would move the first
# one's first token by the other's prefill. llama names no such depth
# and keeps group admission (no cell measures llama past 2,048 tokens).
DEEP_GRID_CHUNKS = 4


def admission_rows(cfg) -> Optional[int]:
    """Rows one admission call over a full-width mini cache may take
    (the batcher asks; None = the whole pool). A model with an indexer
    takes one: its chunk attention selects a row at a time anyway
    (index scores, their image under the selection's passes and a
    [queries, keys] selection are a row's worth of memory each), so a
    group saves no pass over the weights
    worth its rows x 0.25 GB of mini cache beside 11.4 GB resident
    (PERF.md, PR 33)."""
    return 1 if cfg.index_topk else None


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
    # How the router scores: "sigmoid" (`noaux_tc`, with a selection
    # bias) or "softmax" (models/keye.py); `route`.
    router_scoring: str = "sigmoid"
    norm_eps: float = 1e-6
    rope_theta: float = 1e6
    # What `model_type: deepseek_v32` adds; each default is "absent",
    # which is kanana's value, and a member's keys alone pick its path.
    # Queries through a low-rank bottleneck with its own norm.
    q_lora_rank: int = 0
    # The sparse-attention indexer of every layer: `index_heads` query
    # heads of `index_head_dim` score each cached token's ONE indexer
    # key, and a query attends its `index_topk` best keys only. 0: no
    # indexer, every visible key is attended.
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # Group-limited routing: the experts in `n_group` equal groups, the
    # best `topk_group` groups stay in the choice.
    n_group: int = 1
    topk_group: int = 1
    # (first, count): the experts of `num_experts` whose weights this
    # chip holds, its share of an expert-parallel deployment. The
    # router keeps all `num_experts` outputs; a routed pair whose
    # expert is absent adds nothing here. None: every expert.
    experts_held: Optional[tuple] = None

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def kv_planes(self) -> tuple:
        """One latent plane; the V plane is empty, or holds the
        indexer's key of the token where the model has an indexer: a
        second kind of state that rides every page beside its latent.
        The latent plane is as wide
        as the next multiple of the TPU's 128 lanes (576 -> 640, zeros
        at the end): at 576 the compiler keeps the arena in a layout
        with the page axis minor-most, and every tick re-lays all of it
        out twice (PERF.md, PR 28)."""
        return (
            (-(-self.latent_dim // 128) * 128,),
            (self.index_head_dim if self.index_topk else 0,),
        )

    @property
    def softmax_scale(self) -> float:
        return yarn_softmax_gain(self.rope_scaling) / math.sqrt(
            self.qk_nope_head_dim + self.qk_rope_head_dim)

    @property
    def num_experts_held(self) -> int:
        return self.experts_held[1] if self.experts_held else self.num_experts

    @property
    def num_expert_layers(self) -> int:
        return self.num_layers - self.first_dense_layers


_KANANA = dict(
    vocab_size=128256, hidden_dim=2048, num_heads=32, num_kv_heads=32,
    head_dim=64, ffn_dim=6144, max_seq_len=32768,
)

# deepseek-ai/DeepSeek-V3.2 `config.json` (`model_type: deepseek_v32`).
# Its multi-token-prediction module (`num_nextn_predict_layers` 1) is
# not served: it does not enter the main model's logits. The indexer's
# keys are cached in the cache's dtype, not FP8, without the Hadamard
# rotation that precedes FP8 there (orthogonal: it cancels in q.k).
_DSV32 = dict(
    hidden_dim=7168, num_heads=128, num_kv_heads=128, head_dim=64,
    ffn_dim=18432, max_seq_len=163840, rope_theta=10000.0,
    rope_scaling=("yarn", 40.0, 4096, 32.0, 1.0), q_lora_rank=1536,
    index_heads=64, index_head_dim=128, index_topk=2048, num_experts=256,
    experts_per_token=8, expert_ffn_dim=2048, num_shared_experts=1,
    routed_scaling=2.5, n_group=8, topk_group=4,
)

CONFIGS: dict[str, MlaMoeConfig] = {
    # kakaocorp/kanana-2-30b-a3b-instruct-2601, config.json as published.
    "kanana-2-30b-a3b": MlaMoeConfig(
        name="kanana-2-30b-a3b", num_layers=48, **_KANANA),
    # The same widths at the depth one v5e chip holds in bf16 beside
    # its cache: the leading dense layer and 5 expert layers.
    "kanana-2-30b-a3b-6l": MlaMoeConfig(
        name="kanana-2-30b-a3b-6l", num_layers=6, **_KANANA),
    # As published: 3 dense + 58 expert layers, never loaded here.
    "deepseek-v3.2": MlaMoeConfig(
        name="deepseek-v3.2", vocab_size=129280, num_layers=61,
        first_dense_layers=3, **_DSV32),
    # One chip's share of a deployment in which 16 chips share each
    # layer (attention and the shared expert on every chip, 16 routed
    # experts a chip, the vocabulary in 8 slices), cut to a dense layer
    # and 4 expert layers: 4,635M parameters, 9.27 GB in bf16.
    "deepseek-v3.2-ep16-5l": MlaMoeConfig(
        name="deepseek-v3.2-ep16-5l", vocab_size=16160, num_layers=5,
        first_dense_layers=1, experts_held=(0, 16), **_DSV32),
    # Every mechanism of that member live at a size for the CPU tests:
    # q-compression, 4 groups of which 2 stay, experts held < experts,
    # YaRN, and an `index_topk` below the tests' contexts.
    "tiny-dsv32": MlaMoeConfig(
        name="tiny-dsv32", vocab_size=512, hidden_dim=128, num_layers=3,
        num_heads=4, num_kv_heads=4, head_dim=16, ffn_dim=256,
        max_seq_len=1024, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=16, v_head_dim=16, num_experts=16,
        experts_per_token=4, expert_ffn_dim=64, num_shared_experts=1,
        routed_scaling=2.5, rope_theta=10000.0,
        rope_scaling=("yarn", 8.0, 32, 32.0, 1.0), q_lora_rank=48,
        index_heads=4, index_head_dim=32, index_topk=16, n_group=4,
        topk_group=2, experts_held=(4, 8), dtype="float32",
    ),
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
    qr = cfg.q_lora_rank
    queries = {"wq": ((d, h * qk), d**-0.5)} if not qr else {
        "wq_a": ((d, qr), d**-0.5), "wq_b": ((qr, h * qk), qr**-0.5)}
    indexer = {} if not cfg.index_topk else {
        "idx_wq": ((qr, cfg.index_heads * cfg.index_head_dim), qr**-0.5),
        "idx_wk": ((d, cfg.index_head_dim), d**-0.5),
        "idx_ww": ((d, cfg.index_heads), d**-0.5),
    }
    return {
        **queries, **indexer,
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
    weights are ones (the indexer's LayerNorm bias zeros) and not
    drawn. The expert banks hold `experts_held` only, so a share's
    weights are its own draw, not a slice of the whole model's. The
    benchmark's references repeat this recipe from their own copies of
    the list."""
    d, e, eh = cfg.hidden_dim, cfg.num_experts, cfg.num_experts_held
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
        (("layers", "w_gate"), (km, eh, d, f), d**-0.5, cfg.dtype),
        (("layers", "w_up"), (km, eh, d, f), d**-0.5, cfg.dtype),
        (("layers", "w_down"), (km, eh, f, d), f**-0.5, cfg.dtype),
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
        if cfg.q_lora_rank:
            params[stack]["q_norm"] = jnp.ones((n, cfg.q_lora_rank), dtype)
        if cfg.index_topk:
            params[stack]["idx_k_norm"] = jnp.ones(
                (n, cfg.index_head_dim), dtype)
            params[stack]["idx_k_bias"] = jnp.zeros(
                (n, cfg.index_head_dim), dtype)
    params["final_norm"] = jnp.ones((d,), dtype)
    return params


def param_specs(cfg: MlaMoeConfig) -> Params:
    """Heads and FFN widths over `tensor`, as the dense family; the
    experts a chip holds (`cfg.experts_held`) stay whole on it: their
    exchange over a mesh axis is not built (ROADMAP)."""
    attn = {
        "attn_norm": P(None, None), "kv_norm": P(None, None),
        "mlp_norm": P(None, None),
        "wkv_a": P(None, None, None),
        "wkv_b": P(None, None, "tensor"), "wo": P(None, "tensor", None),
    }
    if cfg.q_lora_rank:
        attn.update(
            wq_a=P(None, None, None), q_norm=P(None, None),
            wq_b=P(None, None, "tensor"))
    else:
        attn["wq"] = P(None, None, "tensor")
    if cfg.index_topk:  # small, and every row reads all of it
        attn.update({
            name: P(None, None, None) for name in ("idx_wq", "idx_wk", "idx_ww")
        }, idx_k_norm=P(None, None), idx_k_bias=P(None, None))
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


def _key_block(b: int, s: int, h: int, s_keys: int, page: int) -> int:
    """Keys a block of the XLA walk (the prefill kernel has its own,
    ops/attention.py): bounds the walk's [B, H, S, block] float32
    scores in HBM to a few hundred MB at the published widths (16 rows
    x 512 queries of 32 heads, or a row of 128 heads)."""
    block = 2048 if b * s * h <= 512 * 32 else 512
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
    key_pos=None,  # block index -> [B, block] positions of its keys
    allowed=None,  # block index -> [B, S, block] bool: the selection
):
    """Causal softmax attention of the step's queries over the cached
    latents, block of keys by block of keys with a running maximum and
    sum (float32). `absorbed` picks the form; both give the same
    attention up to rounding. A block's keys are the positions
    `i * block ..` unless `key_pos` says which they are (a block
    gathered by token index); `allowed` narrows what each query may
    see to its selection. Returns [B, S, H, v_head_dim]."""
    b, s, h, nope = q_nope.shape
    rank, vd = cfg.kv_lora_rank, cfg.v_head_dim
    f32 = jnp.float32
    scale = cfg.softmax_scale
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
        if key_pos is None:
            k_pos = (i * block + jnp.arange(block))[None, None, :]
        else:
            k_pos = key_pos(i)[:, None, :]
        seen = (k_pos <= q_pos[:, :, None]) & (
            k_pos < kv_len[:, None, None])  # [B, S, block]
        if allowed is not None:
            seen &= allowed(i)
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


def indexer_inputs(c_q, normed, lp, cfg: MlaMoeConfig, positions):
    """`ops.indexer.indexer_inputs` as this family's members call it:
    queries from the compressed queries `c_q`, RoPE on the first
    `qk_rope_head_dim` values with the attention's frequencies."""
    return indexer.indexer_inputs(
        c_q, normed, lp, positions, heads=cfg.index_heads,
        width=cfg.index_head_dim, rope_dim=cfg.qk_rope_head_dim,
        theta=cfg.rope_theta, scaling=cfg.rope_scaling, eps=cfg.norm_eps)


def attention_block(
    x, lp, cfg: MlaMoeConfig, positions, planes, cache_len,
    page_table, layer, valid=None, use_flash=None, flash_mesh=None,
):
    """Pre-norm latent attention with residual. `planes` are the WHOLE
    cache planes, loop-carried: the latent plane `[L, B, S_max,
    latent]` (contiguous) or `[L, N, P, latent]` with `page_table`
    (paged) and, beside it, the indexer's plane of the same leading
    axes (width 0 where the model has no indexer); this layer writes
    and reads them at `[layer, ...]` in place. None = no cache (the
    step's own latents are the keys). `valid` [B, S] marks the
    real queries: the walk over the cache stops at the last key any of
    THEM may see, so a padding chunk of a chunk grid, or a tick in
    which the longest rows are parked, attends nothing it does not
    need (an all-padding step walks no block at all).

    With an indexer (`cfg.index_topk`) a query attends its selected
    keys only. A decode step (one query a row) scores the row's
    indexer keys to `kv_len`, takes the exact top-k and GATHERS those
    latents by token index, 2,048 x 640 values a row where the walk
    would read the whole context; the family's attention then runs
    over that one block. A chunk or a re-admission suffix has a
    selection a query: gathered, 512 queries x 2,048 latents would be
    1.3 GB a layer, so it reads the keys densely and masks each block
    with the query's selection (the same set): inside the prefill
    kernel, which takes the selection as an operand, where
    `latent_prefill` finds the call its kind (as for a model without
    an indexer: more than `ABSORBED_MAX_QUERIES` queries, a contiguous
    plane in the model's dtype, a TPU), else in the XLA walk, a row at
    a time. Where the keys do not outnumber `index_topk` nothing is
    selected.
    Returns (x + attn, planes, counts): int32 [3], read off the index
    scores and the selection this call made for its real queries (the
    last three of ROUTING_STATS); zeros where none selected."""
    b, s, _ = x.shape
    h, nope, rope = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    rank, topk = cfg.kv_lora_rank, cfg.index_topk

    normed = common.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    if cfg.q_lora_rank:
        c_q = common.rms_norm(normed @ lp["wq_a"], lp["q_norm"], cfg.norm_eps)
        q = c_q @ lp["wq_b"]
    else:
        q = normed @ lp["wq"]
    q = q.reshape(b, s, h, nope + rope)
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
    if topk:
        q_i, k_i, w_i = indexer_inputs(c_q, normed, lp, cfg, positions)

    read_at = None
    chunk = False  # a prefill chunk over a contiguous plane (below)
    counts = jnp.zeros((3,), jnp.int32)

    count = indexer.selection_counts

    if planes is None:
        pad = -s % min(s, 512)
        block = min(s, 512)
        s_keys = s + pad
        n_blocks = s_keys // block
        kv_len = jnp.full((b,), s, jnp.int32)

        last = positions

        def own(t):  # the step's own tokens as the keys
            t = jnp.pad(t, ((0, 0), (0, pad), (0, 0)))

            def fetch(i, row=None):
                rows = t if row is None else (
                    jax.lax.dynamic_slice_in_dim(t, row, 1, 0))
                return jax.lax.dynamic_slice_in_dim(rows, i * block, block, 1)

            return fetch

        fetch, fetch_idx = own(lat), own(k_i) if topk else None
    else:
        io = plane_io(planes, page_table, layer, cache_len, s, lat.dtype)
        s_keys, p_sz, read_at = io.s_keys, io.p_sz, io.read_at
        cache_k, cache_v = planes
        cache_k = io.put(cache_k, lat)
        if topk:  # the token's indexer key, in the page of its latent
            cache_v = io.put(cache_v, k_i)
        planes = (cache_k, cache_v)
        block = _key_block(b, s, h, s_keys, p_sz)
        kv_len = cache_len + s
        last = positions if valid is None else jnp.where(valid, positions, -1)
        n_blocks = jnp.clip(
            (jnp.max(last) + block) // block, 0, s_keys // block)
        chunk = (s > ABSORBED_MAX_QUERIES and page_table is None
                 and not io.quantized)

        def read_block(arena, i, row):
            return io.read_block(arena, i, row, block)

        def fetch(i, row=None):
            return read_block(cache_k, i, row)

        def fetch_idx(i, row=None):
            return read_block(cache_v, i, row)

    def chunk_kernel(allowed=None):
        """A prefill chunk over a contiguous plane (`chunk`), with its
        queries' selection if they have one: the absorbed form as one
        kernel where `latent_prefill` finds its kind (a TPU, the plane
        in the model's dtype), else None and the caller walks."""
        if not chunk:
            return None
        out = attn_ops.latent_prefill(
            absorbed_queries(q_nope, q_rope, wkv_b[..., :nope], lat.shape[-1]),
            cache_k, layer, cache_len, kv_len, jnp.max(last, axis=1), allowed,
            value_width=rank, scale=cfg.softmax_scale,
            use_flash=use_flash, flash_mesh=flash_mesh,
        )
        return out if out is None else jnp.einsum(
            "bshc,chd->bshd", out, wkv_b[..., nope:])

    absorbed = s <= ABSORBED_MAX_QUERIES
    if topk and s_keys > topk and s == 1 and read_at is not None:
        # A decode step. Exact top-k, ties to the lower position
        # (lax.top_k's order); a row no longer than `topk` reads its
        # keys in order and owes nothing to the indexer.
        attn_ops.dispatch_counts["sparse_decode"] += 1
        scores = index_scores(
            q_i, w_i, fetch_idx, n_blocks, block, s_keys, positions, kv_len)
        ran = (kv_len > topk)[:, None]
        best, picked = jax.lax.top_k(scores[:, 0], topk)
        counts = count(
            ran, valid, best[:, None] > -jnp.inf, scores > -jnp.inf)
        picked = jnp.where(ran, picked, jnp.arange(topk)[None])
        chosen = read_at(cache_k, picked)  # [B, topk, latent]
        live = jnp.arange(topk)[None] < jnp.minimum(kv_len, topk)[:, None]
        picked = jnp.where(live, picked, jnp.iinfo(jnp.int32).max)
        out = latent_attention(
            q_nope, q_rope, lambda i: chosen, 1, topk, wkv_b, positions,
            kv_len, cfg, absorbed, key_pos=lambda i: picked)
    elif topk and s_keys > topk:
        # A chunk or a suffix: a selection a query, [S, s_keys] bool,
        # the same set the decode step gathers. Selected a row at a
        # time, each to its own last key: the index scores and their
        # image under the selection's passes stay one row's.
        attn_ops.dispatch_counts["sparse_chunk"] += 1

        def cut(t, row):
            return t if row is None else (
                jax.lax.dynamic_slice_in_dim(t, row, 1, 0))

        def select(row):
            n_row = jnp.clip(
                (jnp.max(cut(last, row)) + block) // block, 0,
                s_keys // block)
            scores = index_scores(
                cut(q_i, row), cut(w_i, row), lambda i: fetch_idx(i, row),
                n_row, block, s_keys, cut(positions, row), cut(kv_len, row))
            # counted as far as the row's keys reach; a padding chunk
            # of a deep grid (no block to walk) selects nothing
            mask = selection_mask(scores, topk, reach=n_row * block)
            scored = scores > -jnp.inf
            return mask, n_row, count(
                scored.sum(-1) > topk,
                None if valid is None else cut(valid, row), mask, scored)

        def walk(row, mask, n_row):
            """The dense walk masked by the selection, its score blocks
            (heads x queries x block, float32, in HBM) one row's."""
            return latent_attention(
                cut(q_nope, row), cut(q_rope, row), lambda i: fetch(i, row),
                n_row, block, wkv_b, cut(positions, row), cut(kv_len, row),
                cfg, absorbed,
                allowed=lambda i: jax.lax.dynamic_slice_in_dim(
                    mask, i * block, block, 2))

        rows = [None] if b == 1 else range(b)
        masks, reach, tallies = zip(*(select(row) for row in rows))
        counts = sum(tallies)
        out = chunk_kernel(jnp.concatenate(masks))  # every row's tiles
        if out is None:
            out = jnp.concatenate([
                walk(*each) for each in zip(rows, masks, reach)])
    else:
        out = chunk_kernel()
        if out is None:
            out = latent_attention(
                q_nope, q_rope, fetch, n_blocks, block, wkv_b, positions,
                kv_len, cfg, absorbed)
    x = x + out.reshape(b, s, h * cfg.v_head_dim) @ lp["wo"]
    return x, planes, counts


# ---------------------------------------------------------------------------
# FFN: dense SwiGLU, and the dropless routed experts
# ---------------------------------------------------------------------------


def _swiglu(x, w_gate, w_up, w_down, act: str = "silu"):
    return (experts_ops.ACTIVATIONS[act](x @ w_gate) * (x @ w_up)) @ w_down


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


def _looped_tasks(xt, order, counts, banks, layer, k: int, block: int,
                  act: str = "silu"):
    """The block tasks as a `fori_loop` in XLA, three sliced matmuls a
    task over the sorted rows; a task's tail rows are the next
    expert's and stay (`keep`). Returns every pair's result in the
    pairs' own order `[pairs, D]`, zeros for a pair routed nowhere."""
    pairs, d, e = order.shape[0], xt.shape[1], counts.shape[0]
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
        ), act)
        old = jax.lax.dynamic_slice(ys, (row0, 0), (block, d))
        keep = rows < task_rows[i]  # the next expert's rows stay
        return jax.lax.dynamic_update_slice(
            ys, jnp.where(keep, yb, old), (row0, 0))

    ys = jax.lax.fori_loop(0, n_tasks, task, jnp.zeros_like(xs))
    return jnp.zeros((pairs, d), xt.dtype).at[order].set(ys[:pairs])


def _task_tiles(xt, order, counts, k: int, tile: int):
    """What `ops.experts.grouped_swiglu` takes of a step: its rows laid
    out a task a tile, gathered straight from the tokens (row r of task
    i is sorted row `task_row0[i] + r`; a task's tail and the tasks
    there are not are padding), and the map's count, experts and rows."""
    pairs, e = order.shape[0], counts.shape[0]
    max_tasks = min(pairs // tile + e, pairs)  # a task has a pair
    n_tasks, task_ex, task_row0, task_rows = task_map(counts, tile, max_tasks)
    at = jnp.minimum(task_row0[:, None] + jnp.arange(tile), pairs - 1)
    return xt[order[at.reshape(-1)] // k], n_tasks, task_ex, task_rows


def _grouped_tasks(xt, flat, order, counts, banks, layer, k: int, tile: int,
                   act: str = "silu"):
    """The block tasks as one kernel (`ops.experts.grouped_swiglu`),
    `tile` rows a task (`_task_tiles`; padding rows are computed or
    skipped, never read back). A pair's result is read back from where
    its sorted row landed: its expert's first task, then in order.
    Same contract as `_looped_tasks`."""
    pairs, e = order.shape[0], counts.shape[0]
    rows, n_tasks, task_ex, task_rows = _task_tiles(xt, order, counts, k, tile)
    ys = experts_ops.grouped_swiglu(
        rows, *banks, layer, n_tasks, task_ex, task_rows, block=tile, act=act)
    rank = jnp.zeros((pairs,), jnp.int32).at[order].set(
        jnp.arange(pairs, dtype=jnp.int32))
    starts = jnp.cumsum(counts) - counts
    tasks = (counts + tile - 1) // tile
    ex = jnp.minimum(flat, e - 1)
    landed = (jnp.cumsum(tasks) - tasks)[ex] * tile + rank - starts[ex]
    return jnp.where(
        (flat < e)[:, None], ys[jnp.clip(landed, 0, ys.shape[0] - 1)], 0)


def routed_experts(
    xt, idx, weight, valid, banks, layer, cfg: MlaMoeConfig,
    use_flash: Optional[bool] = None, flash_mesh: Any = None,
):
    """Every routed (token, expert) pair, no capacity and no drops.
    Pairs are sorted by expert; each block task multiplies up to
    `block` rows of ONE expert by that expert's three matrices, so the
    work is pairs/block + at most one task an expert, and an expert no
    valid token chose is never read. Which expert, which rows and how
    many of them a task keeps come from `task_map`, before the walk.
    `banks` are the STACKED expert matrices `[layers, E, ..]`, indexed
    `[layer, expert]` inside the task: sliced out a layer first, XLA
    would copy a layer's whole bank (1.2 GB at the published widths)
    in front of the walk.
    The tasks run as ONE Pallas kernel where the call is its kind
    (`ops.experts.grouped_experts`: a TPU, banks in the tokens'
    dtype, no mesh; `use_flash` / `flash_mesh` are the engine's word,
    as for the attention kernels): every expert hit is streamed once,
    the next in flight while this one multiplies, its tasks a tile of
    rows each. Everywhere else (the CPU) they are a `fori_loop` of
    three sliced matmuls a task, which is also what the tests hold the
    kernel to.
    Where the chip holds a share (`cfg.experts_held`), `banks` are its
    experts only and a pair routed to an absent expert goes nowhere,
    like padding: its part of the sum is another chip's.
    Returns (out [T, D], stats int32 [4]: experts hit, largest load,
    pairs computed here, pairs whose expert is absent)."""
    t, d = xt.shape
    k, e = idx.shape[1], cfg.num_experts_held
    pairs = t * k
    block = _task_block(pairs, cfg.num_experts)
    flat = idx.reshape(pairs)
    if cfg.experts_held:
        flat = flat - cfg.experts_held[0]
        flat = jnp.where((flat >= 0) & (flat < e), flat, e)
    if valid is not None:  # padding and parked rows route nowhere
        flat = jnp.where(jnp.repeat(valid, k), flat, e)
    order = jnp.argsort(flat, stable=True)
    counts = jnp.zeros((e + 1,), jnp.int32).at[flat].add(1)[:e]
    act = getattr(cfg, "expert_act", "silu")  # the gate's activation
    if experts_ops.grouped_experts(xt, banks, use_flash, flash_mesh):
        y = _grouped_tasks(
            xt, flat, order, counts, banks, layer, k,
            max(block, experts_ops.MIN_ROWS), act)
    else:
        y = _looped_tasks(xt, order, counts, banks, layer, k, block, act)
    out = (
        y.reshape(t, k, d).astype(jnp.float32) * weight[..., None]
    ).sum(1).astype(xt.dtype)
    routed = pairs if valid is None else valid.sum() * k
    stats = jnp.stack([
        (counts > 0).sum(), counts.max(), counts.sum(),
        routed - counts.sum()])
    return out, stats.astype(jnp.int32)


def choose_experts(choice, cfg: MlaMoeConfig):
    """[T, k] expert ids from the selection scores `s + b` [T, E]: the
    top k, and with `n_group` > 1 the top k inside the best
    `topk_group` groups only, a group of consecutive experts scoring
    the sum of its two largest entries."""
    if cfg.n_group > 1:
        groups = choice.reshape(choice.shape[0], cfg.n_group, -1)
        best = jax.lax.top_k(
            jax.lax.top_k(groups, 2)[0].sum(-1), cfg.topk_group)[1]
        kept = (best[..., None] == jnp.arange(cfg.n_group)).any(1)
        choice = jnp.where(
            kept[..., None], groups, -jnp.inf).reshape(choice.shape)
    return jax.lax.top_k(choice, cfg.experts_per_token)[1]


def route(xt, lp, cfg):
    """Each token's experts `[T, k]` and their float32 weights, by the
    config's `router_scoring`. "sigmoid" (`noaux_tc`): `s =
    sigmoid(x W_r)`, chosen by `s + b` (group-limited where the model
    has groups), weighed by `s`. "softmax": `p = softmax(x W_r)` over
    every expert, the top k of `p`, no bias. Either way the chosen
    weights are normalised to sum to 1 and scaled by
    `routed_scaling`."""
    logits = xt.astype(jnp.float32) @ lp["router"]
    if cfg.router_scoring == "softmax":
        scores = choice = jax.nn.softmax(logits, axis=-1)
    else:
        scores = jax.nn.sigmoid(logits)
        choice = scores + lp["router_bias"]
    idx = choose_experts(choice, cfg)
    weight = jnp.take_along_axis(scores, idx, axis=-1)
    weight = weight / (weight.sum(-1, keepdims=True) + 1e-20)
    return idx, weight * cfg.routed_scaling


def moe_ffn(
    x, lp, banks, layer, cfg, valid=None, use_flash=None, flash_mesh=None,
    routing=None,
):
    """The routed experts of every token (`route`, `routed_experts`),
    plus the shared experts where the model has any. `use_flash` /
    `flash_mesh`: the engine's word on kernels for its mesh. `routing`:
    the tokens' (experts, weights) where the caller's router read
    something else than `x` (models/smallthinker.py: the attention
    block's input) and has routed them already."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    idx, weight = route(xt, lp, cfg) if routing is None else routing
    out, stats = routed_experts(
        xt, idx, weight, None if valid is None else valid.reshape(b * s),
        banks, layer, cfg, use_flash, flash_mesh,
    )
    if cfg.num_shared_experts:
        out = out + _swiglu(xt, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    return out.reshape(b, s, d), stats


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
        plane, length = (cache.k, cache.v), cache.length
    else:
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        plane, length = None, None
    table = cache.table if isinstance(cache, PagedKVCache) else None
    kd = cfg.first_dense_layers

    # the selection's counts ride out of the scans only where asked
    # for and where the model selects
    sparse = with_stats and bool(cfg.index_topk)

    def dense_body(carry, scanned):
        x, plane = carry
        lp, layer = scanned
        x, plane, sel = attention_block(
            x, lp, cfg, positions, plane, length, table, layer, valid,
            use_flash, flash_mesh)
        n = common.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        x = x + _swiglu(n, lp["w_gate"], lp["w_up"], lp["w_down"])
        return (x, plane), sel if sparse else None

    bank_names = ("w_gate", "w_up", "w_down")
    banks = tuple(params["layers"][n] for n in bank_names)
    per_layer = {
        k: v for k, v in params["layers"].items() if k not in bank_names}

    def expert_body(carry, scanned):
        x, plane = carry
        lp, layer = scanned
        x, plane, sel = attention_block(
            x, lp, cfg, positions, plane, length, table, layer, valid,
            use_flash, flash_mesh)
        n = common.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        out, stats = moe_ffn(
            n, lp, banks, layer - kd, cfg, valid, use_flash, flash_mesh)
        return (x + out, plane), (
            jnp.concatenate([stats, sel]) if sparse else stats)

    (x, plane), dense_sel = jax.lax.scan(
        dense_body, (x, plane), (params["dense"], jnp.arange(kd)))
    (x, plane), stats = jax.lax.scan(
        expert_body, (x, plane),
        (per_layer, jnp.arange(kd, cfg.num_layers)))
    new_cache = (
        None if cache is None
        else cache._replace(k=plane[0], v=plane[1], length=cache.length + s)
    )
    if logit_idx is not None:
        x = jnp.take_along_axis(x, logit_idx[:, None, None], axis=1)
    x = common.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = (x @ params["lm_head"].astype(cfg.jnp_dtype)).astype(jnp.float32)
    if with_stats:
        stats = stats.sum(0)
        if sparse:  # the dense layers select too
            stats = stats.at[-3:].add(dense_sel.sum(0))
        else:
            stats = jnp.concatenate([stats, jnp.zeros((3,), jnp.int32)])
        return logits, new_cache, stats
    return logits, new_cache


def num_params(cfg: MlaMoeConfig) -> int:
    norms = 2 * cfg.hidden_dim + cfg.kv_lora_rank + cfg.q_lora_rank + (
        2 * cfg.index_head_dim if cfg.index_topk else 0)
    return sum(math.prod(shape) for _, shape, _, _ in leaf_recipe(cfg)) + (
        cfg.num_layers * norms + cfg.hidden_dim)
