"""GQA decoder with a learned sparse-attention indexer and softmax-routed
experts (the language model of Keye-VL-2.0-30B-A3B, `model_type:
KeyeVL2`): a family beside llama, moe and mla_moe, composed of theirs.

What it takes from the others, and what is its own:

- **The cache holds three kinds of state a token a layer**: K and V per
  KV head, as the dense family, and ONE indexer key (`cfg.kv_planes`
  names three planes; `KVCache.extra` holds the third). Pages, reuse,
  CoW, the admission mini cache and `kv_cache_dtype` carry it through
  `llama.map_planes`. The planes are loop-carried through the layer
  scan and indexed `[layer, ...]` in place, contiguous and paged alike,
  as in mla_moe. The decode step's attention never streams indexer
  bytes and the index-score walk never streams K/V bytes: that is what
  a plane of its own is for.
- **The indexer is DeepSeek-V3.2's** (`ops/indexer.py`, shared with
  mla_moe), with its queries from the normed hidden states (there is
  no q-compression) and RoPE on the first `index_rope_dim` values.
- **Selection inside grouped attention**, both ways. A decode step
  scores the row's indexer plane to `kv_len` block of pages by block,
  takes the exact top-k and gathers those tokens' K and V by `[layer,
  page, offset]`; a chunk or suffix walks the keys block by block with
  an online softmax under the queries' `[queries, keys]` selection,
  stopped at the last valid key, so no `[.., S, S_max]` score tensor
  ever exists. Both are XLA (`gqa_attention`); a Pallas walk by token
  index is ROADMAP's.
- **The FFN is mla_moe's dropless routed experts** (`routed_experts`,
  `task_map`) behind its router in the softmax form (`route`): every
  layer an expert layer, no shared expert, no bias, groups or scaling.
  On a TPU they run as the grouped SwiGLU kernel (`ops/experts.py`),
  the one Pallas kernel of this family; `forward` hands `moe_ffn` the
  engine's `use_flash` / `flash_mesh` for it.
- Per-head RMSNorm on q and k before RoPE (`qk_norm`): the config has no
  key for it; it is the convention of the Qwen3-MoE-shaped key set the
  config uses. `mrope_section` gives each rotary frequency one of three
  position streams, which coincide for text: plain RoPE at the token's
  position. The vision tower is not served.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ggrmcp_tpu.models import common, mla_moe
from ggrmcp_tpu.models.llama import (  # noqa: F401
    KVCache,
    LlamaConfig,
    PagedKVCache,
    activation_spec,
    cache_planes,
    plane_io,
    with_planes,
)
from ggrmcp_tpu.ops import attention as attn_ops
from ggrmcp_tpu.ops import indexer
from ggrmcp_tpu.ops.quant import embed_lookup
from ggrmcp_tpu.ops.rope import apply_rope

Params = common.Params

# What the batcher may ask of this family (serving/batching.py reads
# them off the module; models/mla_moe.py says what each means). The
# counts a step returns are mla_moe's, under the same names, so the
# same ServingStats counters are stamped.
HEAD_AT_INDEX = True
ROUTING_STATS = mla_moe.ROUTING_STATS
DEEP_GRID_CHUNKS = mla_moe.DEEP_GRID_CHUNKS
# No ARENA_BY_LAYER: the admission programs put and view the arena in
# one scatter and one gather over the layer axis, as the dense family's.
# A layer at a time (mla_moe's way) read 5-7% slower in this family's
# cell with the same memory (PERF.md, PR 37): K and V a head are the
# dense family's planes, and the key's plane is a ninth of a page.


def admission_rows(cfg) -> Optional[int]:
    """One row an admission call where the model selects, as
    `mla_moe.admission_rows`: a row's mini cache is 0.45 GB at the
    published widths beside ~13 GB resident."""
    return 1 if cfg.index_topk else None


@dataclasses.dataclass(frozen=True)
class KeyeConfig(LlamaConfig):
    """`ffn_dim` keeps the published `intermediate_size` (6144) and
    sizes nothing: every layer is an expert layer."""

    name: str = "keye"
    norm_eps: float = 1e-6
    rope_theta: float = 1e7
    # Per-head RMSNorm on q and k before RoPE (assumed: module docstring).
    qk_norm: bool = True
    # The indexer of every layer (`sa_config`): `index_heads` query
    # heads of `index_head_dim` score each cached token's ONE key, and
    # a query attends its `index_topk` best keys. 0: no indexer.
    index_heads: int = 16
    index_head_dim: int = 64
    index_topk: int = 2048
    index_rope_dim: int = 32  # assumed: DeepSeek-V3.2's half
    num_experts: int = 128
    experts_per_token: int = 8
    expert_ffn_dim: int = 768
    # What `mla_moe.route` / `routed_experts` read of a config.
    router_scoring: str = "softmax"
    routed_scaling: float = 1.0
    num_shared_experts: int = 0
    n_group: int = 1
    topk_group: int = 1
    experts_held: Optional[tuple] = None

    @property
    def kv_planes(self) -> tuple:
        """K and V per KV head and, where the model has an indexer, the
        token's indexer key: a third kind of state in every page. Its
        plane is as wide as the next multiple of the TPU's 128 lanes
        (64 -> 128, zeros at the end), as the latent family's: at 64 the
        compiler carries the arena through the layer loop with the page
        axis minor-most and copies all of it into row-major order in
        every layer of every tick (seen in the tick compiled for a
        described v5e, PERF.md, PR 37)."""
        kv = ((self.num_kv_heads, self.head_dim),) * 2
        if not self.index_topk:
            return kv
        return kv + ((-(-self.index_head_dim // 128) * 128,),)

    @property
    def num_experts_held(self) -> int:
        return self.num_experts

    @property
    def num_expert_layers(self) -> int:  # the batcher's `layer_steps`
        return self.num_layers


# Kwai-Keye/Keye-VL-2.0-30B-A3B `config.json`, the language model.
_KEYE = dict(
    vocab_size=151936, hidden_dim=2048, num_heads=32, num_kv_heads=4,
    head_dim=128, ffn_dim=6144, max_seq_len=262144,
)

CONFIGS: dict[str, KeyeConfig] = {
    # As published: 48 layers, never loaded here.
    "keye-vl-2.0-30b-a3b": KeyeConfig(
        name="keye-vl-2.0-30b-a3b", num_layers=48, **_KEYE),
    # The same widths at the depth one v5e chip holds in bf16 beside
    # its cache: 6 of 48 layers, all 128 experts, the whole vocabulary
    # (4,375M parameters, 8.75 GB): the first of eight pipeline stages.
    "keye-vl-2.0-30b-a3b-6l": KeyeConfig(
        name="keye-vl-2.0-30b-a3b-6l", num_layers=6, **_KEYE),
    # Every mechanism live at a size for the CPU tests: 4 KV heads under
    # 8 query heads, an `index_topk` below the tests' contexts, 16
    # experts of which 4 a token.
    "tiny-keye": KeyeConfig(
        name="tiny-keye", vocab_size=512, hidden_dim=128, num_layers=3,
        num_heads=8, num_kv_heads=4, head_dim=16, ffn_dim=256,
        max_seq_len=1024, rope_theta=10000.0, index_heads=4,
        index_head_dim=32, index_topk=16, index_rope_dim=16,
        num_experts=16, experts_per_token=4, expert_ffn_dim=64,
        dtype="float32",
    ),
}


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def leaf_recipe(cfg: KeyeConfig) -> list:
    """Every drawn leaf, in draw order: (path, shape, scale, dtype
    name), drawn as `mla_moe.leaf_recipe` says. Norm weights are ones
    (the indexer's LayerNorm bias zeros) and not drawn. The benchmark's
    reference repeats this recipe from its own copy of the list."""
    d, n, e, f = (
        cfg.hidden_dim, cfg.num_layers, cfg.num_experts, cfg.expert_ffn_dim)
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    attn = {
        "wq": ((d, h * hd), d**-0.5), "wk": ((d, kvh * hd), d**-0.5),
        "wv": ((d, kvh * hd), d**-0.5),
        "wo": ((h * hd, d), (h * hd) ** -0.5),
    }
    if cfg.index_topk:
        attn.update(
            idx_wq=((d, cfg.index_heads * cfg.index_head_dim), d**-0.5),
            idx_wk=((d, cfg.index_head_dim), d**-0.5),
            idx_ww=((d, cfg.index_heads), d**-0.5))
    return [
        (("embed",), (cfg.vocab_size, d), 0.02, cfg.dtype),
        *((("layers", name), (n, *shape), scale, cfg.dtype)
          for name, (shape, scale) in attn.items()),
        (("layers", "router"), (n, d, e), d**-0.5, "float32"),
        (("layers", "w_gate"), (n, e, d, f), d**-0.5, cfg.dtype),
        (("layers", "w_up"), (n, e, d, f), d**-0.5, cfg.dtype),
        (("layers", "w_down"), (n, e, f, d), f**-0.5, cfg.dtype),
        (("lm_head",), (d, cfg.vocab_size), d**-0.5, cfg.dtype),
    ]


def _norm_shapes(cfg: KeyeConfig) -> dict:
    """The undrawn leaves of a layer: name -> (width, fill)."""
    out = {"attn_norm": (cfg.hidden_dim, 1), "mlp_norm": (cfg.hidden_dim, 1)}
    if cfg.qk_norm:
        out.update(q_norm=(cfg.head_dim, 1), k_norm=(cfg.head_dim, 1))
    if cfg.index_topk:
        out.update(idx_k_norm=(cfg.index_head_dim, 1),
                   idx_k_bias=(cfg.index_head_dim, 0))
    return out


def init_params(key: jax.Array, cfg: KeyeConfig) -> Params:
    recipe = leaf_recipe(cfg)
    params: Params = {"layers": {}}
    for k, (path, shape, scale, leaf_dtype) in zip(
        jax.random.split(key, len(recipe)), recipe
    ):
        node = params
        for name in path[:-1]:
            node = node[name]
        node[path[-1]] = (
            jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32)
            * scale
        ).astype(leaf_dtype)
    for name, (width, fill) in _norm_shapes(cfg).items():
        params["layers"][name] = jnp.full(
            (cfg.num_layers, width), fill, cfg.jnp_dtype)
    params["final_norm"] = jnp.ones((cfg.hidden_dim,), cfg.jnp_dtype)
    return params


def param_specs(cfg: KeyeConfig) -> Params:
    """Heads over `tensor`, as the dense family; the experts whole on
    every chip (their exchange over a mesh axis is not built). A mesh
    of more than one device is refused for a model with an indexer
    (engine._MESH_REFUSALS)."""
    layers = {
        "wq": P(None, None, "tensor"), "wk": P(None, None, "tensor"),
        "wv": P(None, None, "tensor"), "wo": P(None, "tensor", None),
        "router": P(None, None, None),
        "w_gate": P(None, None, None, "tensor"),
        "w_up": P(None, None, None, "tensor"),
        "w_down": P(None, None, "tensor", None),
        **{name: P(None, None) for name in _norm_shapes(cfg)},
    }
    if cfg.index_topk:
        layers.update({
            name: P(None, None, None)
            for name in ("idx_wq", "idx_wk", "idx_ww")})
    return {
        "embed": P("tensor", None), "layers": layers,
        "final_norm": P(None), "lm_head": P(None, "tensor"),
    }


def _plane_specs(rows) -> tuple:
    kv = P(None, rows, None, "tensor", None)
    return kv, kv, (P(None, rows, None, None),)


def cache_specs() -> KVCache:
    """K and V as the dense family's; the indexer keys whole on every
    chip."""
    rows = ("data", "fsdp")
    k, v, extra = _plane_specs(rows)
    return KVCache(k=k, v=v, length=P(rows), extra=extra)


def paged_cache_specs() -> PagedKVCache:
    k, v, extra = _plane_specs(None)
    return PagedKVCache(k=k, v=v, table=P(), length=P(), extra=extra)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def _key_block(b: int, s: int, h: int, s_keys: int, page: int) -> int:
    """Keys a block of the walk: bounds its `[B, H, S, block]` float32
    scores to tens of MB at the published widths (a row of 512 queries
    x 32 heads on 512 keys is 33 MB)."""
    block = 2048 if b * s * h <= 64 * 32 else 512
    while block > page and (s_keys % block or block % page):
        block //= 2
    return block if block > page else page


def gqa_attention(q, fetch, n_blocks, block: int, q_pos, kv_len, kvh: int,
                  key_pos=None, allowed=None):
    """Causal softmax attention of the step's queries `[B, S, H, Dh]`
    over keys and values of `kvh` KV heads, block of keys by block
    (`fetch(i)` -> K and V `[B, block, KVH, Dh]`) with a running
    maximum and sum in float32: the `[B, H, S, block]` scores of one
    block are all that ever exists. Query head h reads KV head
    `h // (H / KVH)`. A block's keys are the positions `i * block ..`
    unless `key_pos(i)` `[B, block]` says which they are (a block
    gathered by token index); `allowed(i)` `[B, S, block]` narrows what
    each query may see to its selection. Returns `[B, S, H, Dh]`."""
    b, s, h, hd = q.shape
    f32 = jnp.float32
    scale = 1.0 / math.sqrt(hd)
    g = h // kvh
    qg = q.reshape(b, s, kvh, g, hd)

    def body(i, carry):
        m, l, acc = carry
        k, v = fetch(i)
        scores = jnp.einsum(
            "bsngd,bknd->bngsk", qg, k, preferred_element_type=f32)
        if key_pos is None:
            k_pos = (i * block + jnp.arange(block))[None, None, :]
        else:
            k_pos = key_pos(i)[:, None, :]
        seen = (k_pos <= q_pos[:, :, None]) & (
            k_pos < kv_len[:, None, None])  # [B, S, block]
        if allowed is not None:
            seen &= allowed(i)
        scores = jnp.where(seen[:, None, None], scores * scale, -1e30)
        m_new = jnp.maximum(m, scores.max(-1))
        p = jnp.exp(scores - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(-1)
        pv = jnp.einsum(
            "bngsk,bknd->bsngd", p.astype(v.dtype), v,
            preferred_element_type=f32)
        acc = acc * alpha.transpose(0, 3, 1, 2)[..., None] + pv
        return m_new, l, acc

    init = (
        jnp.full((b, kvh, g, s), -1e30, f32), jnp.zeros((b, kvh, g, s), f32),
        jnp.zeros((b, s, kvh, g, hd), f32),
    )
    _, l, acc = jax.lax.fori_loop(0, n_blocks, body, init)
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
    return out.reshape(b, s, h, hd).astype(q.dtype)


def attention_block(
    x, lp, cfg: KeyeConfig, positions, planes, cache_len, page_table,
    layer, valid=None,
):
    """Pre-norm grouped attention with residual, under the indexer's
    selection where the model has one. `planes` are the WHOLE cache
    planes, loop-carried (`llama.cache_planes` order: K and V `[L, B,
    S_max, KVH, Dh]`, contiguous, or `[L, N, P, KVH, Dh]` with
    `page_table`; then the indexer's keys `[.., width]`); this layer
    writes and reads them at `[layer, ...]` in place. None = no cache
    (the step's own tokens are the keys). `valid` [B, S] marks the real
    queries: the walk stops at the last key any of THEM may see.

    With an indexer and a cache wider than `index_topk` a query attends
    its selected keys only, as `mla_moe.attention_block` says of its
    two paths: a decode step gathers them by token index, a chunk or a
    suffix masks the block walk. Returns (x + attn, planes, counts):
    the last three of ROUTING_STATS, read off what this call's sparse
    path made for its real queries."""
    b, s, _ = x.shape
    h, kvh, hd, topk = (
        cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.index_topk)

    normed = common.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q = (normed @ lp["wq"]).reshape(b, s, h, hd)
    k = (normed @ lp["wk"]).reshape(b, s, kvh, hd)
    v = (normed @ lp["wv"]).reshape(b, s, kvh, hd)
    if cfg.qk_norm:
        q = common.rms_norm(q, lp["q_norm"], cfg.norm_eps)
        k = common.rms_norm(k, lp["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_scaling)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_scaling)
    if topk:
        q_i, k_i, w_i = indexer.indexer_inputs(
            normed, normed, lp, positions, heads=cfg.index_heads,
            width=cfg.index_head_dim, rope_dim=cfg.index_rope_dim,
            theta=cfg.rope_theta, scaling=cfg.rope_scaling,
            eps=cfg.norm_eps)

    read_at = None
    counts = jnp.zeros((3,), jnp.int32)

    count = indexer.selection_counts

    def cut(t, row):
        return t if row is None else jax.lax.dynamic_slice_in_dim(t, row, 1, 0)

    if planes is None:
        block = min(s, 512)
        pad = -s % block
        s_keys = s + pad
        n_blocks = s_keys // block
        kv_len = jnp.full((b,), s, jnp.int32)
        last = positions

        def own(t):  # the step's own tokens as the keys
            t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            return lambda i, row=None: jax.lax.dynamic_slice_in_dim(
                cut(t, row), i * block, block, 1)

        fetch_k, fetch_v = own(k), own(v)
        fetch_idx = own(k_i) if topk else None
    else:
        io = plane_io(planes, page_table, layer, cache_len, s, x.dtype)
        s_keys, p_sz, read_at = io.s_keys, io.p_sz, io.read_at
        new = (k, v)
        if topk:  # the token's indexer key, to its plane's width
            new += (jnp.pad(k_i, ((0, 0), (0, 0), (
                0, cfg.kv_planes[2][0] - cfg.index_head_dim))),)
        planes = tuple(io.put(arena, val) for arena, val in zip(planes, new))
        block = _key_block(b, s, h, s_keys, p_sz)
        kv_len = cache_len + s
        last = positions if valid is None else jnp.where(valid, positions, -1)
        n_blocks = jnp.clip(
            (jnp.max(last) + block) // block, 0, s_keys // block)

        def read_block(arena, i, row):
            return io.read_block(arena, i, row, block)

        def fetch_k(i, row=None):
            return read_block(planes[0], i, row)

        def fetch_v(i, row=None):
            return read_block(planes[1], i, row)

        def fetch_idx(i, row=None):
            return read_block(planes[2], i, row)[..., :cfg.index_head_dim]

    if topk and s_keys > topk and s == 1 and read_at is not None:
        # A decode step: exact top-k, ties to the lower position
        # (lax.top_k's order), then the chosen tokens' K and V by token
        # index; a row no longer than `topk` reads its keys in order.
        attn_ops.dispatch_counts["sparse_gqa_decode"] += 1
        scores = indexer.index_scores(
            q_i, w_i, fetch_idx, n_blocks, block, s_keys, positions, kv_len)
        ran = (kv_len > topk)[:, None]
        best, picked = jax.lax.top_k(scores[:, 0], topk)
        counts = count(
            ran, valid, best[:, None] > -jnp.inf, scores > -jnp.inf)
        picked = jnp.where(ran, picked, jnp.arange(topk)[None])
        chosen = read_at(planes[0], picked), read_at(planes[1], picked)
        live = jnp.arange(topk)[None] < jnp.minimum(kv_len, topk)[:, None]
        picked = jnp.where(live, picked, jnp.iinfo(jnp.int32).max)
        out = gqa_attention(
            q, lambda i: chosen, 1, topk, positions, kv_len, kvh,
            key_pos=lambda i: picked)
    elif topk and s_keys > topk:
        # A chunk or a suffix: a selection a query, `[S, s_keys]` bool,
        # the set the decode step gathers; made and walked a row at a
        # time, each to its own last key, so the index scores, their
        # image under the selection's passes and the score blocks stay
        # one row's.
        attn_ops.dispatch_counts["sparse_gqa_chunk"] += 1

        def row_attention(row):
            n_row = jnp.clip(
                (jnp.max(cut(last, row)) + block) // block, 0,
                s_keys // block)
            scores = indexer.index_scores(
                cut(q_i, row), cut(w_i, row), lambda i: fetch_idx(i, row),
                n_row, block, s_keys, cut(positions, row), cut(kv_len, row))
            mask = indexer.selection_mask(scores, topk, reach=n_row * block)
            scored = scores > -jnp.inf
            tally = count(
                scored.sum(-1) > topk,
                None if valid is None else cut(valid, row), mask, scored)
            out = gqa_attention(
                cut(q, row), lambda i: (fetch_k(i, row), fetch_v(i, row)),
                n_row, block, cut(positions, row), cut(kv_len, row), kvh,
                allowed=lambda i: jax.lax.dynamic_slice_in_dim(
                    mask, i * block, block, 2))
            return out, tally

        outs, tallies = zip(*(
            row_attention(row) for row in ([None] if b == 1 else range(b))))
        counts = sum(tallies)
        out = jnp.concatenate(outs)
    else:
        out = gqa_attention(
            q, lambda i: (fetch_k(i), fetch_v(i)), n_blocks, block,
            positions, kv_len, kvh)
    x = x + out.reshape(b, s, h * hd) @ lp["wo"]
    return x, planes, counts


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def forward(
    params: Params,
    cfg: KeyeConfig,
    tokens: jnp.ndarray,  # [B, S]
    cache: Optional[Any] = None,  # KVCache or PagedKVCache, three planes
    valid: Optional[jnp.ndarray] = None,  # [B, S] bool
    logit_idx: Optional[jnp.ndarray] = None,  # [B]: one position a row
    with_stats: bool = False,
    use_flash: Optional[bool] = None,
    flash_mesh: Any = None,
):
    """`mla_moe.forward`'s contract (`valid`, `logit_idx`,
    `with_stats`: the same seven counts summed over the layers).
    `use_flash` / `flash_mesh` reach the experts alone
    (`mla_moe.moe_ffn`): this family's attention is XLA on every
    platform."""
    b, s = tokens.shape
    x = embed_lookup(params["embed"], tokens, cfg.jnp_dtype)
    if cache is not None:
        positions = cache.length[:, None] + jnp.arange(s)[None, :]
        planes, length = cache_planes(cache), cache.length
    else:
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        planes, length = None, None
    table = cache.table if isinstance(cache, PagedKVCache) else None

    bank_names = ("w_gate", "w_up", "w_down")
    banks = tuple(params["layers"][n] for n in bank_names)
    per_layer = {
        k: v for k, v in params["layers"].items() if k not in bank_names}

    def body(carry, scanned):
        x, planes = carry
        lp, layer = scanned
        x, planes, sel = attention_block(
            x, lp, cfg, positions, planes, length, table, layer, valid)
        n = common.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        out, stats = mla_moe.moe_ffn(
            n, lp, banks, layer, cfg, valid, use_flash, flash_mesh)
        return (x + out, planes), jnp.concatenate([stats, sel])

    (x, planes), stats = jax.lax.scan(
        body, (x, planes), (per_layer, jnp.arange(cfg.num_layers)))
    new_cache = None if cache is None else with_planes(
        cache, planes, length=cache.length + s)
    if logit_idx is not None:
        x = jnp.take_along_axis(x, logit_idx[:, None, None], axis=1)
    x = common.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = (x @ params["lm_head"].astype(cfg.jnp_dtype)).astype(jnp.float32)
    if with_stats:
        return logits, new_cache, stats.sum(0)
    return logits, new_cache


def num_params(cfg: KeyeConfig) -> int:
    norms = sum(width for width, _ in _norm_shapes(cfg).values())
    return sum(math.prod(shape) for _, shape, _, _ in leaf_recipe(cfg)) + (
        cfg.num_layers * norms + cfg.hidden_dim)
