"""Sparse Mixture-of-Experts decoder (Mixtral-style) with expert
parallelism over the `expert` mesh axis.

TPU-first design (no reference analogue — the Go gateway has no model
code; SURVEY.md §2.4 names EP as a first-class component of the new
framework):

- Same attention trunk as the Llama family (`llama.attention_block`) —
  GQA + RoPE, stacked [L, ...] weights, one `lax.scan` over layers,
  identical KV-cache contract so every serving path (engine, continuous
  batching, streaming) works unchanged.
- The FFN is a top-k routed expert bank using the GShard/Switch
  capacity-based dispatch formulation: routing decisions become one-hot
  dispatch/combine tensors and the whole MoE layer is four einsums.
  This is the MXU-friendly shape — no gathers, no ragged loops, static
  shapes under jit — and when the expert dimension of the weights is
  sharded over the `expert` axis, XLA lowers the dispatch/combine
  einsums to all-to-alls over ICI automatically.
- Tokens beyond an expert's capacity fall through the residual (their
  combine weight is zero) — standard token-dropping semantics; capacity
  is static per (B, S) bucket so compilation is bounded.
- `router_stats` exposes the load-balancing auxiliary loss
  (Switch-style fraction·probability dot product) for the training
  path.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ggrmcp_tpu.models import common
from ggrmcp_tpu.ops.quant import QuantizedArray, embed_lookup
from ggrmcp_tpu.ops.quant import matmul as qmatmul
# KV/activation layouts are identical to the dense family by design —
# the engine treats both families interchangeably, so the specs are
# re-exported rather than duplicated.
from ggrmcp_tpu.models.llama import (  # noqa: F401
    KVCache,
    LlamaConfig,
    activation_spec,
    attention_block,
    cache_specs,
    paged_cache_specs,
)

Params = common.Params


@dataclasses.dataclass(frozen=True)
class MoEConfig(LlamaConfig):
    name: str = "moe"
    num_experts: int = 8
    experts_per_token: int = 2
    capacity_factor: float = 1.25
    # Router auxiliary-loss weight (used by the training path only).
    router_aux_weight: float = 0.01


CONFIGS: dict[str, MoEConfig] = {
    "tiny-moe": MoEConfig(
        name="tiny-moe", vocab_size=512, hidden_dim=256, num_layers=2,
        num_heads=8, num_kv_heads=4, head_dim=32, ffn_dim=512,
        max_seq_len=1024, num_experts=4, experts_per_token=2,
        dtype="float32",
    ),
    "moe-2b": MoEConfig(
        name="moe-2b", vocab_size=32000, hidden_dim=2048, num_layers=12,
        num_heads=16, num_kv_heads=8, head_dim=128, ffn_dim=2816,
        max_seq_len=4096, num_experts=8, experts_per_token=2,
    ),
    # Mirrors the published Mixtral-8x7B architecture.
    "mixtral-8x7b": MoEConfig(
        name="mixtral-8x7b", vocab_size=32000, hidden_dim=4096,
        num_layers=32, num_heads=32, num_kv_heads=8, head_dim=128,
        ffn_dim=14336, max_seq_len=8192, rope_theta=1000000.0,
        num_experts=8, experts_per_token=2,
    ),
    # Windowed MoE (the Mixtral-8x7B-v0.1 config carried
    # sliding_window=4096): attention rides the shared windowed
    # attention_block, so kv_ring serving applies to MoE too — tiny
    # dims + a 16-key window keep the ring-wrap path CPU-testable.
    "tiny-moe-sw": MoEConfig(
        name="tiny-moe-sw", vocab_size=512, hidden_dim=256, num_layers=2,
        num_heads=8, num_kv_heads=4, head_dim=32, ffn_dim=512,
        max_seq_len=1024, num_experts=4, experts_per_token=2,
        sliding_window=16, dtype="float32",
    ),
}


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def init_params(key: jax.Array, cfg: MoEConfig) -> Params:
    dtype = cfg.jnp_dtype
    keys = jax.random.split(key, 10)
    d, l, e, f = cfg.hidden_dim, cfg.num_layers, cfg.num_experts, cfg.ffn_dim
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
            # Router in float32: routing decisions are precision-sensitive.
            "router": common.init_stacked(
                keys[3], l, (d, e), jnp.float32, scale
            ),
            "w_gate": common.init_stacked(keys[4], l, (e, d, f), dtype, scale),
            "w_up": common.init_stacked(keys[5], l, (e, d, f), dtype, scale),
            "w_down": common.init_stacked(
                keys[6], l, (e, f, d), dtype, scale=f**-0.5
            ),
        },
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": common.init_dense(keys[7], d, cfg.vocab_size, dtype, scale),
    }


def param_specs(cfg: MoEConfig) -> Params:
    """EP × TP: expert banks sharded over `expert` on the expert dim and
    `tensor` on the FFN dim; attention stays TP like the dense family."""
    return {
        "embed": P("tensor", None),
        "layers": {
            "attn_norm": P(None, None),
            "wqkv": P(None, None, "tensor"),
            "wo": P(None, "tensor", None),
            "mlp_norm": P(None, None),
            "router": P(None, None, None),
            "w_gate": P(None, "expert", None, "tensor"),
            "w_up": P(None, "expert", None, "tensor"),
            "w_down": P(None, "expert", "tensor", None),
        },
        "final_norm": P(None),
        "lm_head": P(None, "tensor"),
    }




# ---------------------------------------------------------------------------
# MoE FFN: capacity-based top-k dispatch
# ---------------------------------------------------------------------------


def _capacity(cfg: MoEConfig, num_tokens: int) -> int:
    """Static per-expert slot count for this shape bucket."""
    cap = int(
        cfg.capacity_factor * num_tokens * cfg.experts_per_token
        / cfg.num_experts
    )
    # Keep the einsum dims MXU-friendly and never zero.
    return max(8, -(-cap // 8) * 8)


def route(
    x: jnp.ndarray,  # [T, D] tokens
    router: jnp.ndarray,  # [D, E]
    cfg: MoEConfig,
    capacity: int,
    valid: Optional[jnp.ndarray] = None,  # [T] bool
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Top-k routing → (dispatch [T,E,C] bool, combine [T,E,C] float,
    router_probs [T,E]). Tokens past capacity get zero combine weight
    (they ride the residual). Invalid (padding) tokens neither consume
    expert slots nor contribute output — without this, a real token's
    routing would depend on how much padding the serving shape bucket
    added."""
    t = x.shape[0]
    e, k = cfg.num_experts, cfg.experts_per_token
    logits = x.astype(jnp.float32) @ router  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    topk_p, topk_i = jax.lax.top_k(probs, k)  # [T, K]
    topk_p = topk_p / jnp.maximum(topk_p.sum(-1, keepdims=True), 1e-9)

    onehot = jax.nn.one_hot(topk_i, e, dtype=jnp.int32)  # [T, K, E]
    if valid is not None:
        onehot = onehot * valid.astype(jnp.int32)[:, None, None]
    # Slot position of each (token, k) within its expert: cumulative
    # count over the flattened (k-major within token) assignment order.
    flat = onehot.reshape(t * k, e)
    pos = jnp.cumsum(flat, axis=0) - flat  # exclusive prefix count
    slot = (pos * flat).sum(-1).reshape(t, k)  # [T, K]
    kept = slot < capacity

    disp_tke = onehot.astype(jnp.float32) * kept[..., None]  # [T, K, E]
    slot_oh = jax.nn.one_hot(slot, capacity, dtype=jnp.float32)  # [T, K, C]
    dispatch = jnp.einsum("tke,tkc->tec", disp_tke, slot_oh)  # [T, E, C]
    combine = jnp.einsum(
        "tk,tke,tkc->tec", topk_p, disp_tke, slot_oh
    )  # [T, E, C]
    return dispatch, combine, probs


def moe_ffn(
    x: jnp.ndarray,  # [B, S, D] (already normed)
    layer_params: Params,
    cfg: MoEConfig,
    valid: Optional[jnp.ndarray] = None,  # [B, S] bool
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Routed SwiGLU expert bank. Returns (out [B,S,D], aux_loss [])."""
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    capacity = _capacity(cfg, t)

    dispatch, combine, probs = route(
        xt, layer_params["router"], cfg, capacity,
        valid.reshape(t) if valid is not None else None,
    )

    # Dispatch → per-expert batches. With w_* expert-sharded, XLA turns
    # these einsums into all-to-all + local matmul over the expert axis.
    expert_in = jnp.einsum(
        "tec,td->ecd", dispatch.astype(x.dtype), xt
    )  # [E, C, D]
    gate = jax.nn.silu(
        jnp.einsum("ecd,edf->ecf", expert_in, layer_params["w_gate"])
    )
    up = jnp.einsum("ecd,edf->ecf", expert_in, layer_params["w_up"])
    expert_out = jnp.einsum(
        "ecf,efd->ecd", gate * up, layer_params["w_down"]
    )  # [E, C, D]
    out = jnp.einsum(
        "tec,ecd->td", combine.astype(x.dtype), expert_out
    ).reshape(b, s, d)

    # Switch-style load-balance loss: E * <fraction routed, mean prob>,
    # averaged over valid tokens only.
    top1 = jax.nn.one_hot(jnp.argmax(probs, -1), cfg.num_experts)
    if valid is not None:
        w = valid.reshape(t, 1).astype(jnp.float32)
        denom = jnp.maximum(w.sum(), 1.0)
        frac = (top1 * w).sum(axis=0) / denom
        mean_prob = (probs * w).sum(axis=0) / denom
    else:
        frac = top1.mean(axis=0)
        mean_prob = probs.mean(axis=0)
    aux = cfg.num_experts * jnp.sum(frac * mean_prob)
    return out, aux


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _layer(
    x, layer_params, cfg, positions, cache_k, cache_v, cache_len, valid,
    use_flash=None, flash_mesh=None, ring=False,
):
    x, new_cache = attention_block(
        x, layer_params, cfg, positions, cache_k, cache_v, cache_len,
        use_flash=use_flash, flash_mesh=flash_mesh, ring=ring,
    )
    normed = common.rms_norm(x, layer_params["mlp_norm"], cfg.norm_eps)
    ffn_out, aux = moe_ffn(normed, layer_params, cfg, valid)
    return x + ffn_out, new_cache, aux


def forward(
    params: Params,
    cfg: MoEConfig,
    tokens: jnp.ndarray,  # [B, S]
    cache: Optional[KVCache] = None,
    valid: Optional[jnp.ndarray] = None,  # [B, S] bool
    use_flash: Optional[bool] = None,
    flash_mesh=None,
    ring: bool = False,
) -> tuple[jnp.ndarray, Optional[KVCache]]:
    """Same contract as `llama.forward` — the engines treat both
    families interchangeably. `valid` marks real (non-padding) tokens
    so padding never competes for expert capacity."""
    logits, cache, _ = forward_with_aux(
        params, cfg, tokens, cache, valid, use_flash=use_flash,
        flash_mesh=flash_mesh, ring=ring,
    )
    return logits, cache


def forward_with_aux(
    params: Params,
    cfg: MoEConfig,
    tokens: jnp.ndarray,
    cache: Optional[KVCache] = None,
    valid: Optional[jnp.ndarray] = None,
    use_flash: Optional[bool] = None,
    flash_mesh=None,
    ring: bool = False,
) -> tuple[jnp.ndarray, Optional[KVCache], jnp.ndarray]:
    """Forward returning the mean router load-balance loss (training)."""
    b, s = tokens.shape
    x = embed_lookup(params["embed"], tokens, cfg.jnp_dtype)

    if cache is not None:
        positions = cache.length[:, None] + jnp.arange(s)[None, :]
    else:
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))

    layers = params["layers"]

    if cache is None:

        def body(x, layer_params):
            x, _, aux = _layer(
                x, layer_params, cfg, positions, None, None, None, valid,
                use_flash=use_flash, flash_mesh=flash_mesh,
            )
            return x, aux

        x, auxes = jax.lax.scan(body, x, layers)
        new_cache = None
    else:

        def body(x, scanned):
            layer_params, ck, cv = scanned
            x, (ck, cv), aux = _layer(
                x, layer_params, cfg, positions, ck, cv, cache.length, valid,
                use_flash=use_flash, flash_mesh=flash_mesh, ring=ring,
            )
            return x, ((ck, cv), aux)

        x, ((new_k, new_v), auxes) = jax.lax.scan(
            body, x, (layers, cache.k, cache.v)
        )
        new_cache = KVCache(k=new_k, v=new_v, length=cache.length + s)

    x = common.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["lm_head"]
    if not isinstance(head, QuantizedArray):
        head = head.astype(cfg.jnp_dtype)
    logits = qmatmul(x, head)
    return logits.astype(jnp.float32), new_cache, auxes.mean()


def num_params(cfg: MoEConfig) -> int:
    d, l, v, e, f = (
        cfg.hidden_dim, cfg.num_layers, cfg.vocab_size, cfg.num_experts,
        cfg.ffn_dim,
    )
    qkv = d * (cfg.num_heads + 2 * cfg.num_kv_heads) * cfg.head_dim
    per_layer = (
        qkv + cfg.num_heads * cfg.head_dim * d + 2 * d  # attn + norms
        + d * e  # router
        + 3 * e * d * f  # expert banks
    )
    return v * d * 2 + l * per_layer + d


def active_params_per_token(cfg: MoEConfig) -> int:
    """Parameters touched per token (the MoE efficiency headline)."""
    d, e, f, k = cfg.hidden_dim, cfg.num_experts, cfg.ffn_dim, cfg.experts_per_token
    qkv = d * (cfg.num_heads + 2 * cfg.num_kv_heads) * cfg.head_dim
    per_layer = (
        qkv + cfg.num_heads * cfg.head_dim * d + 2 * d + d * e + 3 * k * d * f
    )
    return cfg.vocab_size * d * 2 + cfg.num_layers * per_layer + d
