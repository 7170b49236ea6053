"""GQA decoder of two kinds of layer and ReGLU experts routed from the
attention block's input (SmallThinker-21BA3B-Instruct, `model_name:
smallthinker_21b_instruct`): a family beside the others, composed of
theirs.

What it takes from the others, and what is its own:

- **Layer kinds** (`cfg.layer_period`, the published
  `sliding_window_layout` == `rope_layout`: one "full" layer to three
  "window" layers). A full layer attends every key and has NO
  positional encoding; a window layer attends the last
  `sliding_window` keys (itself included, `s > t - W` as
  `ops/attention.py` has it for Mistral) and turns q and k by RoPE.
  Both are `llama.attention_block` under the config of their kind
  (`kind_cfg`): the window masks, the prefill kernel, the paged-decode
  kernel and the no-rotary switch are the dense family's.
- **Two kinds of page** (`cfg.cache_kinds`, docs/paged_kv.md): the
  paged cache holds an arena and a block table for the full layers,
  which keep every position, and a second arena and table for the
  window layers (`PagedKVCache.window`), whose pages behind the window
  the host lets go (serving/pages.py). A layer reads and writes its own
  kind's arena at its index within the kind. A contiguous cache (the
  admission mini cache, `paged_kv` off) holds all layers in the model's
  order and keeps everything.
- **One `lax.scan` over the periods**, a period's four layers unrolled
  in its body: the stacked per-layer leaves ride as `[periods, 4, ..]`,
  the paged arenas are loop-carried and indexed `[layer, ...]` in
  place, a contiguous cache is scanned in and out a period.
- **The router reads the attention block's normed input** (the catalog:
  "router placed before attention"), so a layer's routing is known
  before its attention runs; it is `mla_moe.route` in the softmax form
  (softmax over all experts, top k, renormalised: equal to softmax over
  the top k's logits), handed past the attention to `mla_moe.moe_ffn`
  (`routing`). The experts are mla_moe's dropless `routed_experts` with
  the gate's activation `relu` (`cfg.expert_act`: ReGLU): the grouped
  expert kernel on a TPU, the task loop elsewhere. No shared expert.
- No attention bias, no per-head q/k norm, no secondary experts: the
  config has no key for any (benchmark/configs/smallthinker-*.json
  `assumed`).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ggrmcp_tpu.models import common, mla_moe
from ggrmcp_tpu.models import llama as llama_mod
from ggrmcp_tpu.models.llama import (  # noqa: F401
    KVCache,
    LlamaConfig,
    PagedKVCache,
    WindowArena,
    activation_spec,
)
from ggrmcp_tpu.ops.quant import embed_lookup

Params = common.Params

# What the batcher may ask of this family (serving/batching.py reads
# them off the module; models/mla_moe.py says what each means). The
# counts a step returns are mla_moe's, under the same names (the three
# of a sparse selection read 0: there is none).
HEAD_AT_INDEX = True
ROUTING_STATS = mla_moe.ROUTING_STATS
DEEP_GRID_CHUNKS = mla_moe.DEEP_GRID_CHUNKS


def admission_rows(cfg) -> Optional[int]:
    """One row an admission call: a row's mini cache is 0.27 GB at the
    published widths beside ~12.7 GB resident, and this family's
    admissions are re-admissions of single sessions, which rarely
    group (what PR 49 found for jamba: a multi-row program, cold
    between uses, costs the round that needs it)."""
    return 1


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig(LlamaConfig):
    """`ffn_dim` sizes nothing (every layer is an expert layer; the
    config gives no dense width)."""

    name: str = "smallthinker"
    norm_eps: float = 1e-6
    rope_theta: float = 1.5e6
    sliding_window: Optional[int] = 4096
    ffn_dim: int = 0
    # One period of the stack, repeated `num_layers / len` times.
    layer_period: tuple = ("full", "window", "window", "window")
    num_experts: int = 64
    experts_per_token: int = 6
    expert_ffn_dim: int = 768
    expert_act: str = "relu"  # ReGLU (`mla_moe.routed_experts` reads it)
    # What `mla_moe.route` / `routed_experts` read of a config.
    router_scoring: str = "softmax"
    routed_scaling: float = 1.0
    num_shared_experts: int = 0
    n_group: int = 1
    topk_group: int = 1
    experts_held: Optional[tuple] = None

    def __post_init__(self):
        assert self.num_layers % len(self.layer_period) == 0, (
            self.num_layers, self.layer_period)
        assert set(self.layer_period) <= {"full", "window"}, self.layer_period

    @property
    def layer_kinds(self) -> tuple:
        """Every layer's kind, in the model's order."""
        return self.layer_period * (self.num_layers // len(self.layer_period))

    @property
    def cache_kinds(self) -> tuple:
        kinds = self.layer_kinds
        return ((kinds.count("full"), None),
                (kinds.count("window"), self.sliding_window))

    @property
    def num_experts_held(self) -> int:
        return self.num_experts

    @property
    def num_expert_layers(self) -> int:  # the batcher's `layer_steps`
        return self.num_layers


def kind_cfg(cfg: SmallThinkerConfig, kind: str) -> SmallThinkerConfig:
    """The config `llama.attention_block` reads for a layer of `kind`:
    a window layer has the window and RoPE, a full layer neither."""
    if kind == "window":
        return cfg
    return dataclasses.replace(cfg, sliding_window=None, rope_theta=0.0)


# PowerInfer/SmallThinker-21BA3B-Instruct `config.json`.
_SMALLTHINKER_21B = dict(
    vocab_size=151936, hidden_dim=2560, num_heads=28, num_kv_heads=4,
    head_dim=128, max_seq_len=16384,
)

CONFIGS: dict[str, SmallThinkerConfig] = {
    # As published: 52 layers (13 periods), never loaded here.
    "smallthinker-21b-a3b": SmallThinkerConfig(
        name="smallthinker-21b-a3b", num_layers=52, **_SMALLTHINKER_21B),
    # The same widths at the depth one v5e chip holds in bf16 beside its
    # two arenas: the first two of thirteen periods (layers 0-7), all 64
    # experts, the whole vocabulary (3,967M parameters, 7.93 GB): the
    # first of seven pipeline stages.
    "smallthinker-21b-a3b-8l": SmallThinkerConfig(
        name="smallthinker-21b-a3b-8l", num_layers=8, **_SMALLTHINKER_21B),
    # Every mechanism live at a size for the CPU tests: two periods, a
    # window the tests' contexts pass, 8 experts of which 2 a token.
    "tiny-smallthinker": SmallThinkerConfig(
        name="tiny-smallthinker", vocab_size=512, hidden_dim=128,
        num_layers=8, num_heads=8, num_kv_heads=4, head_dim=16,
        max_seq_len=1024, rope_theta=10000.0, sliding_window=32,
        num_experts=8, experts_per_token=2, expert_ffn_dim=64,
        dtype="float32",
    ),
}


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def leaf_recipe(cfg: SmallThinkerConfig) -> list:
    """Every drawn leaf, in draw order: (path, shape, scale, dtype
    name), drawn as `mla_moe.leaf_recipe` says. `wqkv` is W_q, W_k and
    W_v side by side (`llama.attention_block` splits it). Norm weights
    are ones and not drawn. The benchmark's reference repeats this
    recipe from its own copy of the list."""
    d, n, e, f = (
        cfg.hidden_dim, cfg.num_layers, cfg.num_experts, cfg.expert_ffn_dim)
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    return [
        (("embed",), (cfg.vocab_size, d), 0.02, cfg.dtype),
        (("layers", "wqkv"), (n, d, (h + 2 * kvh) * hd), d**-0.5, cfg.dtype),
        (("layers", "wo"), (n, h * hd, d), (h * hd) ** -0.5, cfg.dtype),
        (("layers", "router"), (n, d, e), d**-0.5, "float32"),
        (("layers", "w_gate"), (n, e, d, f), d**-0.5, cfg.dtype),
        (("layers", "w_up"), (n, e, d, f), d**-0.5, cfg.dtype),
        (("layers", "w_down"), (n, e, f, d), f**-0.5, cfg.dtype),
        (("lm_head",), (d, cfg.vocab_size), d**-0.5, cfg.dtype),
    ]


def init_params(key: jax.Array, cfg: SmallThinkerConfig) -> Params:
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
    for name in ("attn_norm", "mlp_norm"):
        params["layers"][name] = jnp.ones(
            (cfg.num_layers, cfg.hidden_dim), cfg.jnp_dtype)
    params["final_norm"] = jnp.ones((cfg.hidden_dim,), cfg.jnp_dtype)
    return params


def param_specs(cfg: SmallThinkerConfig) -> Params:
    """Heads over `tensor`, as the dense family; the experts whole on
    every chip (their exchange over a mesh axis is not built)."""
    return {
        "embed": P("tensor", None),
        "layers": {
            "attn_norm": P(None, None), "mlp_norm": P(None, None),
            "wqkv": P(None, None, "tensor"), "wo": P(None, "tensor", None),
            "router": P(None, None, None),
            "w_gate": P(None, None, None, "tensor"),
            "w_up": P(None, None, None, "tensor"),
            "w_down": P(None, None, "tensor", None),
        },
        "final_norm": P(None), "lm_head": P(None, "tensor"),
    }


cache_specs = llama_mod.cache_specs


def paged_cache_specs() -> PagedKVCache:
    """The dense family's arena specs for both kinds."""
    spec = P(None, None, None, "tensor", None)
    return llama_mod.paged_cache_specs()._replace(
        window=WindowArena(spec, spec, P()))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def forward(
    params: Params,
    cfg: SmallThinkerConfig,
    tokens: jnp.ndarray,  # [B, S]
    cache: Optional[Any] = None,  # KVCache, or PagedKVCache with `window`
    valid: Optional[jnp.ndarray] = None,  # [B, S] bool
    logit_idx: Optional[jnp.ndarray] = None,  # [B]: one position a row
    with_stats: bool = False,
    use_flash: Optional[bool] = None,
    flash_mesh: Any = None,
):
    """`mla_moe.forward`'s contract (`valid`, `logit_idx`,
    `with_stats`: the same seven counts summed over the layers).
    `use_flash` / `flash_mesh`: the engine's word on kernels for its
    mesh, heard by the attention and by the experts."""
    b, s = tokens.shape
    x = embed_lookup(params["embed"], tokens, cfg.jnp_dtype)
    if cache is not None:
        positions = cache.length[:, None] + jnp.arange(s)[None, :]
    else:
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    length = None if cache is None else cache.length
    paged = isinstance(cache, PagedKVCache)
    period = cfg.layer_period
    n, periods = len(period), cfg.num_layers // len(period)
    # A layer's index within its kind's arena: its period's first such
    # index, plus the layers of its kind before it in the period.
    of_kind = {kind: period.count(kind) for kind in set(period)}
    before = [period[:j].count(kind) for j, kind in enumerate(period)]

    bank_names = ("w_gate", "w_up", "w_down")
    banks = tuple(params["layers"][name] for name in bank_names)
    per_layer = {
        k: v.reshape(periods, n, *v.shape[1:])
        for k, v in params["layers"].items() if k not in bank_names}
    attn = dict(use_flash=use_flash, flash_mesh=flash_mesh)

    def body(carry, scanned):
        x, arenas = carry  # paged: {kind: (k, v)}, each kind's whole arena
        lps, p, rows = scanned  # contiguous: this period's (k, v) rows
        arenas = None if arenas is None else dict(arenas)
        kept, stats = [], jnp.zeros((4,), jnp.int32)
        for j, kind in enumerate(period):
            lp = {k: v[j] for k, v in lps.items()}
            kcfg = kind_cfg(cfg, kind)
            normed = common.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
            routing = mla_moe.route(normed.reshape(b * s, -1), lp, cfg)
            if cache is None:
                x, _ = llama_mod.attention_block(
                    x, lp, kcfg, positions, None, None, None, **attn)
            elif paged:
                table = cache.table if kind == "full" else cache.window.table
                x, arenas[kind] = llama_mod.attention_block(
                    x, lp, kcfg, positions, *arenas[kind], length, **attn,
                    page_table=table, layer=p * of_kind[kind] + before[j])
            else:
                x, kv = llama_mod.attention_block(
                    x, lp, kcfg, positions, rows[0][j], rows[1][j], length,
                    **attn)
                kept.append(kv)
            out, counts = mla_moe.moe_ffn(
                common.rms_norm(x, lp["mlp_norm"], cfg.norm_eps), lp, banks,
                p * n + j, cfg, valid, use_flash, flash_mesh, routing=routing)
            x, stats = x + out, stats + counts
        rows = tuple(jnp.stack(t) for t in zip(*kept)) if kept else None
        return (x, arenas), (stats, rows)

    arenas = rows = None
    if paged:
        arenas = {"full": (cache.k, cache.v),
                  "window": (cache.window.k, cache.window.v)}
    elif cache is not None:
        rows = tuple(
            jax.tree_util.tree_map(
                lambda a: a.reshape(periods, n, *a.shape[1:]), plane)
            for plane in (cache.k, cache.v))
    (x, arenas), (stats, rows) = jax.lax.scan(
        body, (x, arenas), (per_layer, jnp.arange(periods), rows))
    if paged:
        (gk, gv), (wk, wv) = arenas["full"], arenas["window"]
        new_cache = cache._replace(
            k=gk, v=gv, length=cache.length + s,
            window=cache.window._replace(k=wk, v=wv))
    elif cache is not None:
        k, v = (
            jax.tree_util.tree_map(
                lambda a: a.reshape(cfg.num_layers, *a.shape[2:]), plane)
            for plane in rows)
        new_cache = cache._replace(k=k, v=v, length=cache.length + s)
    else:
        new_cache = None
    if logit_idx is not None:
        x = jnp.take_along_axis(x, logit_idx[:, None, None], axis=1)
    x = common.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = (x @ params["lm_head"].astype(cfg.jnp_dtype)).astype(jnp.float32)
    if with_stats:  # no sparse selection: its three counts read 0
        return logits, new_cache, jnp.concatenate(
            [stats.sum(0), jnp.zeros((3,), jnp.int32)])
    return logits, new_cache


def num_params(cfg: SmallThinkerConfig) -> int:
    return sum(math.prod(shape) for _, shape, _, _ in leaf_recipe(cfg)) + (
        (2 * cfg.num_layers + 1) * cfg.hidden_dim)
