"""Hybrid decoder of state-space (Mamba-1) layers and a few attention
layers (`model_type: jamba`, AI21-Jamba2-3B): a family beside llama,
moe, mla_moe and keye, and the first whose stack is NOT homogeneous and
whose rows keep a state that no position addresses.

- **Layers.** Layer i is attention iff `i % attn_layer_period ==
  attn_layer_offset` (the jamba modeling code's reading of the two
  keys), else Mamba; every layer is `x += mixer(RMSNorm(x)); x +=
  MLP(RMSNorm(x))` with a dense SwiGLU MLP. The Mamba layers' weights
  are stacked `[Lm, ...]`, the attention layers' `[La, ...]`, and the
  forward runs each run of consecutive Mamba layers as one `lax.scan`
  that indexes the stack in place, the attention layers between them.
- **Attention** is the dense family's block (`llama.attention_block`:
  its scatter, the paged-decode kernel for a decode-shaped step, the
  prefill kernel for a chunk) WITHOUT rotary: the model has no position
  signal but the causal mask and the recurrence (`rope_theta` 0). Only
  the attention layers cache K/V: `cfg.cache_layers` sizes the arena's
  and the mini cache's layer axis.
- **The row state** (`cfg.row_state`): a Mamba layer keeps, a row, the
  causal convolution's last `d_conv - 1` inputs `[3, C]` in the model's
  dtype and the recurrence's `h` `[N, C]` float32 (`state_dtype`), C =
  `d_inner` on the lanes. They live in a POOL `[Lm, entries, ...]` that
  rides the cache (`KVCache.state` / `PagedKVCache.state`), loop-carried
  through the layer scans and updated in place as the arena is. Batch
  row b reads and writes entry `cache.state_rows[b]`, or entry b where
  that is None (the tick: a slot's state is the entry of its index).
  The entries past the slots hold snapshots (serving/pages.py, "State
  beside pages" in docs/paged_kv.md): `forward(capture=(pos, dst))`
  copies the state as the chunk's scan passes absolute position
  `pos[b, k]` into entry `dst[b, k]`. The scan walks a chunk in blocks
  of one page (`ops.ssm.SCAN_BLOCK`), so every page boundary of a chunk
  that starts on one is a carry.
- **Padding never moves the state**: past a row's `valid` positions
  `dt` is 0 (`exp(dt A) = 1`, the input term 0) and the convolution's
  window keeps the last three VALID inputs.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ggrmcp_tpu.models import common
from ggrmcp_tpu.models import llama as llama_mod
from ggrmcp_tpu.models.llama import (  # noqa: F401
    KVCache,
    LlamaConfig,
    PagedKVCache,
    activation_spec,
)
from ggrmcp_tpu.ops import ssm
from ggrmcp_tpu.ops.quant import embed_lookup

Params = common.Params

# What the batcher may ask of this family (serving/batching.py reads
# them off the module). HEAD_AT_INDEX: the head at one position a row
# (a [32, 512, 65536] float32 logits block is 4 GB). ROW_STATE: rows
# carry `cache.state`; admissions restore, carry and capture it.
HEAD_AT_INDEX = True
ROUTING_STATS = ()
ROW_STATE = True


@dataclasses.dataclass(frozen=True)
class JambaConfig(LlamaConfig):
    """`ffn_dim` is the published `intermediate_size`: every layer's
    dense SwiGLU MLP (`num_experts` 1)."""

    name: str = "jamba"
    norm_eps: float = 1e-6
    rope_theta: float = 0.0  # no rotary (llama.attention_block skips it)
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 160
    expand: int = 2
    # What the pool keeps `h` in. float32 as the model is served; the
    # `-bf16-state` registry entries are the benchmark's control.
    state_dtype: str = "float32"

    @property
    def d_inner(self) -> int:
        return self.expand * self.hidden_dim

    @property
    def attn_layers(self) -> tuple:
        return tuple(
            i for i in range(self.num_layers)
            if i % self.attn_layer_period == self.attn_layer_offset)

    @property
    def cache_layers(self) -> int:
        return len(self.attn_layers)

    @property
    def mamba_layers(self) -> int:
        return self.num_layers - self.cache_layers

    @property
    def row_state(self) -> tuple:
        """(shape after `[Lm, entry]`, dtype name) of each leaf of a
        row's state: the convolution's window, then `h`."""
        return (
            ((self.d_conv - 1, self.d_inner), self.dtype),
            ((self.d_state, self.d_inner), self.state_dtype),
        )

    @property
    def segments(self) -> tuple:
        """The stack in order: ("mamba", lo, hi), a run of Mamba layers
        by their index into the Mamba stack, or ("attn", j)."""
        out, lo, m, a = [], 0, 0, 0
        for i in range(self.num_layers):
            if i in self.attn_layers:
                if m > lo:
                    out.append(("mamba", lo, m))
                out.append(("attn", a))
                a, lo = a + 1, m
            else:
                m += 1
        if m > lo:
            out.append(("mamba", lo, m))
        return tuple(out)


# ai21labs/AI21-Jamba2-3B `config.json`, every width as published.
_JAMBA2_3B = dict(
    vocab_size=65536, hidden_dim=2560, num_layers=28, num_heads=20,
    num_kv_heads=1, head_dim=128, ffn_dim=8192, max_seq_len=262144,
)
_TINY = dict(
    vocab_size=512, hidden_dim=128, num_layers=4, num_heads=4,
    num_kv_heads=1, head_dim=32, ffn_dim=256, max_seq_len=1024,
    attn_layer_period=4, attn_layer_offset=2, dt_rank=8, dtype="float32",
)

CONFIGS: dict[str, JambaConfig] = {
    "jamba2-3b": JambaConfig(name="jamba2-3b", **_JAMBA2_3B),
    # One whole period for the CPU tests: Mamba, Mamba, attention, Mamba.
    "tiny-jamba": JambaConfig(name="tiny-jamba", **_TINY),
    # The benchmark's control: `h` kept in bfloat16, the nearest
    # precision below the one the configuration states. Never a
    # deployment.
    "jamba2-3b-bf16-state": JambaConfig(
        name="jamba2-3b-bf16-state", state_dtype="bfloat16", **_JAMBA2_3B),
    "tiny-jamba-bf16-state": JambaConfig(
        name="tiny-jamba-bf16-state", state_dtype="bfloat16", **_TINY),
}


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def leaf_recipe(cfg: JambaConfig) -> list:
    """Every drawn leaf, in draw order: (path, shape, scale, dtype
    name), drawn as `mla_moe.leaf_recipe` says. Not drawn: norm weights
    (ones), the convolution's bias (zeros), `A_log`, `D` and `dt_bias`
    (`_fixed_leaves`). The benchmark's reference repeats this recipe
    from its own copy of the list."""
    d, c, f = cfg.hidden_dim, cfg.d_inner, cfg.ffn_dim
    n, r, k = cfg.d_state, cfg.dt_rank, cfg.d_conv
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    lm, la = cfg.mamba_layers, cfg.cache_layers

    def mlp(group, count):
        return [
            ((group, "w_gate"), (count, d, f), d**-0.5, cfg.dtype),
            ((group, "w_up"), (count, d, f), d**-0.5, cfg.dtype),
            ((group, "w_down"), (count, f, d), f**-0.5, cfg.dtype),
        ]

    return [
        (("embed",), (cfg.vocab_size, d), 0.02, cfg.dtype),
        (("mamba", "in_proj"), (lm, d, 2 * c), d**-0.5, cfg.dtype),
        (("mamba", "conv_w"), (lm, k, c), k**-0.5, cfg.dtype),
        (("mamba", "x_proj"), (lm, c, r + 2 * n), c**-0.5, cfg.dtype),
        (("mamba", "dt_proj"), (lm, r, c), r**-0.5, cfg.dtype),
        (("mamba", "out_proj"), (lm, c, d), c**-0.5, cfg.dtype),
        *mlp("mamba", lm),
        (("attn", "wqkv"), (la, d, (h + 2 * kvh) * hd), d**-0.5, cfg.dtype),
        (("attn", "wo"), (la, h * hd, d), (h * hd) ** -0.5, cfg.dtype),
        *mlp("attn", la),
    ]


def _fixed_leaves(cfg: JambaConfig) -> dict:
    """The leaves no key draws, as Mamba initialises them: `A = -(1 ..
    N)` a channel, `D` ones, and a `dt_bias` whose softplus runs from
    1e-3 to 1e-1 over the channels, so that the state of the slowest
    channels remembers thousands of positions. `A_log`, `D` float32."""
    lm, c, n, d, r = (
        cfg.mamba_layers, cfg.d_inner, cfg.d_state, cfg.hidden_dim,
        cfg.dt_rank)
    dt = jnp.exp(jnp.linspace(math.log(1e-3), math.log(1e-1), c))
    ones = {
        name: jnp.ones((lm, width), cfg.jnp_dtype)
        for name, width in (
            ("norm", d), ("mlp_norm", d), ("dt_norm", r), ("b_norm", n),
            ("c_norm", n))}
    return {
        "mamba": {
            **ones,
            "conv_b": jnp.zeros((lm, c), cfg.jnp_dtype),
            "a_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[None, :, None],
                (lm, n, c)),
            "d_skip": jnp.ones((lm, c), jnp.float32),
            "dt_bias": jnp.broadcast_to(
                (dt + jnp.log(-jnp.expm1(-dt)))[None], (lm, c)
            ).astype(jnp.float32),
        },
        "attn": {
            "attn_norm": jnp.ones((cfg.cache_layers, d), cfg.jnp_dtype),
            "mlp_norm": jnp.ones((cfg.cache_layers, d), cfg.jnp_dtype),
        },
    }


def init_params(key: jax.Array, cfg: JambaConfig) -> Params:
    recipe = leaf_recipe(cfg)
    params: Params = {k: dict(v) for k, v in _fixed_leaves(cfg).items()}
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
    params["final_norm"] = jnp.ones((cfg.hidden_dim,), cfg.jnp_dtype)
    return params


def param_specs(cfg: JambaConfig) -> Params:
    """Everything whole on every chip: a state-space layer on a mesh is
    not built, and a mesh of more than one device is refused
    (engine._UNSUPPORTED)."""
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return jax.tree_util.tree_map(lambda s: P(*(None,) * s.ndim), shapes)


def cache_specs() -> KVCache:
    rows = ("data", "fsdp")
    kv = P(None, rows, None, None, None)
    return KVCache(
        k=kv, v=kv, length=P(rows),
        state=(P(None, rows, None, None),) * 2)


def paged_cache_specs() -> PagedKVCache:
    kv = P(None, None, None, None, None)
    return PagedKVCache(
        k=kv, v=kv, table=P(), length=P(), state=(P(),) * 2)


# ---------------------------------------------------------------------------
# Row state
# ---------------------------------------------------------------------------


def restore_rows(state: tuple, rows, src) -> tuple:
    """What an admission starts from: entry `rows[b]` of every leaf
    becomes a copy of entry `src[b]` (a snapshot), or zeros where
    `src[b] < 0` (a cold row). One device copy, no host round trip;
    rows out of range are dropped."""
    def one(pool):
        got = pool[:, jnp.clip(src, 0, pool.shape[1] - 1)]
        got = jnp.where((src >= 0)[None, :, None, None], got, 0)
        return pool.at[:, rows].set(got, mode="drop")

    return tuple(one(pool) for pool in state)


def _read(pool, m, rows, b: int):
    if rows is None:  # the first b entries, in place
        return jax.lax.dynamic_slice(
            pool, (m, 0, 0, 0), (1, b, *pool.shape[2:]))[0]
    return pool[m, jnp.clip(rows, 0, pool.shape[1] - 1)]


def _write(pool, m, rows, val):
    val = val.astype(pool.dtype)
    if rows is None:
        return jax.lax.dynamic_update_slice(pool, val[None], (m, 0, 0, 0))
    return pool.at[m, rows].set(val, mode="drop")


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------


def mamba_mixer(x, lp, cfg: JambaConfig, conv_in, h_in, valid, start,
                capture=None):
    """One Mamba mixer with residual over a step `x` [B, S, D]. The
    row's state enters as `conv_in` [B, d_conv - 1, C] (the last inputs
    of the convolution) and `h_in` [B, N, C] float32 and leaves as the
    second and third results; `valid` [B, S] (None: every position)
    marks the real positions, a PREFIX of the step, and nothing past
    them moves the state. `capture` = absolute positions `pos` [B, K]
    (`start` [B] is the step's first): the fourth result lists, for
    each k, (conv, h, taken [B]): the state after position `pos - 1`
    where the step passes it on a block boundary of the scan."""
    b, s, _ = x.shape
    c, n, r, taps = cfg.d_inner, cfg.d_state, cfg.dt_rank, cfg.d_conv
    f32 = jnp.float32
    normed = common.rms_norm(x, lp["norm"], cfg.norm_eps)
    u, z = jnp.split(normed @ lp["in_proj"], 2, axis=-1)  # [B, S, C]
    if valid is None:
        n_valid = jnp.full((b,), s, jnp.int32)
    else:
        n_valid = valid.sum(-1).astype(jnp.int32)
        u = jnp.where(valid[..., None], u, 0)
    # Depthwise causal convolution over the row's last inputs and the
    # step's: window position j is input j - (taps - 1) of the step.
    window = jnp.concatenate([conv_in.astype(u.dtype), u], axis=1)
    conv = lp["conv_b"].astype(f32) + sum(
        window[:, k: k + s].astype(f32) * lp["conv_w"][k].astype(f32)
        for k in range(taps))
    u = jax.nn.silu(conv).astype(x.dtype)

    def window_at(first):  # the taps - 1 inputs before step position `first`
        idx = first[:, None] + jnp.arange(taps - 1)[None, :]
        return jnp.take_along_axis(window, idx[..., None], axis=1)

    dbc = u @ lp["x_proj"]
    dt_r, b_m, c_m = jnp.split(dbc, [r, r + n], axis=-1)
    dt_r = common.rms_norm(dt_r, lp["dt_norm"], cfg.norm_eps)
    b_m = common.rms_norm(b_m, lp["b_norm"], cfg.norm_eps)
    c_m = common.rms_norm(c_m, lp["c_norm"], cfg.norm_eps)
    dt = jax.nn.softplus(
        (dt_r @ lp["dt_proj"]).astype(f32) + lp["dt_bias"].astype(f32))
    if valid is not None:
        dt = jnp.where(valid[..., None], dt, 0.0)
    a = -jnp.exp(lp["a_log"].astype(f32))  # [N, C]
    caps = []
    if s == 1:
        y, h = ssm.ssm_step(h_in, u[:, 0], dt[:, 0], a, b_m[:, 0], c_m[:, 0])
        y = y[:, None]
    else:
        y, h, hs = ssm.ssm_scan(h_in, u, dt, a, b_m, c_m)
        for k in range(0 if capture is None else capture.shape[1]):
            rel = capture[:, k] - start  # step positions before the cut
            taken = (rel > 0) & (rel <= n_valid) & (rel % ssm.SCAN_BLOCK == 0)
            blk = jnp.clip(rel // ssm.SCAN_BLOCK - 1, 0, hs.shape[0] - 1)
            caps.append((
                window_at(jnp.clip(rel, 0, s)), hs[blk, jnp.arange(b)], taken))
    y = y + lp["d_skip"].astype(f32) * u.astype(f32)
    out = (y * jax.nn.silu(z.astype(f32))).astype(x.dtype) @ lp["out_proj"]
    return x + out, window_at(n_valid), h, caps


def _mlp(x, lp, cfg: JambaConfig):
    n = common.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    return x + (jax.nn.silu(n @ lp["w_gate"]) * (n @ lp["w_up"])) @ lp["w_down"]


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def forward(
    params: Params,
    cfg: JambaConfig,
    tokens: jnp.ndarray,  # [B, S]
    cache: Optional[Any] = None,  # KVCache or PagedKVCache with `state`
    valid: Optional[jnp.ndarray] = None,  # [B, S] bool, a prefix a row
    logit_idx: Optional[jnp.ndarray] = None,  # [B]: one position a row
    with_stats: bool = False,
    use_flash: Optional[bool] = None,
    flash_mesh: Any = None,
    capture: Optional[tuple] = None,  # (pos [B, K], dst [B, K])
):
    """The families' shared contract (`mla_moe.forward`: `valid`,
    `logit_idx`). Without a cache every row starts from a zero state
    (scoring). With one, the K/V of the attention layers go to the
    cache's planes and each row's state is read from and written back
    to its pool entry (module docstring); `capture` also copies the
    state at the absolute positions `pos` into the entries `dst` (out
    of range: none)."""
    b, s = tokens.shape
    x = embed_lookup(params["embed"], tokens, cfg.jnp_dtype)
    if cache is not None:
        start = cache.length
        positions = start[:, None] + jnp.arange(s)[None, :]
        pools, rows = tuple(cache.state), cache.state_rows
    else:
        start = jnp.zeros((b,), jnp.int32)
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        pools, rows = llama_mod.zero_state(cfg, b), None
    paged = isinstance(cache, PagedKVCache)
    planes = None if cache is None else (cache.k, cache.v)
    cap_pos, cap_dst = capture if capture is not None else (None, None)

    def mamba_body(carry, m):
        x, conv_pool, h_pool = carry
        lp = jax.tree_util.tree_map(
            lambda leaf: jax.lax.dynamic_index_in_dim(leaf, m, 0, False),
            params["mamba"])
        x, conv, h, caps = mamba_mixer(
            x, lp, cfg, _read(conv_pool, m, rows, b),
            _read(h_pool, m, rows, b).astype(jnp.float32), valid, start,
            cap_pos)
        conv_pool = _write(conv_pool, m, rows, conv)
        h_pool = _write(h_pool, m, rows, h)
        for k, (conv_k, h_k, taken) in enumerate(caps):
            dst = jnp.where(taken, cap_dst[:, k], conv_pool.shape[1])
            conv_pool = _write(conv_pool, m, dst, conv_k)
            h_pool = _write(h_pool, m, dst, h_k)
        return (_mlp(x, lp, cfg), conv_pool, h_pool), None

    for seg in cfg.segments:
        if seg[0] == "mamba":
            (x, *pools), _ = jax.lax.scan(
                mamba_body, (x, *pools), jnp.arange(seg[1], seg[2]))
            continue
        j = seg[1]
        lp = jax.tree_util.tree_map(lambda leaf: leaf[j], params["attn"])
        if planes is None:
            x, _ = llama_mod.attention_block(
                x, lp, cfg, positions, None, None, None,
                use_flash=use_flash, flash_mesh=flash_mesh)
        elif paged:
            x, planes = llama_mod.attention_block(
                x, lp, cfg, positions, *planes, cache.length,
                use_flash=use_flash, flash_mesh=flash_mesh,
                page_table=cache.table, layer=jnp.int32(j))
        else:
            x, (ck, cv) = llama_mod.attention_block(
                x, lp, cfg, positions, planes[0][j], planes[1][j],
                cache.length, use_flash=use_flash, flash_mesh=flash_mesh)
            planes = (planes[0].at[j].set(ck), planes[1].at[j].set(cv))
        x = _mlp(x, lp, cfg)

    new_cache = None if cache is None else cache._replace(
        k=planes[0], v=planes[1], length=cache.length + s,
        state=tuple(pools))
    if logit_idx is not None:
        x = jnp.take_along_axis(x, logit_idx[:, None, None], axis=1)
    x = common.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum(  # the embedding is the head (tied)
        "bsd,vd->bsv", x, params["embed"].astype(cfg.jnp_dtype)
    ).astype(jnp.float32)
    return logits, new_cache  # no counts to give (`ROUTING_STATS` is empty)


def num_params(cfg: JambaConfig) -> int:
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return sum(math.prod(s.shape) for s in jax.tree_util.tree_leaves(shapes))


def admission_rows(cfg) -> Optional[int]:
    """Rows one admission call over a full-width mini cache may take
    (the batcher asks, as of `mla_moe.admission_rows`): one. A row's
    scan starts where ITS snapshot lies, so rows share a program only
    when first turns coincide; a group then pads every row's scan to
    the widest and, on the chip, runs a program the device has not run
    for tens of seconds: 0.2-1.3 s of one round's host time each time,
    where a row alone takes 20-30 ms on a program every call keeps hot
    (PERF.md, PR 49). Fewer programs to compile at start-up, too."""
    return 1
