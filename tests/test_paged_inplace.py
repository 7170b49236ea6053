"""The paged arena is loop-carried and updated in place inside `forward`
(models/llama.py, docs/paged_kv.md "Inside the jitted tick").

Two properties:

1. VALUES — the carried-arena `forward` equals, bitwise in logits and in
   both arenas, a scanned-in / stacked-out reference written here: each
   layer's [N, P, KVH, Dh] plane scanned in, scattered at
   [page, offset], gathered at [page], stacked out (the form the model
   had before the arena rode the carry).
2. THE PROGRAM — the batcher's compiled `_tick` holds no copy, slice or
   update-slice of arena or plane shape, and the donated arenas are
   aliased to its outputs. Checked on the optimised HLO text.

Marker `paged` (tier-1, `make test-paged`).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ggrmcp_tpu.core.config import BatchingConfig, MeshConfig, ServingConfig
from ggrmcp_tpu.models import common, llama
from ggrmcp_tpu.ops.attention import attention
from ggrmcp_tpu.ops.quant import QuantizedArray, dequantize, kv_map, quantize
from ggrmcp_tpu.ops.rope import apply_rope
from ggrmcp_tpu.serving.batching import ContinuousBatcher
from ggrmcp_tpu.serving.engine import GenerationEngine

pytestmark = pytest.mark.paged

CFG = llama.CONFIGS["tiny-llama"]
PAGE, WIDTH, N_PAGES, JUMP_MAX = 8, 6, 29, 4


def reference_forward(params, cfg, tokens, cache):
    """Plain scanned-in / stacked-out paged forward (dense weights, XLA
    attention): the layer scan takes each layer's plane as a scanned
    input and stacks the updated planes into a fresh arena."""
    b, s = tokens.shape
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    n_pg = cache.k.shape[1]
    pos = cache.length[:, None] + jnp.arange(s)[None, :]
    idx = pos // PAGE
    w_page = jnp.where(
        idx < WIDTH,
        jnp.take_along_axis(
            cache.table, jnp.minimum(idx, WIDTH - 1), axis=1
        ),
        n_pg,
    )
    w_off = pos % PAGE
    read = jnp.minimum(cache.table, n_pg - 1)

    def step(plane, new):  # -> (written plane, [B, W*P, KVH, Dh] view)
        def put(a, u):
            return a.at[w_page, w_off].set(u.astype(a.dtype), mode="drop")

        def view(a):
            return a[read].reshape(b, -1, *a.shape[2:])

        if isinstance(plane, QuantizedArray):
            plane = kv_map(put, plane, quantize(new, axis=-1))
            return plane, dequantize(kv_map(view, plane))
        plane = put(plane, new)
        return plane, view(plane)

    def body(x, scanned):
        lp, ck, cv = scanned
        n = common.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q, k, v = jnp.split(n @ lp["wqkv"], [h * hd, (h + kvh) * hd], -1)
        q = apply_rope(
            q.reshape(b, s, h, hd), pos, cfg.rope_theta, cfg.rope_scaling
        )
        k = apply_rope(
            k.reshape(b, s, kvh, hd), pos, cfg.rope_theta, cfg.rope_scaling
        )
        ck, k_all = step(ck, k)
        cv, v_all = step(cv, v.reshape(b, s, kvh, hd))
        out = attention(
            q, k_all, v_all, causal=True, q_offset=cache.length,
            kv_len=cache.length + s, use_flash=False,
        )
        x = x + out.reshape(b, s, h * hd) @ lp["wo"]
        n = common.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        x = x + (jax.nn.silu(n @ lp["w_gate"]) * (n @ lp["w_up"])) @ lp["w_down"]
        return x, (ck, cv)

    x = params["embed"][tokens].astype(cfg.jnp_dtype)
    x, (new_k, new_v) = jax.lax.scan(
        body, x, (params["layers"], cache.k, cache.v)
    )
    x = common.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = (x @ params["lm_head"]).astype(jnp.float32)
    return logits, cache._replace(k=new_k, v=new_v, length=cache.length + s)


def filled_cache(kv_dtype: str, s: int) -> llama.PagedKVCache:
    """An arena of random pages behind four rows: a row in mid-sequence,
    a row about to cross a page boundary, a PARKED row (table all
    sentinel) and a FULL-WIDTH row whose `s`-position window overshoots
    its table by the window's last position."""
    rng = np.random.default_rng(7)
    cache = llama.PagedKVCache.create(
        CFG, 4, WIDTH * PAGE, N_PAGES, PAGE, kv_dtype
    )

    def rand(a):
        if a.dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, a.shape), jnp.int8)
        return jnp.asarray(rng.standard_normal(a.shape), a.dtype)

    table = rng.permutation(N_PAGES)[: 4 * WIDTH].reshape(4, WIDTH)
    table[0, 3:] = N_PAGES  # unmapped tail
    table[2, :] = N_PAGES  # parked
    return cache._replace(
        k=jax.tree.map(rand, cache.k), v=jax.tree.map(rand, cache.v),
        table=jnp.asarray(table, jnp.int32),
        length=jnp.asarray(
            [11, 2 * PAGE - 1, 0, WIDTH * PAGE - s + 1], jnp.int32
        ),
    )


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.PRNGKey(3), CFG)


@pytest.mark.parametrize("s", [1, 1 + JUMP_MAX], ids=["step", "window"])
@pytest.mark.parametrize("kv_dtype", ["", "int8"], ids=["pages", "int8pages"])
def test_carried_arena_equals_stacked_reference(params, kv_dtype, s):
    cache = filled_cache(kv_dtype, s)
    tokens = jnp.asarray(
        np.random.default_rng(s).integers(3, CFG.vocab_size, (4, s)),
        jnp.int32,
    )
    got_logits, got = jax.jit(
        lambda p, t, c: llama.forward(p, CFG, t, c, use_flash=False)
    )(params, tokens, cache)
    want_logits, want = jax.jit(
        lambda p, t, c: reference_forward(p, CFG, t, c)
    )(params, tokens, cache)
    np.testing.assert_array_equal(got_logits, want_logits)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b)
    # The drops: the parked row and the overshooting position wrote
    # nothing, so every page no row maps is as it was.
    mapped = np.unique(np.asarray(cache.table))
    untouched = np.setdiff1d(np.arange(N_PAGES), mapped)
    assert untouched.size
    for a, b in zip(jax.tree.leaves(got.k), jax.tree.leaves(cache.k)):
        np.testing.assert_array_equal(a[:, untouched], b[:, untouched])
    # The full-width row's window did pass the end of its table.
    assert int(got.length[3]) == WIDTH * PAGE + 1


# ---------------------------------------------------------------------------
# The compiled tick
# ---------------------------------------------------------------------------

_INSTR = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]\S* ([\w\-]+)\("
)


def arena_ops(hlo: str, shapes: set[tuple[int, ...]]) -> list[str]:
    """Every `copy`, `dynamic-slice` or `dynamic-update-slice` in the
    module (inside fusions too: a fused computation's instructions are
    printed like any other) whose result has one of `shapes`."""
    bad = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if not m or m.group(2) not in (
            "copy", "dynamic-slice", "dynamic-update-slice"
        ):
            continue
        dims = tuple(int(d) for d in m.group(1).split(",") if d)
        if dims in shapes:
            bad.append(line.strip()[:160])
    return bad


@pytest.mark.parametrize("tensor", [1, 2], ids=["1dev", "tp2"])
def test_tick_program_updates_the_arena_in_place(tensor):
    engine = GenerationEngine(
        CFG, ServingConfig(mesh=MeshConfig(tensor=tensor, data=0))
    )
    batcher = ContinuousBatcher(
        engine,
        BatchingConfig(
            max_batch_size=3, kv_cache_max_seq=128, paged_kv="on",
            paged_kv_page_size=PAGE, paged_kv_pages=N_PAGES,
            decode_steps_per_tick=4,
        ),
    )
    b = 3
    g_allow, g_trans = batcher._grammar_tables()
    args = (
        engine.params, jnp.zeros((b,), jnp.int32), batcher.cache,
        jnp.zeros((b,), jnp.uint32), jnp.int32(0),
        jnp.zeros((b,), jnp.float32), jnp.zeros((b,), jnp.int32),
        jnp.ones((b,), jnp.float32), jnp.zeros((b,), bool),
        jnp.zeros((b,), jnp.int32), jnp.zeros((b,), jnp.int32),
        g_allow, g_trans,
    )
    with engine.mesh:
        compiled = batcher._tick.lower(*args).compile()
    hlo = compiled.as_text()

    kvh = CFG.num_kv_heads // tensor  # the arena's heads are sharded
    plane = (N_PAGES, PAGE, kvh, CFG.head_dim)
    arena = (CFG.num_layers,) + plane
    assert f"[{','.join(map(str, arena))}]" in hlo, "arena not in the module"
    assert arena_ops(hlo, {arena, plane}) == []

    # Donation honoured: both arena parameters alias an output.
    entry = hlo[hlo.index("\nENTRY "):]
    arena_params = {
        int(n) for n in re.findall(
            r"= \w+\[%s\]\S* parameter\((\d+)\)"
            % ",".join(map(str, arena)), entry,
        )
    }
    header = hlo[: hlo.index("\n")]
    aliased = {
        int(n) for n in re.findall(r"\(\s*(\d+)\s*,\s*\{\s*\}", header)
    }
    assert len(arena_params) == 2 and arena_params <= aliased, header[:400]
