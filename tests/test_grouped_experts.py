"""The grouped-experts kernel (ops/experts.py `grouped_swiglu`) behind
`mla_moe.routed_experts`, in the Pallas interpreter on the CPU.

1. THE KERNEL — `routed_experts` with the kernel against the same call
   with today's loop AND against a float32 `jnp` reference, over the
   count cases of tests/test_expert_task_map.py, with `valid` masking
   rows, with a share of the experts held, at the row tiles of a decode
   step and of a chunk, for the first layer and the last: outputs
   within the loop's own distance from the reference, `stats` equal as
   integers, rows routed nowhere exactly zero.
2. THE CHOOSER — where it decides (`grouped_experts`): the CPU
   keeps the parent's program; an int8 or float8 bank, a width that is
   not whole 128-lane rows and a mesh each decline, the mesh counted.

Marker `paged` (tier-1).
"""

import dataclasses
import functools
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ggrmcp_tpu.models import llama
from ggrmcp_tpu.models import mla_moe as M
from ggrmcp_tpu.ops import attention as attn_ops
from ggrmcp_tpu.ops import experts as X

pytestmark = pytest.mark.paged

D, F, LAYERS = 128, 128, 3
BASE = dataclasses.replace(
    M.CONFIGS["tiny-mla-moe"], hidden_dim=D, expert_ffn_dim=F)


def drawn(seed, experts, pairs):
    """Counts of `pairs` pairs thrown at `experts` experts, skewed."""
    rng = np.random.default_rng(seed)
    p = rng.dirichlet(np.full(experts, 0.3))
    return np.bincount(rng.choice(experts, pairs, p=p), minlength=experts)


def one_hot(experts, at, n):
    return np.eye(experts, dtype=int)[at] * n


# name -> (an expert's pairs, the kernel's row tile). One pair a token,
# so a case IS its counts; the tile is what the counts are whole blocks
# of (8: a decode step's in the loop; 32: a chunk's).
COUNTS = {
    "no_pair_at_all": (np.zeros(16, int), 8),
    "one_pair": (one_hot(16, 5, 1), 8),
    "every_pair_to_the_first": (one_hot(16, 0, 96), 8),
    "every_pair_to_the_last": (one_hot(16, 15, 96), 8),
    "every_pair_to_the_last_block_32": (one_hot(16, 15, 96), 32),
    "one_under_a_whole_block": (np.full(16, 7), 8),
    "every_count_a_whole_block": (np.full(16, 8), 8),
    "one_over_a_whole_block": (np.full(16, 9), 8),
    "one_under_a_whole_block_32": (np.full(16, 31), 32),
    "every_count_a_whole_block_32": (np.full(16, 32), 32),
    "one_over_a_whole_block_32": (np.full(16, 33), 32),
    "empty_experts_between": (np.tile([0, 0, 9, 0], 4), 8),
    "empty_at_both_ends": (np.r_[0, 0, np.full(12, 5), 0, 0], 8),
    "one_a_task": (np.ones(16, int), 8),
    "random_skewed_16": (drawn(0, 16, 96), 8),
    "random_skewed_16_block_32": (drawn(1, 16, 512), 32),
    "random_skewed_128": (drawn(2, 128, 96), 8),
    "random_skewed_128_block_32": (drawn(3, 128, 1024), 32),
}


@functools.lru_cache(maxsize=None)
def banks(experts, dtype, f=F):
    key = jax.random.PRNGKey(experts + f)
    shapes = ((D, f), (D, f), (f, D))
    return tuple(
        (jax.random.normal(jax.random.fold_in(key, i), (LAYERS, experts, *s))
         * s[0] ** -0.5).astype(dtype)
        for i, s in enumerate(shapes))


@functools.lru_cache(maxsize=None)
def runner(cfg, block, kernel):
    """`routed_experts` as one jitted program at `block` rows a task
    (`_task_block` answers it): with the kernel (the chooser says yes,
    the kernel runs in the interpreter, at `block` rows too) or with
    the loop."""
    def run(*operands):
        was = (X.grouped_experts, X.grouped_swiglu, X.MIN_ROWS, M._task_block)
        X.grouped_experts = lambda *a: kernel
        X.grouped_swiglu = functools.partial(was[1], interpret=True)
        X.MIN_ROWS, M._task_block = 1, lambda pairs, experts: block
        try:
            return M.routed_experts(*operands, cfg)
        finally:
            X.grouped_experts, X.grouped_swiglu, X.MIN_ROWS, M._task_block = was

    return jax.jit(run)


def reference(xt, idx, weight, valid, held_banks, layer, cfg):
    """Every expert on every token in float32, then each token's own:
    no sort, no task, no block."""
    first = cfg.experts_held[0] if cfg.experts_held else 0
    hi = jax.lax.Precision.HIGHEST
    x = xt.astype(jnp.float32)
    wg, wu, wd = (w[layer].astype(jnp.float32) for w in held_banks)
    g = jnp.einsum("td,edf->etf", x, wg, precision=hi)
    u = jnp.einsum("td,edf->etf", x, wu, precision=hi)
    y = jnp.einsum("etf,efd->etd", jax.nn.silu(g) * u, wd, precision=hi)
    local = idx - first  # [T, k]
    here = (local >= 0) & (local < wg.shape[0])
    if valid is not None:
        here &= valid[:, None]
    picked = y[jnp.clip(local, 0, wg.shape[0] - 1), jnp.arange(x.shape[0])[:, None]]
    out = (jnp.where(here[..., None], picked, 0.0) * weight[..., None]).sum(1)
    return out, here


def compare(cfg, operands, tile, dtype):
    xt, idx, weight, valid, held_banks, layer = operands
    got, got_stats = runner(cfg, tile, True)(*operands)
    loop, loop_stats = runner(cfg, tile, False)(*operands)
    want, here = reference(xt, idx, weight, valid, held_banks, layer, cfg)
    assert got.dtype == loop.dtype == dtype and got.shape == xt.shape
    np.testing.assert_array_equal(np.asarray(got_stats), np.asarray(loop_stats))
    assert int(got_stats[2]) == int(here.sum())
    got, loop, want = (np.asarray(a, np.float32) for a in (got, loop, want))
    assert np.isfinite(got).all()
    scale = max(float(np.abs(want).max()), 1e-6)
    mine, theirs = np.abs(got - want).max(), np.abs(loop - want).max()
    # bf16 rounds the loop's gate, up and hidden tiles where the kernel
    # keeps float32: the kernel is at least as near, to a few roundings
    # of the result (float32 sums in another order over tiles of F)
    eps = float(jnp.finfo(dtype).eps)
    assert mine <= theirs + 8 * eps * scale, (mine, theirs, scale)
    # a token no pair of which is computed here gets exactly zero
    nowhere = ~np.asarray(here).any(1)
    assert not got[nowhere].any()
    if here.any():
        assert np.abs(got).max() > 1e-3


@pytest.mark.parametrize("layer", [0, LAYERS - 1])
@pytest.mark.parametrize("case", COUNTS, ids=list(COUNTS))
def test_the_kernel_is_the_loop_and_the_reference(case, layer):
    counts, tile = COUNTS[case]
    experts = len(counts)
    cfg = dataclasses.replace(BASE, num_experts=experts, experts_per_token=1)
    # a fixed number of tokens a (experts, tile), the rest padding: one
    # program for every case of the shape
    tokens = {(16, 8): 160, (16, 32): 544, (128, 8): 160, (128, 32): 1056}[
        experts, tile]
    rng = np.random.default_rng(7)
    ids = np.repeat(np.arange(experts), counts)
    slots = rng.permutation(tokens)[:len(ids)]
    idx = rng.integers(0, experts, (tokens, 1))
    idx[slots, 0] = ids
    valid = np.zeros(tokens, bool)
    valid[slots] = True
    key = jax.random.PRNGKey(3)
    xt = jax.random.normal(key, (tokens, D)).astype(jnp.bfloat16)
    weight = jax.random.uniform(jax.random.fold_in(key, 1), (tokens, 1)) + 0.5
    compare(cfg, (
        xt, jnp.asarray(idx, jnp.int32), weight, jnp.asarray(valid),
        banks(experts, jnp.bfloat16), jnp.int32(layer)), tile, jnp.bfloat16)


ROUTED = {
    # name -> (tokens, k, experts, held, masked, tile, dtype, F)
    "a_decode_step": (8, 8, 128, None, False, 16, jnp.bfloat16, F),
    "a_decode_step_float32": (8, 8, 128, None, False, 16, jnp.float32, F),
    "a_suffix_masked": (96, 4, 16, None, True, 16, jnp.bfloat16, F),
    "a_chunk": (256, 4, 16, None, False, 64, jnp.bfloat16, F),
    "a_chunk_masked_float32": (256, 4, 16, None, True, 64, jnp.float32, F),
    "a_share_held": (64, 4, 16, (4, 8), False, 16, jnp.bfloat16, F),
    "a_share_held_masked": (64, 4, 16, (4, 8), True, 32, jnp.bfloat16, F),
    "a_share_nobody_routes_to": (8, 2, 128, (120, 8), False, 16, jnp.bfloat16, F),
    "nobody_real": (32, 4, 16, None, "all", 16, jnp.bfloat16, F),
    "one_token": (1, 4, 16, None, False, 16, jnp.bfloat16, F),
    # an expert's matrices in two tiles of the intermediate width
    "tiles_of_the_width": (96, 4, 16, None, True, 16, jnp.bfloat16, 2 * F),
    "tiles_of_the_width_a_share": (64, 4, 16, (4, 8), False, 32, jnp.float32, 2 * F),
}


@pytest.mark.parametrize("layer", [0, LAYERS - 1])
@pytest.mark.parametrize("case", ROUTED, ids=list(ROUTED))
def test_routed_top_k_with_masks_and_shares(case, layer, monkeypatch):
    tokens, k, experts, held, masked, tile, dtype, f = ROUTED[case]
    cfg = dataclasses.replace(
        BASE, num_experts=experts, experts_per_token=k, experts_held=held,
        expert_ffn_dim=f)
    if f != F:  # a slot holds half an expert
        monkeypatch.setattr(
            X, "_SLOT_BYTES", 3 * D * F * jnp.dtype(dtype).itemsize)
        assert X._f_tile(D, f, jnp.dtype(dtype).itemsize) == F
    key = jax.random.PRNGKey(tokens + k)
    xt = jax.random.normal(key, (tokens, D)).astype(dtype)
    weight = jax.random.uniform(jax.random.fold_in(key, 1), (tokens, k))
    scores = jax.random.normal(jax.random.fold_in(key, 2), (tokens, experts))
    idx = jax.lax.top_k(scores, k)[1].astype(jnp.int32)
    if case == "a_share_nobody_routes_to":
        idx = idx % 100  # every pair is another chip's
    valid = None
    if masked == "all":
        valid = jnp.zeros((tokens,), bool)
    elif masked:
        valid = jax.random.bernoulli(jax.random.fold_in(key, 3), 0.6, (tokens,))
    n_held = held[1] if held else experts
    compare(cfg, (
        xt, idx, weight, valid, banks(n_held, dtype, f), jnp.int32(layer)),
        tile, dtype)


def test_a_task_of_the_kernel_is_whole_tiles_of_rows():
    """The loop's block where that is 16 rows or more (a chunk), 16
    where it is 8 (a decode step, a short suffix): bf16 packs 16 rows a
    tile. A function of `pairs` and `experts` only, as `_task_block`."""
    assert X.MIN_ROWS == 16
    for pairs, experts, block in (
            (64, 128, 8), (96, 128, 8), (3072, 128, 32), (4096, 128, 32),
            (64, 256, 8), (4096, 256, 16)):
        assert M._task_block(pairs, experts) == block


# ---------------------------------------------------------------------------
# The chooser
# ---------------------------------------------------------------------------


def parents_routed_experts(xt, idx, weight, valid, banks, layer, cfg):
    """`routed_experts` as the parent commit (5e8374c) had it: the loop
    in its body, no chooser."""
    t, d = xt.shape
    k, e = idx.shape[1], cfg.num_experts_held
    pairs = t * k
    block = M._task_block(pairs, cfg.num_experts)
    flat = idx.reshape(pairs)
    if cfg.experts_held:
        flat = flat - cfg.experts_held[0]
        flat = jnp.where((flat >= 0) & (flat < e), flat, e)
    if valid is not None:
        flat = jnp.where(jnp.repeat(valid, k), flat, e)
    order = jnp.argsort(flat, stable=True)
    counts = jnp.zeros((e + 1,), jnp.int32).at[flat].add(1)[:e]
    n_tasks, task_ex, task_row0, task_rows = M.task_map(
        counts, block, pairs // block + e)
    xs = jnp.pad(xt[order // k], ((0, block), (0, 0)))
    rows = jnp.arange(block)[:, None]

    def task(i, ys):
        ex, row0 = task_ex[i], task_row0[i]
        xb = jax.lax.dynamic_slice(xs, (row0, 0), (block, d))
        yb = M._swiglu(xb, *(
            jax.lax.dynamic_slice(
                w, (layer, ex, 0, 0), (1, 1, *w.shape[2:])
            ).reshape(w.shape[2:])
            for w in banks
        ))
        old = jax.lax.dynamic_slice(ys, (row0, 0), (block, d))
        keep = rows < task_rows[i]
        return jax.lax.dynamic_update_slice(
            ys, jnp.where(keep, yb, old), (row0, 0))

    ys = jax.lax.fori_loop(0, n_tasks, task, jnp.zeros_like(xs))
    y = jnp.zeros((pairs, d), xt.dtype).at[order].set(ys[:pairs])
    out = (
        y.reshape(t, k, d).astype(jnp.float32) * weight[..., None]
    ).sum(1).astype(xt.dtype)
    routed = pairs if valid is None else valid.sum() * k
    stats = jnp.stack([
        (counts > 0).sum(), counts.max(), counts.sum(),
        routed - counts.sum()])
    return out, stats.astype(jnp.int32)


@pytest.mark.parametrize("held", [None, (4, 8)], ids=["whole", "a_share"])
def test_on_the_cpu_the_program_is_the_parents(held):
    cfg = dataclasses.replace(
        BASE, num_experts=16, experts_per_token=4, experts_held=held)
    tokens = 96
    operands = (
        jnp.zeros((tokens, D), jnp.bfloat16), jnp.zeros((tokens, 4), jnp.int32),
        jnp.zeros((tokens, 4), jnp.float32), jnp.zeros((tokens,), bool),
        banks(held[1] if held else 16, jnp.bfloat16), jnp.int32(1))
    before = attn_ops.dispatch_counts["grouped_experts"]

    def f(*a):  # one name for both: the module's name is in the text
        return f.body(*a, cfg)

    f.body = M.routed_experts
    mine = jax.jit(f).lower(*operands).as_text()
    f.body = parents_routed_experts
    parents = jax.jit(f).lower(*operands).as_text()
    assert mine == parents
    assert "stablehlo.while" in mine and "custom_call" not in mine
    assert attn_ops.dispatch_counts["grouped_experts"] == before


def spec(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def bank_specs(dtype, d=2048, f=768):
    return (spec((6, 128, d, f), dtype), spec((6, 128, d, f), dtype),
            spec((6, 128, f, d), dtype))


DECLINES = {
    "an_int8_bank": dict(banks=bank_specs(jnp.int8)),
    "a_float8_bank": dict(banks=bank_specs(jnp.float8_e4m3fn)),
    "float32_banks_under_bf16_tokens": dict(banks=bank_specs(jnp.float32)),
    "a_hidden_width_of_part_rows": dict(banks=bank_specs(jnp.bfloat16, d=2000)),
    "an_intermediate_width_of_part_rows": dict(
        banks=bank_specs(jnp.bfloat16, f=704)),
    "an_engine_with_no_kernels_on_its_mesh": dict(use_flash=False),
    "a_mesh_that_runs_kernels_per_shard": dict(flash_mesh=object()),
}


@pytest.mark.parametrize("case", DECLINES, ids=list(DECLINES))
def test_the_chooser_declines(case, monkeypatch):
    monkeypatch.setattr(attn_ops, "_on_tpu", lambda: True)
    call = dict(banks=bank_specs(jnp.bfloat16), use_flash=None, flash_mesh=None)
    call.update(DECLINES[case])
    x = spec((8, call["banks"][0].shape[2]), jnp.bfloat16)
    before = dict(attn_ops.dispatch_counts)
    assert X.grouped_experts(
        x, call["banks"], call["use_flash"], call["flash_mesh"]) is False
    assert attn_ops.dispatch_counts["grouped_experts"] == before.get(
        "grouped_experts", 0)
    by_mesh = call["use_flash"] is False or call["flash_mesh"] is not None
    assert attn_ops.dispatch_counts["xla_fallback"] - before.get(
        "xla_fallback", 0) == int(by_mesh)


@pytest.mark.parametrize("pairs", [64, 96, 3072, 4096])
def test_the_chooser_takes_the_served_calls_and_counts_them(pairs, monkeypatch):
    x = spec((pairs // 8, 2048), jnp.bfloat16)
    assert X.grouped_experts(x, bank_specs(jnp.bfloat16)) is False
    monkeypatch.setattr(attn_ops, "_on_tpu", lambda: True)
    before = dict(attn_ops.dispatch_counts)
    stats = attn_ops.dispatch_stats()
    assert X.grouped_experts(x, bank_specs(jnp.bfloat16)) is True
    assert attn_ops.dispatch_counts["grouped_experts"] == before.get(
        "grouped_experts", 0) + 1
    after = attn_ops.dispatch_stats()
    assert after["attn_kernel_programs"] == stats["attn_kernel_programs"] + 1
    assert after["attn_kernel_fallbacks"] == stats["attn_kernel_fallbacks"]


def test_the_dense_family_never_asks(monkeypatch):
    """A llama forward does not reach the experts' chooser: its
    programs are the parent's (scripts/tick_hlo.py --cpu compared the
    tick's HLO with the parent's byte for byte, PERF.md PR 43)."""
    def refuse(*a, **k):
        raise AssertionError("the dense family asked for the experts' kernel")

    monkeypatch.setattr(X, "grouped_experts", refuse)
    cfg = llama.CONFIGS["tiny-llama"]
    params = jax.eval_shape(lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    text = jax.jit(lambda p, t: llama.forward(p, cfg, t)[0]).lower(
        params, spec((2, 16), jnp.int32)).as_text()
    assert "grouped_experts" not in text


def test_the_smokes_experts_leg_rehearses_on_the_cpu():
    """`chip_smoke.py --legs experts`: the control flow of the leg that
    holds kernel and loop to float32 at keye's widths on the chip."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "chip_smoke.py"),
         "--cpu-rehearsal", "--legs", "experts"],
        capture_output=True, text=True, timeout=600, check=False)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    assert proc.stdout.count("experts hit") == 3
    assert "experts leg ok" in proc.stdout
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["ok"] is True and result["partial"] == ["experts"]
