"""The experts' task map (models/mla_moe.py `task_map`) and what it
replaced in `routed_experts`.

1. THE MAP — every block task's expert, first row and rows kept,
   against `jnp.searchsorted` over the cumulative task counts (what the
   loop body ran a task before): random and adversarial counts.
2. THE OUTPUT — `routed_experts` bit for bit against the parent's, kept
   here with its search inside the loop: integer arithmetic changed,
   and nothing else.

Marker `paged` (tier-1).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ggrmcp_tpu.models import mla_moe as M

pytestmark = pytest.mark.paged

CFG = M.CONFIGS["tiny-mla-moe"]
E = 16


def searched(counts, block):
    """What the parent's loop body computed for task i, for every task
    there is: (n, expert, first row, rows kept)."""
    counts = jnp.asarray(counts, jnp.int32)
    starts = jnp.cumsum(counts) - counts
    n_tasks = (counts + block - 1) // block
    task_end = jnp.cumsum(n_tasks)
    n = int(task_end[-1])
    i = jnp.arange(n, dtype=jnp.int32)
    ex = jnp.searchsorted(task_end, i, side="right").astype(jnp.int32)
    j = i - (task_end[ex] - n_tasks[ex])
    return n, ex, starts[ex] + j * block, counts[ex] - j * block


def drawn(seed, experts, pairs):
    """Counts of `pairs` pairs thrown at `experts` experts, skewed."""
    rng = np.random.default_rng(seed)
    p = rng.dirichlet(np.full(experts, 0.3))
    return np.bincount(rng.choice(experts, pairs, p=p), minlength=experts)


COUNTS = {
    "random_0": (drawn(0, E, 96), 8),
    "random_1": (drawn(1, E, 3072), 32),
    "random_2": (drawn(2, 128, 3072), 32),
    "random_decode_128": (drawn(3, 128, 96), 8),
    "no_pair_at_all": (np.zeros(E, int), 8),
    "one_pair": (np.eye(E, dtype=int)[5], 8),
    "every_pair_to_the_first": (np.eye(E, dtype=int)[0] * 96, 8),
    "every_pair_to_the_last": (np.eye(E, dtype=int)[E - 1] * 96, 8),
    "every_count_a_whole_block": (np.full(E, 16), 8),
    "one_over_a_whole_block": (np.full(E, 17), 8),
    "one_under_a_whole_block": (np.full(E, 15), 8),
    "empty_experts_between": (np.tile([0, 0, 9, 0], E // 4), 8),
    "empty_at_both_ends": (np.r_[0, 0, np.full(E - 4, 5), 0, 0], 8),
    "one_a_task": (np.ones(E, int), 8),
    "block_of_one": (drawn(4, E, 40), 1),
}


@pytest.mark.parametrize("case", COUNTS, ids=list(COUNTS))
def test_the_map_is_the_search(case):
    counts, block = COUNTS[case]
    max_tasks = int(counts.sum()) // block + len(counts)
    n, ex, row0, keep = jax.jit(M.task_map, static_argnums=(1, 2))(
        jnp.asarray(counts, jnp.int32), block, max_tasks)
    want_n, want_ex, want_row0, want_keep = searched(counts, block)
    assert int(n) == want_n <= max_tasks
    assert ex.shape == row0.shape == keep.shape == (max_tasks,)
    assert ex.dtype == row0.dtype == keep.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(ex)[:want_n], want_ex)
    np.testing.assert_array_equal(np.asarray(row0)[:want_n], want_row0)
    np.testing.assert_array_equal(np.asarray(keep)[:want_n], want_keep)
    # The tasks cover every pair once, in expert order, and a task the
    # loop never runs still names an expert there is.
    kept = np.clip(np.asarray(keep)[:want_n], 0, block)
    assert kept.sum() == counts.sum() and (kept > 0).all()
    assert (np.asarray(ex) >= 0).all() and (np.asarray(ex) < len(counts)).all()


def parents_routed_experts(xt, idx, weight, valid, banks, layer, cfg):
    """`routed_experts` as the parent commit had it: the task's expert
    found by `jnp.searchsorted` inside the loop."""
    t, d = xt.shape
    k, e = idx.shape[1], cfg.num_experts
    pairs = t * k
    block = M._task_block(pairs, e)
    flat = idx.reshape(pairs)
    if valid is not None:
        flat = jnp.where(jnp.repeat(valid, k), flat, e)
    order = jnp.argsort(flat, stable=True)
    counts = jnp.zeros((e + 1,), jnp.int32).at[flat].add(1)[:e]
    starts = jnp.cumsum(counts) - counts
    n_tasks = (counts + block - 1) // block
    task_end = jnp.cumsum(n_tasks)
    xs = jnp.pad(xt[order // k], ((0, block), (0, 0)))
    rows = jnp.arange(block)[:, None]

    def task(i, ys):
        ex = jnp.searchsorted(task_end, i, side="right").astype(jnp.int32)
        j = i - (task_end[ex] - n_tasks[ex])
        row0 = starts[ex] + j * block
        xb = jax.lax.dynamic_slice(xs, (row0, 0), (block, d))
        yb = M._swiglu(xb, *(
            jax.lax.dynamic_slice(
                w, (layer, ex, 0, 0), (1, 1, *w.shape[2:])
            ).reshape(w.shape[2:])
            for w in banks
        ))
        old = jax.lax.dynamic_slice(ys, (row0, 0), (block, d))
        keep = rows < counts[ex] - j * block
        return jax.lax.dynamic_update_slice(
            ys, jnp.where(keep, yb, old), (row0, 0))

    ys = jax.lax.fori_loop(0, task_end[-1], task, jnp.zeros_like(xs))
    y = jnp.zeros((pairs, d), xt.dtype).at[order].set(ys[:pairs])
    out = (
        y.reshape(t, k, d).astype(jnp.float32) * weight[..., None]
    ).sum(1).astype(xt.dtype)
    stats = jnp.stack([(counts > 0).sum(), counts.max(), counts.sum()])
    return out, stats.astype(jnp.int32)


@pytest.fixture(scope="module")
def banks():
    params = jax.jit(lambda k: M.init_params(k, CFG))(jax.random.PRNGKey(7))
    return tuple(params["layers"][n] for n in ("w_gate", "w_up", "w_down"))


def routing(tokens, how, dtype, seed=11):
    """Fixed inputs: tokens, their experts and weights, who is real."""
    key = jax.random.PRNGKey(seed)
    k, e = CFG.experts_per_token, CFG.num_experts
    xt = jax.random.normal(key, (tokens, CFG.hidden_dim)).astype(dtype)
    weight = jax.random.uniform(jax.random.fold_in(key, 1), (tokens, k))
    valid = None
    if how == "one_expert":  # every pair of every token to expert 3
        idx = jnp.full((tokens, k), 3, jnp.int32)
    elif how == "two_experts":
        idx = jnp.broadcast_to(jnp.asarray([e - 1, 0], jnp.int32)[:k], (tokens, k))
    else:
        scores = jax.random.normal(jax.random.fold_in(key, 2), (tokens, e))
        _, idx = jax.lax.top_k(scores, k)
    if how == "masked_rows":
        valid = jax.random.bernoulli(jax.random.fold_in(key, 3), 0.6, (tokens,))
    elif how == "nobody_real":
        valid = jnp.zeros((tokens,), bool)
    return xt, idx.astype(jnp.int32), weight, valid


ROUTINGS = {
    "a_decode_tick": (16, "random", jnp.float32),
    "a_chunk": (512, "random", jnp.float32),
    "a_chunk_bf16": (512, "random", jnp.bfloat16),
    "one_token": (1, "random", jnp.float32),
    "masked_rows": (96, "masked_rows", jnp.float32),
    "masked_rows_bf16": (96, "masked_rows", jnp.bfloat16),
    "nobody_real": (32, "nobody_real", jnp.float32),
    "every_pair_to_one_expert": (64, "one_expert", jnp.float32),
    "two_experts_at_the_ends": (40, "two_experts", jnp.float32),
}


@pytest.mark.parametrize("case", ROUTINGS, ids=list(ROUTINGS))
@pytest.mark.parametrize("layer", [0, 1])
def test_routed_experts_is_the_parents_bit_for_bit(banks, case, layer):
    tokens, how, dtype = ROUTINGS[case]
    xt, idx, weight, valid = routing(tokens, how, dtype)
    args = (xt, idx, weight, valid,
            tuple(w.astype(dtype) for w in banks), jnp.int32(layer))
    got, got_stats = jax.jit(lambda *a: M.routed_experts(*a, CFG))(*args)
    want, want_stats = jax.jit(
        lambda *a: parents_routed_experts(*a, CFG))(*args)
    assert got.dtype == want.dtype == dtype
    np.testing.assert_array_equal(
        np.asarray(got, np.float32), np.asarray(want, np.float32))
    # experts hit, largest load, pairs computed: the parent's three;
    # since PR 33 a fourth, the pairs whose expert the chip does not
    # hold, which is none where it holds every expert
    np.testing.assert_array_equal(
        np.asarray(got_stats), np.append(np.asarray(want_stats), 0))
    if how == "nobody_real":
        assert not np.asarray(got, np.float32).any()
    else:
        assert float(np.abs(np.asarray(got, np.float32)).max()) > 1e-3


def test_no_search_is_left_in_the_program(banks):
    """The loop the task map feeds holds no `while` of its own: the
    binary search was the only nested loop of the expert layer."""
    xt, idx, weight, valid = routing(64, "masked_rows", jnp.float32)
    text = jax.jit(lambda *a: M.routed_experts(*a, CFG)).lower(
        xt, idx, weight, valid, banks, jnp.int32(0)).as_text()
    parent = jax.jit(lambda *a: parents_routed_experts(*a, CFG)).lower(
        xt, idx, weight, valid, banks, jnp.int32(0)).as_text()
    assert text.count("stablehlo.while") == 1
    assert parent.count("stablehlo.while") > 1
