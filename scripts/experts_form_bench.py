#!/usr/bin/env python3
"""The routed experts as a loop or as one kernel? Times
`models/mla_moe.routed_experts` in both forms, one layer of stacked
banks at the served widths, on the chip:

    python3 scripts/experts_form_bench.py            # on the TPU
    python3 scripts/experts_form_bench.py --cpu      # rehearsal, tiny sizes

- the XLA loop (`_looped_tasks`: a `fori_loop` of three sliced matmuls
  a block task), what every platform ran before the kernel and the CPU
  still does;
- the grouped SwiGLU kernel (`ops/experts.grouped_swiglu` behind
  `_grouped_tasks`), at the row tile `routed_experts` gives it (the
  loop's block, 16 rows at the least) and at every larger tile of
  `--tiles` beside it (how the tile was chosen);
- the kernel alone, its rows laid out and its task map made outside
  the timed program: what the sort, the row gathers and the weighted
  sum around it cost is the difference.

Shapes: (hidden 2,048, intermediate 768, 128 experts, 6 layers stacked:
keye top-8 and kanana top-6) x (a decode step of 8 rows, one of 16, a
512-token chunk of each) and (7,168 / 2,048 / 16 held of 256 experts, 4
layers stacked: the deepseek-v3.2 share) x (a decode step, a chunk).
Routing is drawn as the cells' counters say it falls: even at decode
(38% of 128 experts hit by 64 pairs), skewed in a chunk
(`moe_load_max_over_mean` ~8 at 3,072 pairs); the line says what was
drawn. Prints milliseconds a layer (median of `--reps` programs of
`--inner` layers each, so that a program's ~0.7 ms of host launch is
shared) and the GB/s of expert bytes that is: every expert hit, once,
over the time, whatever the form reads. Then the kernel's largest
difference from the loop, as a share of the loop's largest value.
"""

from __future__ import annotations

import argparse
import functools
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# (registry model, tokens, skew of the draw: 0 even, else the Dirichlet
# concentration of the experts' shares)
CHIP_CASES = [
    ("keye-vl-2.0-30b-a3b-6l", 8, 0.0),
    ("keye-vl-2.0-30b-a3b-6l", 512, 0.35),
    ("kanana-2-30b-a3b-6l", 16, 0.0),
    ("kanana-2-30b-a3b-6l", 512, 0.35),
    ("deepseek-v3.2-ep16-5l", 8, 0.0),
    ("deepseek-v3.2-ep16-5l", 512, 0.35),
]
CPU_CASES = [
    ("tiny-mla-moe", 8, 0.0), ("tiny-mla-moe", 64, 0.35),
    ("tiny-dsv32", 8, 0.0), ("tiny-dsv32", 64, 0.35),
]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--inner", type=int, default=8,
                    help="layers a timed program runs, one after another")
    ap.add_argument("--tiles", default="",
                    help="row tiles to time beside the chosen one, e.g. 32,128")
    args = ap.parse_args()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    from ggrmcp_tpu.models import keye
    from ggrmcp_tpu.models import mla_moe as M

    configs = {**M.CONFIGS, **keye.CONFIGS}
    tiles = [int(t) for t in args.tiles.split(",") if t]
    print(f"device {jax.devices()[0].device_kind}")
    banks, held = None, None
    for name, tokens, skew in CPU_CASES if args.cpu else CHIP_CASES:
        cfg = configs[name]
        layers = cfg.num_layers - getattr(cfg, "first_dense_layers", 0)
        shape = (layers, cfg.num_experts_held, cfg.hidden_dim,
                 cfg.expert_ffn_dim, cfg.jnp_dtype)
        if shape != held:  # one set of banks at a time on the device
            banks = None
            banks, held = draw_banks(*shape), shape
        bench(args, cfg, name, tokens, skew, banks, tiles)
    return 0


def draw_banks(layers, experts, d, f, dtype):
    import jax

    def bank(seed, *dims):  # drawn on the device, a layer at a time
        return jax.jit(lambda keys: jax.lax.map(
            lambda key: jax.random.normal(key, (experts, *dims), dtype)
            * dims[0] ** -0.5, keys)
        )(jax.random.split(jax.random.PRNGKey(seed), layers))

    return bank(1, d, f), bank(2, d, f), bank(3, f, d)


def draw_routing(cfg, tokens: int, skew: float, seed: int = 5):
    """Each token's k distinct experts of `cfg.num_experts`: Gumbel
    top-k over shares that are even (`skew` 0) or Dirichlet-drawn."""
    import numpy as np

    rng = np.random.default_rng(seed)
    e, k = cfg.num_experts, cfg.experts_per_token
    share = (np.full(e, 1.0 / e) if not skew
             else rng.dirichlet(np.full(e, skew)) + 1e-9)
    scores = np.log(share)[None] + rng.gumbel(size=(tokens, e))
    return np.argsort(-scores, axis=1)[:, :k].astype(np.int32)


def bench(args, cfg, name, tokens, skew, banks, tiles) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ggrmcp_tpu.models import mla_moe as M
    from ggrmcp_tpu.ops import experts as X

    k, d = cfg.experts_per_token, cfg.hidden_dim
    pairs = tokens * k
    idx = draw_routing(cfg, tokens, skew)
    key = jax.random.PRNGKey(tokens)
    xt = jax.random.normal(key, (tokens, d), cfg.jnp_dtype)
    weight = jax.random.uniform(jax.random.fold_in(key, 1), (tokens, k))
    layer = jnp.int32(banks[0].shape[0] - 1)
    operands = (xt, jnp.asarray(idx), weight, banks, layer)

    def programs():
        """A form's two programs, traced anew for every form (one
        function object would be one cached trace): `--inner` layers,
        each fed the last one's result, and one layer's result."""
        def program(xt, idx, weight, banks, layer):
            def one(_, carry):
                x, _ = carry
                out, stats = M.routed_experts(
                    x, idx, weight, None, banks, layer, cfg)
                return (xt + out * 1e-3).astype(xt.dtype), stats

            return jax.lax.fori_loop(
                0, args.inner, one, (xt, jnp.zeros((4,), jnp.int32)))

        def single(xt, idx, weight, banks, layer):
            return M.routed_experts(
                xt, idx, weight, None, banks, layer, cfg)[0]

        return jax.jit(program), jax.jit(single)

    block = M._task_block(pairs, cfg.num_experts)
    chosen = max(block, X.MIN_ROWS)
    forms = {"loop": None, f"kernel, {chosen} rows a task": chosen}
    forms.update({
        f"kernel, {t} rows a task": t for t in tiles if t > chosen})
    was = X.grouped_experts, X.grouped_swiglu, X.MIN_ROWS
    got, stats = {}, None

    def line(form, ms):
        hit, load_max, here, _ = (int(v) for v in stats)
        mb = hit * 3 * d * cfg.expert_ffn_dim * banks[0].dtype.itemsize / 1e6
        print(
            f"{name} tokens {tokens:4d} pairs {pairs:5d} (loop block {block}; "
            f"{hit} of {cfg.num_experts_held} held experts hit, {here} pairs "
            f"here, max/mean load {load_max * hit / max(here, 1):.1f}) {form}: "
            f"{ms:8.3f} ms a layer, {mb:7.1f} MB of experts, "
            f"{mb / ms:6.1f} GB/s", flush=True)

    try:
        if args.cpu:  # no Mosaic here: the interpreter, for the control flow
            X.grouped_swiglu = functools.partial(was[1], interpret=True)
        for form, tile in forms.items():
            X.grouped_experts = lambda *a, tile=tile: tile is not None
            X.MIN_ROWS = tile or was[2]
            fn, single = programs()
            _, stats = jax.block_until_ready(fn(*operands))
            ms = median_ms(fn, operands, args.reps) / args.inner
            got[form] = np.asarray(single(*operands), np.float32)
            line(form, ms)
        alone = kernel_alone(args, cfg, chosen, *operands)
        jax.block_until_ready(alone())
        line(f"kernel alone, {chosen} rows a task",
             median_ms(alone, (), args.reps) / args.inner)
    finally:
        X.grouped_experts, X.grouped_swiglu, X.MIN_ROWS = was
    scale = float(np.abs(got["loop"]).max()) or 1.0
    for form, out in got.items():
        if form != "loop":
            print(f"  {form}: max|kernel - loop| / max|loop| = "
                  f"{float(np.abs(out - got['loop']).max()) / scale:.2e}",
                  flush=True)


def kernel_alone(args, cfg, tile, xt, idx, weight, banks, layer):
    """`grouped_swiglu` `--inner` times over rows laid out beforehand
    (what `mla_moe._grouped_tasks` does in front of it), as a program
    of no operands."""
    import jax
    import jax.numpy as jnp

    from ggrmcp_tpu.models import mla_moe as M
    from ggrmcp_tpu.ops import experts as X

    k, e = cfg.experts_per_token, cfg.num_experts_held
    pairs = idx.size
    flat = idx.reshape(pairs)
    if cfg.experts_held:
        flat = flat - cfg.experts_held[0]
        flat = jnp.where((flat >= 0) & (flat < e), flat, e)
    order = jnp.argsort(flat, stable=True)
    counts = jnp.zeros((e + 1,), jnp.int32).at[flat].add(1)[:e]
    rows, n_tasks, task_ex, task_rows = M._task_tiles(
        xt, order, counts, k, tile)

    def program(rows, banks, layer, n_tasks, task_ex, task_rows):
        def one(_, x):
            ys = X.grouped_swiglu(
                x, *banks, layer, n_tasks, task_ex, task_rows, block=tile)
            return (rows + ys * 1e-3).astype(rows.dtype)

        return jax.lax.fori_loop(0, args.inner, one, rows)

    return functools.partial(
        jax.jit(program), rows, banks, layer, n_tasks, task_ex, task_rows)


def median_ms(fn, operands, reps: int) -> float:
    """Of a program its caller has already run once (compiled)."""
    import jax

    times = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*operands))
        times.append((time.perf_counter() - t) * 1000.0)
    return statistics.median(times)


if __name__ == "__main__":
    sys.exit(main())
