"""The routed experts as one grouped SwiGLU Pallas kernel.

`models/mla_moe.routed_experts` sorts a step's (token, expert) pairs by
expert and cuts them into block tasks (`task_map`): up to `block` rows
of ONE expert a task. Its XLA form is a `fori_loop` of three
`dynamic_slice`d matmuls a task: three kernels, each starting a cold
stream of one matrix behind a scalar loop iteration, an expert read
again for every task it has. `grouped_swiglu` is that loop as one
kernel a layer:

- the three STACKED banks `[layers, E, ..]` stay in HBM, whole, and are
  addressed `[layer, task_ex[i]]` by hand-issued DMA (sliced out a
  layer first, XLA would copy a layer's bank in front of the call);
- an expert's matrices cross HBM once a call: consecutive tasks of one
  expert reuse the buffer, and the next expert's matrices are in flight
  while this one multiplies (two slots); where one expert's three
  matrices do not fit a slot (7,168 x 2,048), they travel in tiles of
  the intermediate width and every task streams them once;
- gate and up products, `silu(g) * u` (float32) and the down product in
  one pass, accumulated in float32 in VMEM, one result a row in the
  activations' dtype;
- the walk is bounded by `n_tasks`, read from SMEM: the tasks of the
  static bound that do not exist cost nothing;
- rows arrive a task a tile (`[max_tasks * block, D]`, the caller's
  gather), so every row DMA is tile-aligned and a task's tail rows are
  its own padding, not the next expert's.

`grouped_experts` picks the kernel from what the call can see
(platform, dtypes, widths, mesh), as `ops.attention.paged_decode` and
`latent_prefill` do, and counts it in `attention.dispatch_counts`.
"""

from __future__ import annotations

import functools
import logging
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ggrmcp_tpu.ops import attention as attn_ops

logger = logging.getLogger(__name__)

# Bytes of one slot of expert weights (a tile of the intermediate width
# of all three matrices). Two slots are resident: 9.4 MB a slot at
# (2,048 x 768) holds an expert whole; at (7,168 x 2,048) a 256-wide
# tile is 11 MB.
_SLOT_BYTES = 12 << 20


# The gate's activation, by the name a config gives it
# (`cfg.expert_act`): the kernel's body and the XLA loop
# (`mla_moe._swiglu`) read the same table.
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


# Rows of a task at the least: bf16 packs 16 rows a tile, and a row DMA
# moves whole tiles.
MIN_ROWS = 16


def _f_tile(d: int, f: int, itemsize: int) -> int:
    """Columns of the intermediate width a slot holds: `f` whole where
    three `[d, f]` matrices fit `_SLOT_BYTES`, else the largest
    128-lane multiple dividing `f` that does (128 at the least)."""
    tile = f
    while 3 * d * tile * itemsize > _SLOT_BYTES and tile % 256 == 0:
        tile //= 2
    return tile


def _vmem_bytes(block: int, d: int, ft: int, itemsize: int) -> int:
    """VMEM the kernel needs, told to the compiler: two slots of weight
    tiles; a task's rows in and out, two slots each; the float32 gate,
    up and hidden tiles and the float32 result and accumulator; 4 MiB
    of headroom for Mosaic's own scratch. 24 MiB at (2,048 x 768), 16
    rows; 44 MiB at (7,168 x 2,048), 128 rows."""
    weights = 2 * 3 * d * ft * itemsize
    rows = 2 * 2 * block * d * itemsize
    work = 4 * block * (3 * ft + 2 * d)
    return weights + rows + work + (4 << 20)


def _grouped_swiglu_kernel(
    layer_ref,  # SMEM [1] int32
    n_ref,  # SMEM [1] int32 — tasks there are
    ex_ref,  # SMEM [max_tasks] int32 — a task's expert
    rows_ref,  # SMEM [max_tasks] int32 — rows of its expert from the task on
    x_hbm,  # HBM [max_tasks * block, D] — a task's rows a tile
    wg_hbm,  # HBM [L, E, D, F] — every layer's experts, never copied
    wu_hbm,  # HBM [L, E, D, F]
    wd_hbm,  # HBM [L, E, F, D]
    y_hbm,  # HBM [max_tasks * block, D] — written for tasks there are
    x_buf,  # VMEM [2, block, D]
    y_buf,  # VMEM [2, block, D]
    wg_buf,  # VMEM [2, D, ft]
    wu_buf,  # VMEM [2, D, ft]
    wd_buf,  # VMEM [2, ft, D]
    x_sems,  # DMA [2]
    y_sems,  # DMA [2]
    w_sems,  # DMA [3, 2]
    *acc,  # with tiles of F: VMEM [block, D] float32
    block: int,
    ft: int,
    act: str,
):
    """The whole walk in one grid step: units (task, tile of F) in
    order, the next unit's weights in flight while this one multiplies.
    With one tile (an expert fits a slot) a unit whose expert is the
    last unit's fetches nothing, and the expert after this one is
    started when this one's first task is."""
    layer, n = layer_ref[0], n_ref[0]
    nf = wg_hbm.shape[-1] // ft

    def when(cond):  # `pl.when`, or plainly where the condition is static
        return (lambda body: body()) if cond is True else pl.when(cond)

    def weights(ex, j, slot):
        if nf == 1:  # an expert whole: three contiguous streams
            src = (wg_hbm.at[layer, ex], wu_hbm.at[layer, ex],
                   wd_hbm.at[layer, ex])
        else:
            cols = pl.ds(pl.multiple_of(j * ft, 128), ft)
            src = (wg_hbm.at[layer, ex, :, cols], wu_hbm.at[layer, ex, :, cols],
                   wd_hbm.at[layer, ex, cols, :])
        return [
            pltpu.make_async_copy(hbm, buf.at[slot], w_sems.at[m, slot])
            for m, (hbm, buf) in enumerate(zip(src, (wg_buf, wu_buf, wd_buf)))
        ]

    def rows_in(i, slot):
        return pltpu.make_async_copy(
            x_hbm.at[pl.ds(pl.multiple_of(i * block, block), block)],
            x_buf.at[slot], x_sems.at[slot])

    def rows_out(i, slot):
        return pltpu.make_async_copy(
            y_buf.at[slot],
            y_hbm.at[pl.ds(pl.multiple_of(i * block, block), block)],
            y_sems.at[slot])

    @pl.when(n > 0)
    def _():
        for copy in weights(ex_ref[0], 0, 0):
            copy.start()
        rows_in(0, 0).start()

    def unit(u, w_slot):
        i, j = (u, 0) if nf == 1 else (u // nf, u % nf)
        ex = ex_ref[i]
        if nf == 1:  # the expert's first task fetches, and starts the next
            fetch = (i == 0) | (ex_ref[jnp.maximum(i - 1, 0)] != ex)
            after = i + (rows_ref[i] + block - 1) // block
            w_slot = jnp.where(fetch & (i > 0), 1 - w_slot, w_slot)
        else:
            fetch, after, w_slot = True, u + 1, u % 2
        slot = i % 2
        gate, up, down = weights(ex, j, w_slot)

        @when(fetch)
        def _():
            @pl.when(after < n * nf)
            def _():
                nxt = after if nf == 1 else after // nf
                for copy in weights(ex_ref[nxt], after % nf, 1 - w_slot):
                    copy.start()

        @when(j == 0)
        def _():
            @pl.when(i + 1 < n)
            def _():
                rows_in(i + 1, 1 - slot).start()

            rows_in(i, slot).wait()

        x = x_buf[slot]
        # each matrix is waited for where it is first used: the gate
        # product runs while the other two still arrive
        when(fetch)(gate.wait)
        g = jnp.dot(x, wg_buf[w_slot], preferred_element_type=jnp.float32)
        when(fetch)(up.wait)
        h = ACTIVATIONS[act](g) * jnp.dot(
            x, wu_buf[w_slot], preferred_element_type=jnp.float32)
        when(fetch)(down.wait)
        part = jnp.dot(
            h.astype(x.dtype), wd_buf[w_slot],
            preferred_element_type=jnp.float32)

        def emit(y):
            # the slot's last result has left before it is overwritten
            pl.when(i >= 2)(rows_out(i - 2, slot).wait)
            y_buf[slot] = y.astype(y_buf.dtype)
            rows_out(i, slot).start()

        if nf == 1:
            emit(part)
        else:
            (acc_ref,) = acc

            @pl.when(j == 0)
            def _():
                acc_ref[...] = part

            @pl.when(j > 0)
            def _():
                acc_ref[...] += part

            pl.when(j == nf - 1)(lambda: emit(acc_ref[...]))
        return w_slot

    jax.lax.fori_loop(0, n * nf, unit, 0)
    for back in (1, 2):  # the last two results are still leaving
        pl.when(n >= back)(rows_out(n - back, (n - back) % 2).wait)


@functools.partial(jax.jit, static_argnames=("block", "interpret", "act"))
def grouped_swiglu(
    x: jnp.ndarray,  # [max_tasks * block, D] — task i's rows at i * block
    w_gate: jnp.ndarray,  # [L, E, D, F]
    w_up: jnp.ndarray,  # [L, E, D, F]
    w_down: jnp.ndarray,  # [L, E, F, D]
    layer: jnp.ndarray,  # scalar layer index
    n_tasks: jnp.ndarray,  # scalar: tasks there are (<= max_tasks)
    task_ex: jnp.ndarray,  # [max_tasks] a task's expert
    task_rows: jnp.ndarray,  # [max_tasks] its expert's rows from it on
    *,
    block: int,
    interpret: bool = False,
    act: str = "silu",
) -> jnp.ndarray:
    """`(act(x W_gate) * (x W_up)) W_down` of every block task
    (`mla_moe.task_map`) with its own expert's matrices `[layer,
    task_ex[i]]`, read in place out of the stacked banks. Row r of task
    i is row `i * block + r` of `x` and of the result; rows of a task
    past its expert's (`task_rows`) are computed like the others and
    the caller's to drop; tasks from `n_tasks` on are not computed and
    their rows of the result are undefined. Operands in the banks'
    dtype, every product accumulated in float32, `act(g) * u` in
    float32, `act` one of ACTIVATIONS (the caller's: SwiGLU's silu, or
    relu for a ReGLU expert). Compiled for the TPU unless `interpret=True` (CPU tests)
    asks for the interpreter."""
    d, f = w_gate.shape[2:]
    max_tasks = task_ex.shape[0]
    assert x.shape == (max_tasks * block, d), (x.shape, max_tasks, block, d)
    assert w_up.shape == w_gate.shape and w_down.shape == (
        *w_gate.shape[:2], f, d), (w_gate.shape, w_up.shape, w_down.shape)
    ft = _f_tile(d, f, w_gate.dtype.itemsize)
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    scratch = [
        pltpu.VMEM((2, block, d), x.dtype), pltpu.VMEM((2, block, d), x.dtype),
        pltpu.VMEM((2, d, ft), w_gate.dtype),
        pltpu.VMEM((2, d, ft), w_up.dtype),
        pltpu.VMEM((2, ft, d), w_down.dtype),
        pltpu.SemaphoreType.DMA((2,)), pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((3, 2)),
    ]
    if ft != f:
        scratch.append(pltpu.VMEM((block, d), jnp.float32))
    return pl.pallas_call(
        functools.partial(
            _grouped_swiglu_kernel, block=block, ft=ft, act=act),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(1,),
            in_specs=[any_space] * 4,
            out_specs=any_space,
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_bytes(block, d, ft, w_gate.dtype.itemsize),
        ),
        name="grouped_experts_swiglu",
        interpret=interpret,
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        jnp.reshape(n_tasks, (1,)).astype(jnp.int32),
        task_ex.astype(jnp.int32), task_rows.astype(jnp.int32),
        x, w_gate, w_up, w_down,
    )


def grouped_experts(
    x: jnp.ndarray,  # [T, D] the tokens
    banks,  # (w_gate, w_up, w_down), stacked [L, E, ..]
    use_flash: Optional[bool] = None,
    flash_mesh=None,
) -> bool:
    """Whether the call is `grouped_swiglu`'s kind; where not, the
    caller runs its loop (`mla_moe.routed_experts`). Chosen from what
    the call can see, no option: a TPU; banks stored in the
    activations' own dtype (an int8 or float8 bank is turned away
    here); both widths whole 128-lane rows. A call of that kind under
    an engine that runs no kernel on its mesh (`use_flash=False`) or
    runs them per shard (`flash_mesh`: the banks shard over `expert`
    and `tensor`, and the kernel has no per-shard form yet) runs the
    loop too, logged and counted as a fallback."""
    d, f = banks[0].shape[2:]
    if (
        not attn_ops._on_tpu()
        or any(w.dtype != x.dtype for w in banks)
        or d % 128 != 0
        or f % 128 != 0
    ):
        return False
    if use_flash is False or flash_mesh is not None:
        attn_ops.dispatch_counts["xla_fallback"] += 1
        logger.warning(
            "experts: x%s banks%s wanted the grouped Pallas kernel (%s) — "
            "this program runs the task loop in XLA instead (watch gauge "
            "attn_kernel_fallbacks)",
            tuple(x.shape), tuple(banks[0].shape),
            "the engine runs no kernel on this mesh" if use_flash is False
            else "the kernel has no per-shard form",
        )
        return False
    attn_ops.dispatch_counts["grouped_experts"] += 1
    logger.info(
        "experts: grouped SwiGLU Pallas kernel for x%s banks%s",
        tuple(x.shape), tuple(banks[0].shape),
    )
    return True
