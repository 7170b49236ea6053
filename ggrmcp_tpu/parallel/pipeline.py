"""Pipeline parallelism (PP) over the mesh's `stage` axis.

GPipe-style microbatch pipelining for the decoder layer stack, built
the TPU way (SURVEY.md §2.4 names PP as a first-class component of the
new framework; the Go reference has no model execution at all):

- The stacked [L, ...] layer weights are sharded over `stage` on the
  layer dimension — each stage holds a contiguous block of L/S layers.
- `jax.shard_map` runs manual collectives over ONLY the `stage` axis
  (`axis_names={"stage"}`); every other mesh axis (data/fsdp/tensor/
  sequence) stays under XLA's automatic SPMD partitioning, so tensor
  parallelism composes with pipelining inside the stage body without
  hand-written all-reduces.
- The schedule is a single `lax.scan` over S+M-1 ticks. Each tick every
  stage runs its local layer block on its current microbatch, then the
  activation rotates one hop along the ring via `lax.ppermute` — the
  classic bubble-fill/drain schedule, expressed as one compiled XLA
  program (differentiable: scan + ppermute both transpose cleanly, so
  the same code serves training).
- Embedding, final norm and the LM head run OUTSIDE the pipeline in
  plain auto-sharded (TP/DP) form; only the layer stack is staged.

Scope: full-sequence forward (training / scoring) AND cached serving
(`pipeline_forward_cached`): the same tick schedule threads each
stage's local [L/S, ...] KV-cache block, with microbatches slicing the
batch dimension — so prefill and batched decode both pipeline across
stages. This is the serve-a-model-bigger-than-a-slice path; on meshes
where the model fits, TP/DP remain the better choice (decode latency
still pays the S-stage traversal).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ggrmcp_tpu.models import common
from ggrmcp_tpu.models import llama as llama_mod
from ggrmcp_tpu.ops import quant
from ggrmcp_tpu.parallel import mesh as mesh_mod


def stage_count(mesh: Mesh) -> int:
    return mesh_mod.axis_size(mesh, "stage")


def param_specs_pp(cfg: llama_mod.LlamaConfig) -> common.Params:
    """`param_specs` with the stacked layer dimension sharded over
    `stage` (TP axes unchanged — PP × TP compose)."""
    fam = _family(cfg)
    specs = fam.param_specs(cfg)

    def stage_first(spec: P) -> P:
        rest = tuple(spec)[1:]
        return P("stage", *rest)

    specs["layers"] = jax.tree_util.tree_map(
        stage_first, specs["layers"], is_leaf=lambda x: isinstance(x, P)
    )
    return specs


def _family(cfg):
    from ggrmcp_tpu.models import family_module

    return family_module(cfg)


def _run_block(layers_local, x, cfg, positions, fam):
    """Scan this stage's local layer block (no cache: training path)."""
    from ggrmcp_tpu.models import moe as moe_mod

    if fam is moe_mod:

        def body(h, lp):
            h, _, aux = fam._layer(h, lp, cfg, positions, None, None, None, None)
            return h, aux

        x, auxes = jax.lax.scan(body, x, layers_local)
        return x, jnp.mean(auxes)

    def body(h, lp):
        h, _ = fam._layer(h, lp, cfg, positions, None, None, None)
        return h, None

    x, _ = jax.lax.scan(body, x, layers_local)
    return x, jnp.float32(0.0)


def pipeline_layers(
    layers: common.Params,
    cfg: llama_mod.LlamaConfig,
    x: jnp.ndarray,  # [B, S, D]
    positions: jnp.ndarray,  # [B, S]
    mesh: Mesh,
    num_microbatches: Optional[int] = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Run the stacked layer block through the stage pipeline.

    Returns (activations [B, S, D], mean router aux loss — 0 for dense).
    Batch B must divide into `num_microbatches` (default: stage count).
    """
    S = stage_count(mesh)
    fam = _family(cfg)
    if S == 1:
        x, aux = _run_block(layers, x, cfg, positions, fam)
        return x, aux
    M = num_microbatches or S
    b = x.shape[0]
    if b % M != 0:
        raise ValueError(f"batch {b} not divisible by {M} microbatches")
    if cfg.num_layers % S != 0:
        raise ValueError(f"{cfg.num_layers} layers not divisible by {S} stages")

    mb = b // M
    x_mb = x.reshape(M, mb, *x.shape[1:])
    pos_mb = positions.reshape(M, mb, positions.shape[1])

    layer_specs = jax.tree_util.tree_map(lambda _: P("stage"), layers)
    fwd = partial(_pipelined, cfg=cfg, fam=fam, num_stages=S, num_micro=M)
    out, aux = jax.shard_map(
        fwd,
        mesh=mesh,
        axis_names={"stage"},
        in_specs=(layer_specs, P(), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )(layers, x_mb, pos_mb)
    return out.reshape(b, *x.shape[1:]), aux


def _pipelined(layers_local, x_mb, pos_mb, *, cfg, fam, num_stages, num_micro):
    """Per-stage body (manual over `stage` only). x_mb/pos_mb are the
    full microbatch arrays, replicated over `stage`; layers_local is
    this stage's [L/S, ...] block."""
    S, M = num_stages, num_micro
    stage = jax.lax.axis_index("stage")
    perm = [(i, (i + 1) % S) for i in range(S)]

    state0 = jnp.zeros(x_mb.shape[1:], x_mb.dtype)
    out0 = jnp.zeros_like(x_mb)
    aux0 = jnp.float32(0.0)

    def tick(carry, t):
        state, out, aux = carry
        # Stage 0 ingests microbatch t (clipped: ticks >= M feed junk
        # that drains past the output window and is never stored).
        m_in = jnp.clip(t, 0, M - 1)
        inp = jax.lax.dynamic_index_in_dim(x_mb, m_in, 0, keepdims=False)
        state = jnp.where(stage == 0, inp, state)
        # This stage is processing microbatch m = t - stage.
        m = jnp.clip(t - stage, 0, M - 1)
        pos = jax.lax.dynamic_index_in_dim(pos_mb, m, 0, keepdims=False)
        y, block_aux = _run_block(layers_local, state, cfg, pos, fam)
        live = (t - stage >= 0) & (t - stage < M)
        aux = aux + jnp.where(live, block_aux, 0.0)
        # Last stage stores finished microbatch t-(S-1) once it exists.
        m_out = t - (S - 1)
        upd = jax.lax.dynamic_update_index_in_dim(
            out, y, jnp.clip(m_out, 0, M - 1), 0
        )
        out = jnp.where((stage == S - 1) & (m_out >= 0), upd, out)
        # Rotate activations one hop along the stage ring.
        state = jax.lax.ppermute(y, "stage", perm)
        return (state, out, aux), None

    (state, out, aux), _ = jax.lax.scan(
        tick, (state0, out0, aux0), jnp.arange(S + M - 1)
    )
    # `out` is complete only on the last stage; the masked psum
    # replicates it (one all-gather-sized collective over `stage`).
    out = jax.lax.psum(jnp.where(stage == S - 1, out, 0), "stage")
    # Each stage accumulated aux over its M live ticks; psum/(S*M) is
    # the global per-layer-block mean.
    aux = jax.lax.psum(aux, "stage") / (S * M)
    return out, aux


def pipeline_forward(
    params: common.Params,
    cfg: llama_mod.LlamaConfig,
    tokens: jnp.ndarray,  # [B, S]
    mesh: Mesh,
    num_microbatches: Optional[int] = None,
) -> jnp.ndarray:
    """Full forward (embed → staged layers → norm → head) for training
    and scoring. Same logits contract as `llama.forward(..., cache=None)`.
    """
    logits, _ = pipeline_forward_with_aux(
        params, cfg, tokens, mesh, num_microbatches
    )
    return logits


def pipeline_forward_with_aux(
    params: common.Params,
    cfg: llama_mod.LlamaConfig,
    tokens: jnp.ndarray,
    mesh: Mesh,
    num_microbatches: Optional[int] = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    b, s = tokens.shape
    x = params["embed"].astype(cfg.jnp_dtype)[tokens]
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    x, aux = pipeline_layers(
        params["layers"], cfg, x, positions, mesh, num_microbatches
    )
    x = common.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = x @ params["lm_head"].astype(cfg.jnp_dtype)
    return logits.astype(jnp.float32), aux


# ---------------------------------------------------------------------------
# Cached (serving) pipeline: prefill + decode with a staged KV cache
# ---------------------------------------------------------------------------


def cache_specs_pp() -> llama_mod.KVCache:
    """KV cache sharding for the staged path: layer dim over `stage`
    (batch over data as usual, heads over tensor)."""
    spec = P("stage", ("data", "fsdp"), None, "tensor", None)
    return llama_mod.KVCache(
        k=spec, v=spec, length=P(("data", "fsdp"))
    )


def _run_block_cached(
    layers_local, x, cfg, positions, ck, cv, clen, fam, ring=False
):
    """Scan this stage's local layer block threading its cache block.
    ck/cv: [L/S, mb, S_max, KVH, D] for the current microbatch's rows —
    dense arrays or QuantizedArray (int8 KV) pytrees; scan slices the
    leading layer axis of every leaf either way, and the family layer
    handles quantized cache blocks natively (llama.attention_block).
    `ring=True`: each stage's cache block has ring layout — the family
    layer writes at pos % capacity and masks by absolute slot position
    (models/llama.py::attention_block), identically per stage because
    positions/lengths are global, not stage-local."""

    def body(h, scanned):
        lp, k_layer, v_layer = scanned
        h, (k2, v2) = fam._layer(
            h, lp, cfg, positions, k_layer, v_layer, clen, use_flash=False,
            ring=ring,
        )
        return h, (k2, v2)

    x, (ck2, cv2) = jax.lax.scan(body, x, (layers_local, ck, cv))
    return x, ck2, cv2


def pipeline_forward_cached(
    params: common.Params,
    cfg: llama_mod.LlamaConfig,
    tokens: jnp.ndarray,  # [B, S]
    cache: llama_mod.KVCache,  # k/v [L, B, S_max, KVH, D], layer-staged
    mesh: Mesh,
    num_microbatches: Optional[int] = None,
    ring: bool = False,
) -> tuple[jnp.ndarray, llama_mod.KVCache]:
    """`llama.forward(..., cache=...)` semantics with the layer stack
    (and its KV cache) pipelined over `stage`. Serves both prefill
    (S > 1) and decode (S == 1); microbatches split the BATCH dim, so
    batched decode overlaps stages GPipe-style. Dense Llama only.

    `ring=True`: the cache's sequence dim is a ring (sliding-window
    serving) — forwarded into every stage's layer block, where writes
    land at pos % capacity and attention masks by absolute position
    (llama.attention_block's contract; capacity invariants validated by
    the engine, docs/kv_ring_design.md).

    Must run under jit (every engine path is): this JAX version rejects
    partial-manual shard_map out_specs naming the manual axis when
    applied eagerly."""
    from ggrmcp_tpu.ops.quant import QuantizedArray, embed_lookup
    from ggrmcp_tpu.ops.quant import matmul as qmatmul

    S_stages = stage_count(mesh)
    b, s = tokens.shape
    x = embed_lookup(params["embed"], tokens, cfg.jnp_dtype)
    positions = cache.length[:, None] + jnp.arange(s)[None, :]
    fam = _family(cfg)

    if S_stages == 1:
        logits, new_cache = fam.forward(params, cfg, tokens, cache, ring=ring)
        return logits, new_cache

    M = num_microbatches or (S_stages if b % S_stages == 0 else 1)
    if b % M != 0:
        raise ValueError(f"batch {b} not divisible by {M} microbatches")
    if cfg.num_layers % S_stages != 0:
        raise ValueError(
            f"{cfg.num_layers} layers not divisible by {S_stages} stages"
        )
    mb = b // M
    x_mb = x.reshape(M, mb, *x.shape[1:])
    pos_mb = positions.reshape(M, mb, s)
    clen_mb = cache.length.reshape(M, mb)

    layer_specs = jax.tree_util.tree_map(lambda _: P("stage"), params["layers"])
    fwd = partial(
        _pipelined_cached, cfg=cfg, fam=fam, num_stages=S_stages,
        num_micro=M, mb=mb, ring=ring,
    )
    out, new_k, new_v = jax.shard_map(
        fwd,
        mesh=mesh,
        axis_names={"stage"},
        in_specs=(layer_specs, P(), P(), P(), P("stage"), P("stage")),
        out_specs=(P(), P("stage"), P("stage")),
        check_vma=False,
    )(params["layers"], x_mb, pos_mb, clen_mb, cache.k, cache.v)
    x = out.reshape(b, s, -1)

    x = common.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["lm_head"]
    if not isinstance(head, QuantizedArray):
        head = head.astype(cfg.jnp_dtype)
    logits = qmatmul(x, head)
    new_cache = llama_mod.KVCache(
        k=new_k, v=new_v, length=cache.length + s
    )
    return logits.astype(jnp.float32), new_cache


def _pipelined_cached(
    layers_local, x_mb, pos_mb, clen_mb, ck, cv, *, cfg, fam, num_stages,
    num_micro, mb, ring=False,
):
    """Per-stage body with the stage's local cache block threaded
    through the tick schedule. ck/cv: [L/S, B, S_max, KVH, D]; the tick
    for microbatch m slices rows [m*mb, (m+1)*mb) and commits the
    updated block only when the (stage, tick) pair is live — junk
    drain/fill ticks never touch the cache."""
    S, M = num_stages, num_micro
    stage = jax.lax.axis_index("stage")
    perm = [(i, (i + 1) % S) for i in range(S)]

    state0 = jnp.zeros(x_mb.shape[1:], x_mb.dtype)
    out0 = jnp.zeros_like(x_mb)

    def tick(carry, t):
        state, out, ck, cv = carry
        m_in = jnp.clip(t, 0, M - 1)
        inp = jax.lax.dynamic_index_in_dim(x_mb, m_in, 0, keepdims=False)
        state = jnp.where(stage == 0, inp, state)
        m = jnp.clip(t - stage, 0, M - 1)
        pos = jax.lax.dynamic_index_in_dim(pos_mb, m, 0, keepdims=False)
        clen = jax.lax.dynamic_index_in_dim(clen_mb, m, 0, keepdims=False)
        row0 = m * mb
        # kv_map: cache blocks may be QuantizedArray (int8 KV) — every
        # bookkeeping op indexes leading axes only, so it applies to
        # values and scales identically (ops/quant.py).
        ck_m = quant.kv_map(
            lambda c: jax.lax.dynamic_slice_in_dim(c, row0, mb, axis=1), ck
        )
        cv_m = quant.kv_map(
            lambda c: jax.lax.dynamic_slice_in_dim(c, row0, mb, axis=1), cv
        )
        y, ck2_m, cv2_m = _run_block_cached(
            layers_local, state, cfg, pos, ck_m, cv_m, clen, fam, ring=ring
        )
        live = (t - stage >= 0) & (t - stage < M)

        def commit(c, new, old):
            return jax.lax.dynamic_update_slice_in_dim(
                c, jnp.where(live, new, old), row0, axis=1
            )

        ck = quant.kv_map(commit, ck, ck2_m, ck_m)
        cv = quant.kv_map(commit, cv, cv2_m, cv_m)
        m_out = t - (S - 1)
        upd = jax.lax.dynamic_update_index_in_dim(
            out, y, jnp.clip(m_out, 0, M - 1), 0
        )
        out = jnp.where((stage == S - 1) & (m_out >= 0), upd, out)
        state = jax.lax.ppermute(y, "stage", perm)
        return (state, out, ck, cv), None

    (state, out, ck, cv), _ = jax.lax.scan(
        tick, (state0, out0, ck, cv), jnp.arange(S + M - 1)
    )
    out = jax.lax.psum(jnp.where(stage == S - 1, out, 0), "stage")
    return out, ck, cv


# ---------------------------------------------------------------------------
# Training over the pipeline
# ---------------------------------------------------------------------------


def pipeline_lm_loss(params, cfg, tokens, mesh, num_microbatches=None):
    from ggrmcp_tpu.models import moe as moe_mod
    from ggrmcp_tpu.models.training import next_token_xent

    logits, aux = pipeline_forward_with_aux(
        params, cfg, tokens[:, :-1], mesh, num_microbatches
    )
    loss = next_token_xent(logits, tokens[:, 1:])
    if isinstance(cfg, moe_mod.MoEConfig):
        loss = loss + cfg.router_aux_weight * aux
    return loss


def make_pipeline_train_step(
    cfg: llama_mod.LlamaConfig,
    mesh: Mesh,
    num_microbatches: Optional[int] = None,
    optimizer=None,
):
    """jitted (TrainState, tokens[B,S]) → (TrainState, loss) with the
    forward/backward staged over `stage` (grads flow back through the
    ppermute ring — the reverse pipeline is the transposed schedule)."""
    import optax

    from ggrmcp_tpu.models import training

    optimizer = optimizer or training.make_optimizer()

    def step(state, tokens):
        loss, grads = jax.value_and_grad(pipeline_lm_loss)(
            state.params, cfg, tokens, mesh, num_microbatches
        )
        updates, opt_state = optimizer.update(
            grads, state.opt_state, state.params
        )
        params = optax.apply_updates(state.params, updates)
        return training.TrainState(params, opt_state, state.step + 1), loss

    batch_sharding = NamedSharding(mesh, P(("data", "fsdp"), None))
    return jax.jit(step, in_shardings=(None, batch_sharding)), optimizer


def shard_params_pp(params, cfg, mesh: Mesh):
    """Place a param pytree with PP × TP shardings (layer dim over
    `stage`; mesh-incompatible dims fall back to replication)."""
    specs = jax.tree_util.tree_map(
        lambda s, x: mesh_mod.compatible_spec(s, x.shape, mesh),
        param_specs_pp(cfg), params,
    )
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs
    )
