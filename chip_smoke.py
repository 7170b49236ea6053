#!/usr/bin/env python3
"""Proof that the main path starts and answers on the chip.

One MCP `tools/call` travels gateway -> gRPC/UDS -> sidecar -> scheduler
-> ContinuousBatcher -> KV manager -> jitted model. This script drives
that path once on the TPU, through the entry point a user would call
(`python -m ggrmcp_tpu gateway --tpu --config <file>`), at the published
width of a model the registry has (mistral-7b, 32 layers, random weights
from a seed), and checks what comes back by the repo's own means.

    python3 chip_smoke.py                  # on the chip; never the CPU
    python3 chip_smoke.py --cpu-rehearsal  # control flow only, tiny-llama

Legs, one process holding the chip(s) at a time, each gone before the
next starts (this parent never imports JAX or the package):

  kernel      flash_attention (compiled Pallas) against attention_xla at
              the serving shapes; on >= 4 devices also under
              flash_attention_sharded over tensor=4. Then
              paged_decode_attention against the gathered view
              (paged_view + attention_xla) at mistral's widths: 8 rows,
              page 16, mixed lengths, a freed slot. Then
              latent_prefill_attention against the XLA walk
              (mla_moe.latent_attention) at kanana's attention widths:
              2 rows of 512 queries on a 640-wide latent plane.
  sparse      one expert layer of deepseek-v3.2 at its published widths
              (models/mla_moe.py: q-compressed latent attention, the
              indexer, group-limited routing over this chip's 16 of 256
              experts) against the benchmark's float32 reference layer
              (benchmark/reference_dsv32.py): 4,608 tokens through a
              contiguous cache 512 at a time, so the last chunks select
              2,048 of up to 4,608 keys a query (on the chip inside the
              latent-prefill kernel; the rehearsal's masked walk), then
              one decode step over the same latents as pages (the
              gather by token index).
  keye        one layer of Keye-VL-2.0-30B-A3B's language model at its
              published widths (models/keye.py: 32 query heads on 4 KV
              heads, the indexer's key in a third cache plane, 128
              softmax-routed experts) against benchmark/reference_keye.py's
              float32 layer, as the sparse leg: 4,608 tokens through a
              contiguous three-plane cache 512 at a time (the masked
              block walk), then one decode step over the same state as
              pages (K and V gathered by token index); fails unless
              both sparse paths ran.
  jamba       AI21-Jamba2-3B whole at its published widths (models/
              jamba.py: 26 Mamba layers, attention without rotary at
              layers 7 and 21) against benchmark/reference_jamba.py's
              float32 logits: one 512-token chunk into a contiguous
              mini cache (the chunk scan, the prefill kernel), then 8
              decode steps over the same K/V as pages and the same
              state in a pool (the state update, the paged-decode
              kernel); prints a chunk's and a 32-row decode step's time.
  window      one period of SmallThinker-21BA3B-Instruct at its published
              widths (models/smallthinker.py: a full layer without
              positional encoding, three window-4,096 layers, 64 ReGLU
              experts) against benchmark/reference_smallthinker.py's
              float32 logits: ten 512-token chunks (past the window),
              8 decode steps over pages of two kinds with the window
              layers' pages behind the window unmapped and poisoned,
              and a re-admission on both kinds' views. Only with
              `--legs window`.
  experts     the routed experts of one keye layer at its published
              widths (2,048 x 768, 128 experts, top 8): the grouped
              SwiGLU Pallas kernel (ops/experts.py) against the XLA
              task loop and both against float32, a decode step, a
              suffix with padding rows and a 512-token chunk; fails
              unless the chooser took the kernel. Only with `--legs
              experts`: the sparse and keye legs run the same kernel
              inside their layers on the chip.
  serve       mistral-7b int8 synthetic weights, paged KV, one chip:
              tools/list, greedy generate (twice: same ids), SSE
              generatestream, a >= 1,024-token prompt, a second prompt
              sharing >= 512 tokens with it, eight concurrent generates.
              Chunked admission prefills into a contiguous mini cache,
              so the long prompt must take the Pallas kernel here too.
  default_kv  same model, paged_kv off: the contiguous shared cache.
              Same requests, same kernel assertion; its first greedy
              token must equal the serve leg's (same weights, same
              prefill).
  tp4         (>= 4 devices) mistral-7b bf16 over tensor=4, the serve
              leg's requests, per-device bytes within 1.3x.

Every leg runs its requests twice. The first pass is the probe batch
(it may compile); during the second `compile_post_warmup` must not move.
What is asserted about token ids is what bf16 on the chip can promise:
the same request twice in a row (same programs, same state) returns the
same ids. Across paths that compute the same mathematics in different
programs - cold prefill vs page reuse, paged vs contiguous decode - the
roundings differ and, with random weights, the largest logit changes on
rounding; those comparisons are printed, not asserted.
Any failed assertion, erroring request or missing TPU exits non-zero
with the reason on the last lines; nothing is caught and continued.
Times printed are set-up times (load, warm-up, compile), labelled with
the device; none of them is a statement about serving speed.

The last line of standard output on success is
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

HERE = os.path.dirname(os.path.abspath(__file__))
# Each leg's config and the gateway's full log stay here (git-ignored;
# the chip tool brings this directory back).
WORKDIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

# The contract gives 1200 s, compilation included. Stop a little short
# so that an overrun is this script's own failure, with a reason.
BUDGET_S = 1150.0
T0 = time.monotonic()

GENERATE = "ggrmcp_tpu_generateservice_generate"
STREAM = "ggrmcp_tpu_generateservice_generatestream"
MODEL_INFO = "ggrmcp_tpu_modelinfoservice_getmodelinfo"
STATS = "ggrmcp_tpu_modelinfoservice_getservingstats"

# Serving geometry per mode. The chip sizes are what fits 16 GB beside
# 7.4 GB of int8 weights: KV is 128 KiB/token at mistral-7b, and every
# admission program holds a mini cache of rows x max_seq next to the
# shared one, so 8 x 2048 is 2 GiB shared + 2 GiB mini (8 x 4096 is
# 4 + 4 and does not load).
CHIP = {
    "model": "mistral-7b", "slots": 8, "max_seq": 2048, "chunk": 512,
    "long_prompt": 1100, "shared_prefix": 640, "ready_s": 900.0,
    "call_s": 600.0,
}
REHEARSAL = {
    "model": "tiny-llama", "slots": 4, "max_seq": 256, "chunk": 64,
    "long_prompt": 150, "shared_prefix": 80, "ready_s": 300.0,
    "call_s": 120.0,
}
SHORT_NEW = 8  # new tokens of the sequential requests
CONCURRENT, CONCURRENT_NEW = 8, 32  # the batched burst


class SmokeFailure(Exception):
    """A leg, an assertion or a request failed; the message is the reason."""


def say(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, reason: str) -> None:
    if not cond:
        raise SmokeFailure(reason)


def remaining() -> float:
    left = BUDGET_S - (time.monotonic() - T0)
    check(left > 0, f"out of time: the {BUDGET_S:.0f} s budget is spent")
    return left


def child_env(rehearsal: bool) -> dict:
    env = dict(os.environ)
    if rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
    elif env.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        # The no-argument run never asks for the CPU: with no chip the
        # platform rule (utils/jaxenv.py) refuses to start.
        del env["JAX_PLATFORMS"]
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    return env


# ---------------------------------------------------------------------------
# Kernel leg: a child of its own, because it needs JAX
# ---------------------------------------------------------------------------


def kernel_leg_child(rehearsal: bool) -> None:
    """Runs in the child. flash_attention against attention_xla on the
    device, at the shapes the default-KV prefill hands the dispatcher.

    Tolerance, bf16 (the chip): 2e-2 absolute and relative. Both paths
    take bf16 q/k/v, accumulate in float32 and round the softmax
    weights to bf16 before the PV matmul (the kernel block by block,
    against its running maximum), and both round the output to bf16
    (half an ulp at |x| <= 1 is 2e-3). The sum over up to 4,096 keys of
    weight roundings of relative size 2^-9 stays an order below 2e-2; a
    wrong mask, offset or block skip moves outputs by O(0.1-1). float32 (the
    interpreted rehearsal): 2e-3, as tests/test_models.py. The same
    tolerance holds paged_decode_attention to the gathered view: it
    rounds the weights to bf16 as attention_xla does, in another order
    of blocks; and latent_prefill_attention to the walk over the
    latent plane, which rounds the same weights in blocks of another
    size."""
    from ggrmcp_tpu.utils.jaxenv import init_runtime

    init_runtime("chip_smoke kernel leg")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ggrmcp_tpu.core.config import MeshConfig
    from ggrmcp_tpu.models import mla_moe
    from ggrmcp_tpu.models.llama import paged_view
    from ggrmcp_tpu.ops.attention import (
        attention_xla,
        flash_attention,
        flash_attention_sharded,
        latent_prefill_attention,
        latent_prefill_attention_sharded,
        paged_decode_attention,
        paged_decode_attention_sharded,
    )
    from ggrmcp_tpu.parallel import mesh as mesh_mod

    devices = jax.devices()
    dev = devices[0]
    if rehearsal:
        b, sq, sk, h, kvh, d = 2, 128, 256, 8, 4, 32
        dtype, tol, windows = jnp.float32, 2e-3, (None, 64)
        q_offset, kv_len = [0, 96], [128, 224]
    else:
        check(dev.platform == "tpu", f"kernel leg on {dev.platform}")
        b, sq, sk, h, kvh, d = 2, 512, 4096, 32, 8, 128
        dtype, tol = jnp.bfloat16, 2e-2
        # 4096 is the served value (mistral-7b's window; it compiles
        # the branch but cannot bind inside a 4096-key cache); 1024
        # binds for the row whose chunk starts at 3072.
        windows = (None, 4096, 1024)
        q_offset, kv_len = [0, 3072], [512, 3584]
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, sq, h, d), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, sk, kvh, d), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, sk, kvh, d), dtype)
    qo = jnp.asarray(q_offset, jnp.int32)
    kl = jnp.asarray(kv_len, jnp.int32)

    def compare(name, out, ref):
        out = np.asarray(out, np.float32)
        ref = np.asarray(ref, np.float32)
        check(out.shape == ref.shape, f"{name}: shape {out.shape}")
        check(bool(np.isfinite(out).all()), f"{name}: non-finite output")
        err = float(np.abs(out - ref).max())
        bad = np.abs(out - ref) > tol + tol * np.abs(ref)
        say(f"  {name}: max|kernel - xla| = {err:.2e} (tolerance {tol:g})")
        check(not bad.any(), f"{name}: {int(bad.sum())} elements beyond "
              f"tolerance {tol:g}, max error {err:.3e}")

    for window in windows:
        ref = jax.jit(
            lambda q, k, v, qo, kl, w=window: attention_xla(
                q, k, v, causal=True, q_offset=qo, kv_len=kl, window=w
            )
        )(q, k, v, qo, kl)
        t0 = time.monotonic()
        out = flash_attention(
            q, k, v, causal=True, q_offset=qo, kv_len=kl, window=window,
            interpret=rehearsal,
        )
        jax.block_until_ready(out)
        say(f"  flash_attention window={window}: compiled and ran in "
            f"{time.monotonic() - t0:.1f} s (set-up, {dev.device_kind})")
        compare(f"q[{b},{sq},{h},{d}] k[{b},{sk},{kvh},{d}] "
                f"window={window}", out, ref)
        if len(devices) >= 4:
            mesh = mesh_mod.build_mesh(MeshConfig(tensor=4), devices[:4])
            out = jax.jit(
                lambda q, k, v, qo, kl, w=window: flash_attention_sharded(
                    q, k, v, mesh, causal=True, q_offset=qo, kv_len=kl,
                    window=w, interpret=rehearsal,
                )
            )(q, k, v, qo, kl)
            compare(f"sharded tensor=4 window={window}", out, ref)

    # The decode tick's read: each row's own pages out of the whole
    # arena against the gathered full-width view. Lengths on both sides
    # of a page and of a block of the walk, a short row, a full one,
    # and a freed slot (length kept, table unmapped), whose output the
    # batcher drops: it is left out of the comparison.
    if rehearsal:
        layers, rows, page, width, sq = 2, 4, 8, 8, 1
        lens, freed, windows = [5, 17, 64, 30], 3, (None, 12)
    else:
        layers, rows, page, width, sq = 4, 8, 16, 128, 1
        lens = [1, 16, 129, 300, 1100, 2048, 1801, 700]
        freed, windows = 7, (None, 4096, 512)
    n_pages = rows * width
    ka = jax.random.normal(
        jax.random.fold_in(key, 3), (layers, n_pages, page, kvh, d), dtype)
    va = jax.random.normal(
        jax.random.fold_in(key, 4), (layers, n_pages, page, kvh, d), dtype)
    qd = jax.random.normal(
        jax.random.fold_in(key, 5), (rows, sq, h, d), dtype)
    table = np.random.default_rng(0).permutation(n_pages).reshape(
        rows, width)
    table[freed] = n_pages
    table = jnp.asarray(table, jnp.int32)
    kl = jnp.asarray(lens, jnp.int32)
    layer = jnp.int32(layers - 1)
    live = np.arange(rows) != freed
    for window in windows:
        ref = jax.jit(
            lambda q, ka, va, t, kl, ly, w=window: attention_xla(
                q, paged_view(ka, t, ly), paged_view(va, t, ly),
                causal=True, q_offset=kl - sq, kv_len=kl, window=w,
            )
        )(qd, ka, va, table, kl, layer)
        t0 = time.monotonic()
        out = paged_decode_attention(
            qd, ka, va, table, kl, layer, window=window,
            interpret=rehearsal,
        )
        jax.block_until_ready(out)
        say(f"  paged_decode_attention window={window}: compiled and ran "
            f"in {time.monotonic() - t0:.1f} s (set-up, {dev.device_kind})")
        name = (f"paged decode q[{rows},{sq},{h},{d}] arena[{layers},"
                f"{n_pages},{page},{kvh},{d}] window={window}")
        compare(name, np.asarray(out)[live], np.asarray(ref)[live])
        check(not np.asarray(out, np.float32)[freed].any(),
              f"{name}: the freed slot's output is not zero")
        if len(devices) >= 4:
            out = jax.jit(
                lambda q, ka, va, t, kl, ly, w=window:
                paged_decode_attention_sharded(
                    q, ka, va, t, kl, ly, mesh, window=w,
                    interpret=rehearsal,
                )
            )(qd, ka, va, table, kl, layer)
            compare(f"paged decode sharded tensor=4 window={window}",
                    np.asarray(out)[live], np.asarray(ref)[live])
    # A prefill chunk of the latent family: every head of 512 queries
    # on the one shared latent a position, read in place out of the
    # contiguous plane, against the XLA walk in its absorbed form. A
    # first chunk, a chunk deep in a document whose tail is padding,
    # and (its output dropped by the batcher, zeros here) a row with no
    # real query.
    cfg = mla_moe.CONFIGS[
        "tiny-mla-moe" if rehearsal else "kanana-2-30b-a3b-6l"]
    if rehearsal:
        layers, sq, s_max, block = 2, 32, 128, 32
        q_offset, n_real = [0, 75, 40], [32, 20, 0]
    else:
        layers, sq, s_max, block = 2, 512, 4096, 512
        q_offset, n_real = [0, 3072, 1024], [512, 300, 0]
    rows, h = len(q_offset), cfg.num_heads
    nope, rope, rank = (
        cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank)
    width = cfg.kv_planes[0][0]
    plane = jax.random.normal(
        jax.random.fold_in(key, 6), (layers, rows, s_max, width), dtype
    ).at[..., cfg.latent_dim:].set(0)
    q_nope = jax.random.normal(
        jax.random.fold_in(key, 7), (rows, sq, h, nope), dtype)
    q_rope = jax.random.normal(
        jax.random.fold_in(key, 8), (rows, sq, h, rope), dtype)
    wkv_b = 0.04 * jax.random.normal(
        jax.random.fold_in(key, 9), (rank, h, nope + cfg.v_head_dim), dtype)
    qo = jnp.asarray(q_offset, jnp.int32)
    q_pos = qo[:, None] + jnp.arange(sq)[None, :]
    real = np.arange(sq)[None, :] < np.asarray(n_real)[:, None]
    last_q = jnp.max(jnp.where(real, q_pos, -1), axis=1)
    layer = jnp.int32(layers - 1)

    def walk(q_nope, q_rope, plane, wkv_b, q_pos, last_q):
        def fetch(i):
            return jax.lax.dynamic_slice_in_dim(
                plane[layer], i * block, block, 1)

        n_blocks = jnp.clip(
            (jnp.max(last_q) + block) // block, 0, s_max // block)
        return mla_moe.latent_attention(
            q_nope, q_rope, fetch, n_blocks, block, wkv_b, q_pos, qo + sq,
            cfg, absorbed=True)

    def fused(attend, q_nope, q_rope, plane, wkv_b, last_q):
        # The folding `mla_moe.attention_block` does around the kernel.
        out = attend(
            mla_moe.absorbed_queries(
                q_nope, q_rope, wkv_b[..., :nope], width), plane, layer,
            qo, qo + sq, last_q, value_width=rank,
            scale=(nope + rope) ** -0.5, interpret=rehearsal)
        return jnp.einsum("bshc,chd->bshd", out, wkv_b[..., nope:])

    ref = jax.jit(walk)(q_nope, q_rope, plane, wkv_b, q_pos, last_q)
    t0 = time.monotonic()
    out = jax.jit(functools.partial(fused, latent_prefill_attention))(
        q_nope, q_rope, plane, wkv_b, last_q)
    jax.block_until_ready(out)
    say(f"  latent_prefill_attention: compiled and ran in "
        f"{time.monotonic() - t0:.1f} s (set-up, {dev.device_kind})")
    name = (f"latent prefill q[{rows},{sq},{h},{width}] "
            f"plane[{layers},{rows},{s_max},{width}]")
    compare(name, np.asarray(out)[real], np.asarray(ref)[real])
    check(not np.asarray(out, np.float32)[np.asarray(n_real) == 0].any(),
          f"{name}: the output of a row with no real query is not zero")
    if len(devices) >= 4:
        out = jax.jit(functools.partial(fused, functools.partial(
            latent_prefill_attention_sharded, mesh=mesh)))(
            q_nope, q_rope, plane, wkv_b, last_q)
        compare("latent prefill sharded tensor=4",
                np.asarray(out)[real], np.asarray(ref)[real])
    print("LEG_RESULT " + json.dumps({
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }), flush=True)


def sparse_leg_child(rehearsal: bool) -> None:
    """Runs in the child. One expert layer of the `deepseek_v32`
    member (`attention_block` + `moe_ffn`, what a layer of `forward`
    runs) against `reference_dsv32`'s float32 `expert_layer` on the
    same drawn weights and the same input, random hidden states.

    What is compared is the layer's own contribution `y - x`, by root
    mean square over the compared positions, as a share of the
    reference's: bf16 weights and activations on the chip against
    float32 at highest precision give ~1e-2 (every matmul input is
    rounded to 8 bits); the limit is 3e-2. A wrong selection is not a
    rounding: the same comparison against the reference WITHOUT its
    selection is printed beside it and must read at least three times
    larger. float32 (the rehearsal): 1e-4."""
    from ggrmcp_tpu.utils.jaxenv import init_runtime

    init_runtime("chip_smoke sparse leg")
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference_dsv32 as ref_mod
    from ggrmcp_tpu.models import common, mla_moe
    from ggrmcp_tpu.models.llama import KVCache, PagedKVCache
    from ggrmcp_tpu.ops import attention as attn_ops

    dev = jax.devices()[0]
    if rehearsal:
        name, chunk, n_chunks, page, tol = "tiny-dsv32", 16, 5, 8, 1e-4
        with open(os.path.join(
                HERE, "tests", "benchmark", "rehearsal_dsv32", "benchmark",
                "configs", "tiny-dsv32-cpu.json")) as f:
            model = json.load(f)
    else:
        check(dev.platform == "tpu", f"sparse leg on {dev.platform}")
        name, chunk, n_chunks, page, tol = (
            "deepseek-v3.2-ep16-5l", 512, 9, 16, 3e-2)
        with open(os.path.join(
                HERE, "benchmark", "configs",
                "deepseek-v3.2-bf16-ep16-1chip.json")) as f:
            model = json.load(f)
    # one expert layer, no dense one; every width as published
    cfg = dataclasses.replace(
        mla_moe.CONFIGS[name], num_layers=1, first_dense_layers=0)
    model = dict(model, num_hidden_layers=1, first_k_dense_replace=0)
    s_all = chunk * n_chunks
    check(s_all > 2 * cfg.index_topk, "the selection would not bind")
    weights = ref_mod.family_init_weights(jax, model)
    lp = {k.split(".", 1)[1]: v[0] for k, v in weights.items()
          if k.startswith("moe.")}
    banks = tuple(weights["moe." + b] for b in ("w_gate", "w_up", "w_down"))
    ones = {"attn_norm": cfg.hidden_dim, "mlp_norm": cfg.hidden_dim,
            "kv_norm": cfg.kv_lora_rank, "q_norm": cfg.q_lora_rank,
            "idx_k_norm": cfg.index_head_dim}
    lp.update({k: jnp.ones((n,), cfg.jnp_dtype) for k, n in ones.items()})
    lp["idx_k_bias"] = jnp.zeros((cfg.index_head_dim,), cfg.jnp_dtype)
    x = jax.random.normal(
        jax.random.PRNGKey(1), (s_all + 1, cfg.hidden_dim), jnp.float32)
    x = x.astype(cfg.jnp_dtype)

    def layer(lp, banks, x, planes, length, table):
        # (the weights as arguments: closed over, 2 GB of them would be
        # constants of the program and its compile would not fit the host)
        positions = length[:, None] + jnp.arange(x.shape[1])[None, :]
        y, planes, _ = mla_moe.attention_block(
            x, lp, cfg, positions, planes, length, table, 0)
        n = common.rms_norm(y, lp["mlp_norm"], cfg.norm_eps)
        out, _ = mla_moe.moe_ffn(n, lp, banks, 0, cfg)
        return y + out, planes

    t0 = time.monotonic()
    cache = KVCache.create(cfg, 1, 2 * s_all)
    planes, got = (cache.k, cache.v), []
    step = jax.jit(functools.partial(layer, table=None), donate_argnums=(3,))
    for i in range(n_chunks):
        y, planes = step(
            lp, banks, x[None, i * chunk:(i + 1) * chunk], planes,
            jnp.asarray([i * chunk], jnp.int32))
        got.append(np.asarray(y[0], np.float32))
    # the same latents and indexer keys as pages, and one decode step
    n_pages = 2 * s_all // page
    paged = PagedKVCache.create(cfg, 1, 2 * s_all, n_pages, page)
    arena = tuple(
        p.reshape(p.shape[0], n_pages, page, p.shape[-1]) for p in planes)
    check(arena[0].shape == paged.k.shape and arena[1].shape == paged.v.shape,
          f"page planes {arena[0].shape} {arena[1].shape}")
    table = jnp.arange(n_pages, dtype=jnp.int32)[None, :]
    y, _ = jax.jit(layer)(
        lp, banks, x[None, s_all:], arena, jnp.asarray([s_all], jnp.int32),
        table)
    got.append(np.asarray(y[0], np.float32))
    got = np.concatenate(got)
    took = attn_ops.dispatch_counts
    say(f"  sparse layer: {n_chunks} chunks of {chunk} and a decode step "
        f"compiled and ran in {time.monotonic() - t0:.1f} s (set-up, "
        f"{dev.device_kind}); programs: sparse_chunk {took['sparse_chunk']}, "
        f"of them through the latent-prefill kernel {took['latent_prefill']}, "
        f"sparse_decode {took['sparse_decode']}")
    # On the chip the chunk's selection goes into the kernel; the CPU
    # rehearsal walks.
    check(took["latent_prefill"] == (0 if rehearsal else 1)
          and took["xla_fallback"] == 0, f"the chunk's path: {dict(took)}")

    x32 = np.asarray(x, np.float32)
    pad = ref_mod.padded_len(s_all + 1) - (s_all + 1)
    x_ref = jnp.pad(x.astype(jnp.float32), ((0, pad), (0, 0)))
    w1 = {k.split(".", 1)[1]: v[0] for k, v in weights.items()
          if k.startswith("moe.")}

    def rel(select, lo, hi):
        want = np.asarray(
            ref_mod.make_layers(jax, model, select=select)[1](x_ref, w1)
        )[: s_all + 1]
        d_ref, d_got = (want - x32)[lo:hi], (got - x32)[lo:hi]
        check(bool(np.isfinite(d_got).all()), "non-finite layer output")
        return float(np.sqrt(((d_got - d_ref) ** 2).mean())
                     / np.sqrt((d_ref ** 2).mean()))

    for label, lo, hi in (("sparse chunk", s_all - chunk, s_all),
                          ("sparse decode step", s_all, s_all + 1)):
        sound, dense = rel(True, lo, hi), rel(False, lo, hi)
        say(f"  {label} ({cfg.index_topk} of {lo + 1}..{hi} keys): rms "
            f"error {sound:.2e} of the layer's contribution (limit {tol:g}); "
            f"against the reference without its selection {dense:.2e}")
        check(sound < tol, f"{label}: {sound:.3e} beyond {tol:g}")
        check(dense > 3 * sound, f"{label}: the selection does not show")
    print("LEG_RESULT " + json.dumps({
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }), flush=True)


def keye_leg_child(rehearsal: bool) -> None:
    """Runs in the child. One layer of the keye family at its published
    widths (`keye.attention_block` + `mla_moe.moe_ffn`, what a layer of
    `keye.forward` runs: grouped attention under the indexer's
    selection over three cache planes, 128 softmax-routed experts)
    against `reference_keye`'s float32 layer on the same drawn weights
    and the same input, as the sparse leg compares dsv32's: the rms of
    the layer's contribution `y - x` over a sparse chunk and a sparse
    decode step, with and without the reference's selection, for the
    whole layer and for its attention block alone. Fails unless both
    sparse paths ran (`dispatch_counts`).

    The limit on the chip is 0.2, not the sparse leg's 3e-2, and it was
    read, not chosen (PERF.md, PR 37): where nothing is selected the
    attention's contribution agrees to 1.4e-2 (bf16 inputs); where 2,048
    of ~4,600 keys are, it reads 6.5e-2 to 9.5e-2, on the CPU in bf16 as
    on the chip, because the program's hidden states are rounded to 8
    bits before the indexer sees them and a handful of keys beside the
    2,048th change places, and over random weights the output is a sum
    in which every selected key counts alike (five keys of 2,048 are 7%
    of its norm). Without the selection the same comparison reads 1.0."""
    from ggrmcp_tpu.utils.jaxenv import init_runtime

    init_runtime("chip_smoke keye leg")
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference_keye as ref_mod
    from ggrmcp_tpu.models import common, keye, mla_moe
    from ggrmcp_tpu.models.llama import KVCache, PagedKVCache, cache_planes
    from ggrmcp_tpu.ops import attention as attn_ops

    dev = jax.devices()[0]
    if rehearsal:
        name, chunk, n_chunks, page, tol, tol_layer = (
            "tiny-keye", 16, 5, 8, 1e-4, 1e-4)
        path = os.path.join(
            HERE, "tests", "benchmark", "rehearsal_keye", "benchmark",
            "configs", "tiny-keye-cpu.json")
    else:
        check(dev.platform == "tpu", f"keye leg on {dev.platform}")
        name, chunk, n_chunks, page, tol, tol_layer = (
            "keye-vl-2.0-30b-a3b-6l", 512, 9, 16, 0.2, 0.2)
        path = os.path.join(
            HERE, "benchmark", "configs",
            "keye-vl-2.0-30b-a3b-bf16-1chip.json")
    with open(path) as f:
        model = dict(json.load(f), num_hidden_layers=1)
    cfg = dataclasses.replace(keye.CONFIGS[name], num_layers=1)
    s_all = chunk * n_chunks
    check(s_all > 2 * cfg.index_topk, "the selection would not bind")
    weights = ref_mod.family_init_weights(jax, model)
    w1 = ref_mod.layer_weights(model, weights)[0]
    banks = tuple(weights["layers." + b] for b in ("w_gate", "w_up", "w_down"))
    lp = {k: v for k, v in w1.items() if not k.startswith("w_")}
    lp.update({k: jnp.full((n,), fill, cfg.jnp_dtype)
               for k, (n, fill) in keye._norm_shapes(cfg).items()})
    x = jax.random.normal(
        jax.random.PRNGKey(1), (s_all + 1, cfg.hidden_dim), jnp.float32)
    x = x.astype(cfg.jnp_dtype)

    def layer(lp, banks, x, planes, length, table):
        # (the weights as arguments, as in the sparse leg); the layer's
        # output and, beside it, the attention block's alone
        positions = length[:, None] + jnp.arange(x.shape[1])[None, :]
        y, planes, _ = keye.attention_block(
            x, lp, cfg, positions, planes, length, table, 0)
        n = common.rms_norm(y, lp["mlp_norm"], cfg.norm_eps)
        out, _ = mla_moe.moe_ffn(n, lp, banks, 0, cfg)
        return jnp.stack([y + out, y]), planes

    t0 = time.monotonic()
    planes, got = cache_planes(KVCache.create(cfg, 1, 2 * s_all)), []
    step = jax.jit(functools.partial(layer, table=None), donate_argnums=(3,))
    for i in range(n_chunks):
        y, planes = step(
            lp, banks, x[None, i * chunk:(i + 1) * chunk], planes,
            jnp.asarray([i * chunk], jnp.int32))
        got.append(np.asarray(y[:, 0], np.float32))
    # the same K, V and indexer keys as pages, and one decode step
    n_pages = 2 * s_all // page
    paged = PagedKVCache.create(cfg, 1, 2 * s_all, n_pages, page)
    arena = tuple(
        p.reshape(p.shape[0], n_pages, page, *p.shape[3:]) for p in planes)
    check([a.shape for a in arena] == [a.shape for a in cache_planes(paged)],
          f"page planes {[a.shape for a in arena]}")
    table = jnp.arange(n_pages, dtype=jnp.int32)[None, :]
    y, _ = jax.jit(layer)(
        lp, banks, x[None, s_all:], arena, jnp.asarray([s_all], jnp.int32),
        table)
    got.append(np.asarray(y[:, 0], np.float32))
    got = np.concatenate(got, axis=1)  # [layer | attention, tokens, D]
    took = attn_ops.dispatch_counts
    say(f"  keye layer: {n_chunks} chunks of {chunk} and a decode step "
        f"compiled and ran in {time.monotonic() - t0:.1f} s (set-up, "
        f"{dev.device_kind}); programs: sparse_gqa_chunk "
        f"{took['sparse_gqa_chunk']}, sparse_gqa_decode "
        f"{took['sparse_gqa_decode']}")
    check(took["sparse_gqa_chunk"] == 1 and took["sparse_gqa_decode"] == 1,
          f"the intended paths did not run: {dict(took)}")

    x32 = np.asarray(x, np.float32)
    pad = ref_mod.padded_len(s_all + 1) - (s_all + 1)
    x_ref = jnp.pad(x.astype(jnp.float32), ((0, pad), (0, 0)))

    def rel(select, part, lo, hi):
        """rms of (ours - the reference's) over rms of the reference's,
        for the contribution `. - x` of the whole layer (part 0) or of
        its attention block (part 1)."""
        fns = ref_mod.make_layers(jax, model, select=select)
        want = np.asarray(fns[2 * part](x_ref, w1))[: s_all + 1]
        d_ref, d_got = (want - x32)[lo:hi], (got[part] - x32)[lo:hi]
        check(bool(np.isfinite(d_got).all()), "non-finite layer output")
        return float(np.sqrt(((d_got - d_ref) ** 2).mean())
                     / np.sqrt((d_ref ** 2).mean()))

    for label, lo, hi in (("sparse chunk", s_all - chunk, s_all),
                          ("sparse decode step", s_all, s_all + 1)):
        for part, what, limit in ((1, "attention", tol), (0, "layer", tol_layer)):
            sound, dense = rel(True, part, lo, hi), rel(False, part, lo, hi)
            say(f"  {label} ({cfg.index_topk} of {lo + 1}..{hi} keys), "
                f"{what}: rms error {sound:.2e} of its contribution (limit "
                f"{limit:g}); against the reference without its selection "
                f"{dense:.2e}")
            check(sound < limit, f"{label}, {what}: {sound:.3e} beyond {limit:g}")
            check(dense > 2 * sound, f"{label}, {what}: the selection does "
                  "not show")
    print("LEG_RESULT " + json.dumps({
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }), flush=True)


def jamba_leg_child(rehearsal: bool) -> None:
    """Runs in the child. The whole hybrid model at its published widths
    (`jamba.forward`: 26 Mamba layers' chunk scan and decode step, the 2
    attention layers through the prefill and the paged-decode kernels),
    one chunk of 512 positions into a contiguous mini cache and then 8
    decode steps over the same K/V as pages and the same state in a
    pool, against `reference_jamba`'s float32 logits of the same 520
    tokens on the same drawn weights (the program's and the
    reference's are drawn one after the other from one recipe: both do
    not fit the chip together). The statistic is the rms of the logits'
    difference over the rms of the reference's logits about their mean.
    It also times the chunk program and a 32-row decode step."""
    from ggrmcp_tpu.utils.jaxenv import init_runtime

    init_runtime("chip_smoke jamba leg")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference_jamba as ref_mod
    from ggrmcp_tpu.models import jamba
    from ggrmcp_tpu.models.llama import KVCache, PagedKVCache
    from ggrmcp_tpu.ops import attention as attn_ops

    dev = jax.devices()[0]
    if rehearsal:
        name, chunk, page, rows, tol = "tiny-jamba", 64, 16, 2, 1e-4
        path = os.path.join(
            HERE, "tests", "benchmark", "rehearsal_jamba", "benchmark",
            "configs", "tiny-jamba-cpu.json")
    else:
        check(dev.platform == "tpu", f"jamba leg on {dev.platform}")
        name, chunk, page, rows, tol = "jamba2-3b", 512, 16, 32, 0.1
        path = os.path.join(
            HERE, "benchmark", "configs", "jamba2-3b-bf16-1chip.json")
    with open(path) as f:
        model = json.load(f)
    cfg = jamba.CONFIGS[name]
    steps, s_max = 8, 2 * chunk
    ids = np.random.RandomState(3).randint(3, cfg.vocab_size, chunk + steps)
    params = jax.jit(lambda k: jamba.init_params(k, cfg))(jax.random.PRNGKey(0))

    def run(params, tokens, cache):
        return jamba.forward(params, cfg, tokens, cache)

    t0 = time.monotonic()
    step = jax.jit(run, donate_argnums=(2,))
    logits, mini = step(
        params, jnp.asarray(ids[None, :chunk]), KVCache.create(cfg, 1, s_max))
    got = [np.asarray(logits[0])]
    # the same K and V as pages, the same state as entry 0 of a pool
    n_pages = s_max // page
    paged = PagedKVCache.create(cfg, 1, s_max, n_pages, page)
    paged = paged._replace(
        k=mini.k.reshape(paged.k.shape), v=mini.v.reshape(paged.v.shape),
        table=jnp.arange(n_pages, dtype=jnp.int32)[None, :],
        length=mini.length,
        state=tuple(pool.at[:, :1].set(leaf)
                    for pool, leaf in zip(paged.state, mini.state)))
    for i in range(chunk, chunk + steps):
        logits, paged = step(params, jnp.asarray(ids[None, i:i + 1]), paged)
        got.append(np.asarray(logits[0]))
    got = np.concatenate(got)
    took = attn_ops.dispatch_counts
    say(f"  jamba: a chunk of {chunk} and {steps} decode steps compiled and "
        f"ran in {time.monotonic() - t0:.1f} s (set-up, {dev.device_kind}); "
        f"programs: ssm_scan {took['ssm_scan']}, ssm_step {took['ssm_step']}, "
        f"flash {took['flash']}, paged_decode {took['paged_decode']}")
    check(took["ssm_scan"] >= 1 and took["ssm_step"] >= 1,
          f"the intended paths did not run: {dict(took)}")
    if not rehearsal:
        check(took["flash"] >= 1 and took["paged_decode"] >= 1,
              f"the attention kernels did not run: {dict(took)}")

    def timed(fn, *args, n=5):
        out = fn(*args)
        jax.block_until_ready(out)
        t = time.monotonic()
        for _ in range(n):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.monotonic() - t) / n * 1e3

    # a chunk of one row, as a trickle admission runs it, and a decode
    # step of the whole pool (host launch included in both)
    chunk_fn = jax.jit(lambda p, t, c: jamba.forward(
        p, cfg, t, c, logit_idx=jnp.zeros((1,), jnp.int32))[0])
    ms_chunk = timed(chunk_fn, params, jnp.asarray(ids[None, :chunk]),
                     KVCache.create(cfg, 1, s_max))
    pool = PagedKVCache.create(cfg, rows, s_max, rows * n_pages, page)
    pool = pool._replace(
        table=jnp.arange(rows * n_pages, dtype=jnp.int32).reshape(rows, -1),
        length=jnp.full((rows,), chunk, jnp.int32))
    tick_fn = jax.jit(lambda p, t, c: jamba.forward(p, cfg, t, c)[0])
    ms_step = timed(tick_fn, params, jnp.zeros((rows, 1), jnp.int32), pool)
    say(f"  jamba: a [1, {chunk}] chunk {ms_chunk:.2f} ms, a decode step of "
        f"{rows} rows {ms_step:.2f} ms (host clock, launch included, "
        f"{dev.device_kind})")
    del params, mini, paged, pool, logits

    weights = ref_mod.to_host(jax, model, ref_mod.family_init_weights(jax, model))
    want = np.asarray(ref_mod.logits_of(jax, model, weights, ids.tolist()))
    check(bool(np.isfinite(got).all()), "non-finite logits")
    for label, lo, hi in (("chunk", 0, chunk),
                          ("decode steps", chunk, chunk + steps)):
        ref = want[lo:hi]
        err = float(np.sqrt(((got[lo:hi] - ref) ** 2).mean())
                    / np.sqrt(((ref - ref.mean(-1, keepdims=True)) ** 2).mean()))
        agree = float((got[lo:hi].argmax(-1) == ref.argmax(-1)).mean())
        say(f"  {label}: rms error {err:.2e} of the reference logits' spread "
            f"(limit {tol:g}); the argmax agrees at {agree:.3f} of positions")
        check(err < tol, f"{label}: {err:.3e} beyond {tol:g}")
    print("LEG_RESULT " + json.dumps({
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }), flush=True)


def window_leg_child(rehearsal: bool) -> None:
    """Runs in the child. One period of SmallThinker-21BA3B-Instruct at
    its published widths (`smallthinker.forward`: a full layer without
    positional encoding, three window-4,096 layers with RoPE, 64 ReGLU
    experts routed from the attention block's input), against
    `reference_smallthinker`'s float32 logits of the same tokens on the
    same drawn weights: ten 512-token chunks into a contiguous mini
    cache (the prefill kernel, with the window and without), then 8
    decode steps over the same K/V as pages of TWO kinds, the window
    layers' pages behind the window unmapped and poisoned (the
    paged-decode kernel's window walk), then a re-admission: both
    kinds' views gathered back into a mini and a 128-token suffix run
    on it. The statistic is the rms of the logits' difference over the
    rms of the reference's logits about their mean."""
    import dataclasses

    from ggrmcp_tpu.utils.jaxenv import init_runtime

    init_runtime("chip_smoke window leg")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference_smallthinker as ref_mod
    from ggrmcp_tpu.models import llama
    from ggrmcp_tpu.models import smallthinker as st
    from ggrmcp_tpu.ops import attention as attn_ops

    dev = jax.devices()[0]
    if rehearsal:
        name, chunk, chunks, suffix, tol = "tiny-smallthinker", 64, 3, 32, 1e-4
        path = os.path.join(
            HERE, "tests", "benchmark", "rehearsal_smallthinker", "benchmark",
            "configs", "tiny-smallthinker-cpu.json")
    else:
        check(dev.platform == "tpu", f"window leg on {dev.platform}")
        name, chunk, chunks, suffix, tol = (
            "smallthinker-21b-a3b-8l", 512, 10, 128, 0.1)
        path = os.path.join(
            HERE, "benchmark", "configs",
            "smallthinker-21b-a3b-bf16-1chip.json")
    with open(path) as f:
        model = dict(json.load(f), num_hidden_layers=4)
    cfg = dataclasses.replace(st.CONFIGS[name], num_layers=4)
    window, page, steps = cfg.sliding_window, 16, 8
    filled = chunk * chunks
    check(filled > window + chunk, "the prefill does not pass the window")
    s_max = -(-(filled + steps + suffix) // chunk) * chunk
    width = s_max // page
    ids = np.random.RandomState(5).randint(
        3, cfg.vocab_size, filled + steps + suffix)
    params = jax.jit(lambda k: st.init_params(k, cfg))(jax.random.PRNGKey(0))

    t0 = time.monotonic()
    step = jax.jit(
        lambda p, t, c: st.forward(p, cfg, t, c), donate_argnums=(2,))
    mini, got = llama.KVCache.create(cfg, 1, s_max), []
    for at in range(0, filled, chunk):
        logits, mini = step(params, jnp.asarray(ids[None, at:at + chunk]), mini)
        got.append(np.asarray(logits[0]))
    # the same K and V as pages of two kinds: every block of the full
    # layer mapped, of the window layers those a query at `filled` can
    # read; their other pages poisoned
    first = max(0, filled - window + 1) // page
    gone = (jnp.arange(width) < first)[None, :, None, None, None]
    table = jnp.arange(width, dtype=jnp.int32)[None]
    arenas = {}
    for plane in ("k", "v"):
        full, tail = llama.split_kinds(cfg, getattr(mini, plane))
        arenas[plane] = full.reshape(full.shape[0], width, page, *full.shape[3:])
        tail = tail.reshape(tail.shape[0], width, page, *tail.shape[3:])
        arenas["w" + plane] = jnp.where(gone, 1e4, tail).astype(tail.dtype)
    paged = llama.PagedKVCache.create(
        cfg, 1, s_max, width, page, window_pages=width)._replace(
        k=arenas["k"], v=arenas["v"], table=table, length=mini.length,
        window=llama.WindowArena(
            arenas["wk"], arenas["wv"],
            jnp.where(jnp.arange(width) >= first, table, width)))
    for i in range(filled, filled + steps):
        logits, paged = step(params, jnp.asarray(ids[None, i:i + 1]), paged)
        got.append(np.asarray(logits[0]))
    views = [llama.join_kinds(cfg, [
        llama.paged_view_layers(a, t) for a, t in (
            (full, paged.table), (tail, paged.window.table))])
        for full, tail in ((paged.k, paged.window.k),
                           (paged.v, paged.window.v))]
    again = llama.KVCache(
        views[0], views[1], jnp.asarray([filled + steps], jnp.int32))
    logits, _ = step(
        params, jnp.asarray(ids[None, filled + steps:]), again)
    got = np.concatenate(got + [np.asarray(logits[0])])
    took = attn_ops.dispatch_counts
    say(f"  window: {chunks} chunks of {chunk}, {steps} decode steps and a "
        f"suffix of {suffix} compiled and ran in {time.monotonic() - t0:.1f} s "
        f"(set-up, {dev.device_kind}); programs: flash {took['flash']}, "
        f"paged_decode {took['paged_decode']}, grouped_experts "
        f"{took['grouped_experts']}")
    if not rehearsal:
        check(took["flash"] >= 2 and took["paged_decode"] >= 2
              and took["grouped_experts"] >= 1,
              f"the kernels did not run: {dict(took)}")
    del params, mini, paged, again, views, arenas, logits

    weights = ref_mod.to_host(jax, model, ref_mod.family_init_weights(jax, model))
    want = np.asarray(ref_mod.logits_of(jax, model, weights, ids.tolist()))
    check(bool(np.isfinite(got).all()), "non-finite logits")
    for label, lo, hi in (
            ("chunks inside the window", 0, window),
            ("chunks past the window", window, filled),
            ("decode steps on two kinds of page", filled, filled + steps),
            ("the re-admitted suffix", filled + steps, len(ids))):
        ref = want[lo:hi]
        err = float(np.sqrt(((got[lo:hi] - ref) ** 2).mean())
                    / np.sqrt(((ref - ref.mean(-1, keepdims=True)) ** 2).mean()))
        agree = float((got[lo:hi].argmax(-1) == ref.argmax(-1)).mean())
        say(f"  {label}: rms error {err:.2e} of the reference logits' spread "
            f"(limit {tol:g}); the argmax agrees at {agree:.3f} of positions")
        check(err < tol, f"{label}: {err:.3e} beyond {tol:g}")
    print("LEG_RESULT " + json.dumps({
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }), flush=True)


def experts_leg_child(rehearsal: bool) -> None:
    """Runs in the child. The routed experts of one layer at keye's
    published widths (2,048 x 768, 128 experts, top 8; two layers of
    banks stacked, the second read) as `mla_moe.routed_experts` runs
    them on the device: the grouped SwiGLU kernel (`ops/experts.py`;
    the leg fails unless the chooser took it on the chip) against the
    XLA task loop on the same operands, both against every expert on
    every token in float32 at the highest precision. A decode step (8
    tokens), a suffix with padding rows (96 tokens, 60% real) and a
    chunk (512 tokens): `stats` equal, rows routed nowhere exactly zero, and the
    kernel no further from float32 than the loop is (it rounds fewer
    intermediates) up to a rounding of the result; both within 2e-2 of
    its largest value. The rehearsal runs the kernel in the
    interpreter at tiny widths in float32: 1e-5."""
    from ggrmcp_tpu.utils.jaxenv import init_runtime

    init_runtime("chip_smoke experts leg")
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ggrmcp_tpu.models import keye, mla_moe
    from ggrmcp_tpu.ops import attention as attn_ops
    from ggrmcp_tpu.ops import experts as experts_ops

    dev = jax.devices()[0]
    chooser = experts_ops.grouped_experts
    if rehearsal:
        cfg, tol = keye.CONFIGS["tiny-keye"], 1e-5
        cases = ((8, None), (24, 0.6), (64, None))
        experts_ops.grouped_swiglu = functools.partial(
            experts_ops.grouped_swiglu, interpret=True)
        take = lambda *_: True  # noqa: E731
    else:
        check(dev.platform == "tpu", f"experts leg on {dev.platform}")
        cfg, tol = keye.CONFIGS["keye-vl-2.0-30b-a3b-6l"], 2e-2
        cases = ((8, None), (96, 0.6), (512, None))
        take = chooser
    cfg = dataclasses.replace(cfg, num_layers=2)
    dtype, d, f = cfg.jnp_dtype, cfg.hidden_dim, cfg.expert_ffn_dim
    e, k = cfg.num_experts, cfg.experts_per_token
    key = jax.random.PRNGKey(43)
    banks = tuple(
        jax.jit(lambda kk, dims=dims: jax.random.normal(
            kk, (2, e, *dims), dtype) * dims[0] ** -0.5
        )(jax.random.fold_in(key, i))
        for i, dims in enumerate(((d, f), (d, f), (f, d))))
    banks32 = tuple(w.astype(jnp.float32) for w in banks)

    @jax.jit
    def dense(xt, idx, weight, valid, banks32, layer):
        """Every expert on every token in float32 at the highest
        precision, then each token's own: no sort, no task, no block."""
        hi = jax.lax.Precision.HIGHEST
        x = xt.astype(jnp.float32)
        wg, wu, wd = (w[layer] for w in banks32)
        g = jnp.einsum("td,edf->etf", x, wg, precision=hi)
        u = jnp.einsum("td,edf->etf", x, wu, precision=hi)
        y = jnp.einsum(
            "etf,efd->etd", jax.nn.silu(g) * u, wd, precision=hi)
        picked = y[idx, jnp.arange(x.shape[0])[:, None]]  # [T, k, D]
        out = (picked * weight[..., None]).sum(1)
        return out if valid is None else jnp.where(valid[:, None], out, 0.0)

    def run(choose, *operands):
        experts_ops.grouped_experts = choose
        try:  # a new function a call: a new trace under this chooser
            return jax.jit(lambda *a: mla_moe.routed_experts(*a, cfg))(
                *operands)
        finally:
            experts_ops.grouped_experts = chooser

    for tokens, real_share in cases:
        kk = jax.random.fold_in(key, tokens)
        xt = jax.random.normal(kk, (tokens, d), dtype)
        weight = jax.random.uniform(jax.random.fold_in(kk, 1), (tokens, k))
        idx = jax.lax.top_k(jax.random.normal(
            jax.random.fold_in(kk, 2), (tokens, e)), k)[1].astype(jnp.int32)
        valid = None if real_share is None else jax.random.bernoulli(
            jax.random.fold_in(kk, 3), real_share, (tokens,))
        layer = jnp.int32(1)
        before = attn_ops.dispatch_counts["grouped_experts"]
        got, got_stats = run(take, xt, idx, weight, valid, banks, layer)
        if not rehearsal:
            check(attn_ops.dispatch_counts["grouped_experts"] == before + 1,
                  "the chooser did not take the grouped-experts kernel")
        loop, loop_stats = run(
            lambda *a: False, xt, idx, weight, valid, banks, layer)
        want = dense(xt, idx, weight, valid, banks32, layer)
        check(np.array_equal(np.asarray(got_stats), np.asarray(loop_stats)),
              f"stats differ: {got_stats} vs {loop_stats}")
        got, loop, want = (np.asarray(a, np.float32) for a in (got, loop, want))
        check(bool(np.isfinite(got).all()), "non-finite output")
        scale = float(np.abs(want).max())
        mine = float(np.abs(got - want).max()) / scale
        theirs = float(np.abs(loop - want).max()) / scale
        hit, load_max, pairs, _ = (int(v) for v in got_stats)
        say(f"  {tokens} tokens ({pairs} pairs, {hit} of {e} experts hit, "
            f"largest load {load_max}): max|. - float32| / max|float32|: "
            f"kernel {mine:.2e}, loop {theirs:.2e} (limit {tol:g})")
        check(mine < tol and theirs < tol, f"{tokens} tokens: beyond {tol:g}")
        check(mine <= theirs + float(jnp.finfo(dtype).eps),
              f"{tokens} tokens: the kernel is further from float32 "
              f"({mine:.3e}) than the loop ({theirs:.3e})")
        if valid is not None:
            check(not got[~np.asarray(valid)].any(),
                  "a padding row's result is not zero")
    print("LEG_RESULT " + json.dumps({
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }), flush=True)


def run_child_leg(name: str, title: str, rehearsal: bool) -> dict:
    """A leg that is one child process on the device: `--child-<name>`."""
    say(f"== leg {name}: {title}")
    cmd = [sys.executable, os.path.abspath(__file__), f"--child-{name}"]
    if rehearsal:
        cmd.append("--cpu-rehearsal")
    proc = subprocess.Popen(
        cmd, cwd=HERE, env=child_env(rehearsal), stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, start_new_session=True,
    )
    try:
        out, _ = proc.communicate(timeout=remaining())
    except subprocess.TimeoutExpired:
        raise SmokeFailure(f"{name} leg did not finish inside the budget")
    finally:
        stop_process(proc)
    lines = out.splitlines()
    result = [ln for ln in lines if ln.startswith("LEG_RESULT ")]
    for ln in lines:
        if not ln.startswith("LEG_RESULT "):
            say("  | " + ln)
    check(proc.returncode == 0 and len(result) == 1,
          f"{name} leg failed (exit {proc.returncode}); its output is above")
    info = json.loads(result[0][len("LEG_RESULT "):])
    say(f"   {name} leg ok: platform={info['platform']} "
        f"device_kind={info['kind']!r} devices={info['count']}")
    return info


def run_kernel_leg(rehearsal: bool) -> dict:
    return run_child_leg(
        "kernel", "flash_attention, paged_decode_attention and "
        "latent_prefill_attention vs their XLA forms on the device",
        rehearsal)


# ---------------------------------------------------------------------------
# Serving legs: the README's command as the child, driven over HTTP
# ---------------------------------------------------------------------------


def stop_process(proc: subprocess.Popen) -> None:
    """SIGTERM the child's process group, then SIGKILL what remains."""
    if proc.poll() is None:
        os.killpg(proc.pid, signal.SIGTERM)
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            pass
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except ProcessLookupError:
        pass
    proc.wait()


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Stack:
    """`python -m ggrmcp_tpu gateway --tpu --config <file>` for one leg."""

    def __init__(self, leg: str, serving: dict, geo: dict, rehearsal: bool):
        self.leg, self.geo, self.rehearsal = leg, geo, rehearsal
        self.serving = serving
        self.port = free_port()
        os.makedirs(WORKDIR, exist_ok=True)
        self.log_path = os.path.join(WORKDIR, f"{leg}.log")
        self.cfg_path = os.path.join(WORKDIR, f"{leg}.json")
        config = {
            # A probe request may compile a program (a new chunk-grid
            # depth: 11-15 s at 7B, a cold first program more), which
            # the gateway's 30 s defaults leave too little room for.
            "server": {"request_timeout_s": geo["call_s"]},
            "grpc": {"call_timeout_s": geo["call_s"]},
            "serving": serving,
        }
        with open(self.cfg_path, "w") as f:
            json.dump(config, f, indent=1)
        self.proc: subprocess.Popen | None = None

    def __enter__(self) -> "Stack":
        cmd = [sys.executable, "-m", "ggrmcp_tpu", "gateway", "--tpu",
               "--config", self.cfg_path, "--http-port", str(self.port)]
        say("   $ " + " ".join(cmd[1:]))
        say("   config: " + json.dumps(self.serving))
        self._log = open(self.log_path, "wb")
        t0 = time.monotonic()
        self.proc = subprocess.Popen(
            cmd, cwd=HERE, env=child_env(self.rehearsal), stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True,
        )
        deadline = t0 + min(self.geo["ready_s"], remaining())
        while True:
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"{self.leg}: the gateway exited with code "
                    f"{self.proc.returncode} before it served"
                )
            try:
                tools = self.rpc("tools/list", {}, timeout=5)["tools"]
                if any(t["name"] == GENERATE for t in tools):
                    break
            except (OSError, SmokeFailure):
                pass  # not listening yet, or the sidecar not discovered
            check(time.monotonic() < deadline,
                  f"{self.leg}: not ready after {self.geo['ready_s']:.0f} s")
            time.sleep(1.0)
        self.ready_s = time.monotonic() - t0
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self.proc is not None:
            stop_process(self.proc)
        self._log.close()
        if exc_type is not None:
            say(f"---- last lines of {self.log_path}")
            with open(self.log_path, errors="replace") as f:
                for ln in f.readlines()[-60:]:
                    say("  | " + ln.rstrip()[:400])

    # -- HTTP ---------------------------------------------------------------

    def _post(self, body: dict, timeout: float, sse: bool = False) -> bytes:
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.port}/",
            data=json.dumps(body).encode(),
            headers={
                "Content-Type": "application/json",
                "Accept": "text/event-stream" if sse else "application/json",
            },
        )
        with urllib.request.urlopen(
            req, timeout=min(timeout, remaining())
        ) as resp:
            return resp.read()

    def rpc(self, method: str, params: dict, timeout: float) -> dict:
        reply = json.loads(self._post(
            {"jsonrpc": "2.0", "method": method, "id": 1, "params": params},
            timeout,
        ))
        check("error" not in reply, f"{self.leg}: {method} -> {reply}")
        return reply["result"]

    def call(self, tool: str, arguments: dict) -> dict:
        result = self.rpc(
            "tools/call", {"name": tool, "arguments": arguments},
            self.geo["call_s"],
        )
        check(not result.get("isError"),
              f"{self.leg}: {tool} returned isError: {result}")
        return json.loads(result["content"][0]["text"])

    def generate(self, prompt: str, new: int) -> list[int]:
        out = self.call(GENERATE, {
            "prompt": prompt, "maxNewTokens": new,
            "sampling": {"temperature": 0}, "returnTokens": True,
        })
        ids = out.get("tokenIds", [])
        check(1 <= len(ids) <= new and out["finishReason"] in
              ("length", "stop"),
              f"{self.leg}: generate returned {out}")
        check(all(isinstance(i, int) and i >= 0 for i in ids),
              f"{self.leg}: token ids {ids}")
        return ids

    def stream(self, prompt: str, new: int) -> int:
        """One generatestream over SSE; returns the number of events.
        Token ids ride only the chunks that carry text, and random
        weights at a 32,000 vocabulary mostly emit ids the byte
        tokenizer has no text for, so the proof is the terminal chunk."""
        raw = self._post({
            "jsonrpc": "2.0", "method": "tools/call", "id": 1,
            "params": {"name": STREAM, "arguments": {
                "prompt": prompt, "maxNewTokens": new,
                "sampling": {"temperature": 0}, "returnTokens": True,
            }},
        }, self.geo["call_s"], sse=True).decode()
        events = [
            (blk.split("\n", 1)[0].removeprefix("event: "),
             json.loads(blk.split("\ndata: ", 1)[1]))
            for blk in raw.strip().split("\n\n") if "\ndata: " in blk
        ]
        check(bool(events) and events[-1][0] == "result",
              f"{self.leg}: SSE did not end in a result event: {raw[-400:]}")
        result = events[-1][1].get("result", {})
        check("error" not in events[-1][1] and not result.get("isError"),
              f"{self.leg}: stream failed: {events[-1][1]}")
        last = json.loads(result["content"][-1]["text"])
        check(last.get("done") is True and last.get("finishReason") in
              ("length", "stop"), f"{self.leg}: stream ended with {last}")
        return len(events)

    def stats(self) -> dict:
        return self.call(STATS, {})

    def memory(self) -> dict:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{self.port}/debug/memory?reconcile=1",
            timeout=min(60, remaining()),
        ) as resp:
            backends = json.loads(resp.read())["backends"]
        check(len(backends) == 1, f"{self.leg}: /debug/memory {backends}")
        return backends[0]


def num(d: dict, key: str) -> int:
    """proto3 JSON: int64 arrives as a string, zero not at all."""
    return int(d.get(key, 0))


def prompt_of(n_tokens: int, salt: str) -> str:
    """A prompt of exactly n_tokens under the byte tokenizer (one token
    per byte plus BOS), distinct per salt from the first byte on."""
    body = (salt + " the quick brown fox jumps over the lazy dog;") * (
        n_tokens // 8
    )
    return body[: n_tokens - 1]


def request_pass(stack: Stack, geo: dict) -> dict:
    """Every request shape of a leg, once. Returns the greedy ids."""
    short = "chip smoke"
    ids = {"short": stack.generate(short, SHORT_NEW)}
    again = stack.generate(short, SHORT_NEW)
    check(again == ids["short"],
          f"{stack.leg}: greedy twice differs: {ids['short']} vs {again}")
    events = stack.stream(short, SHORT_NEW)
    long_prompt = prompt_of(geo["long_prompt"], "L")
    ids["long"] = stack.generate(long_prompt, SHORT_NEW)
    # Shares shared_prefix tokens with the long prompt, then diverges.
    cut = geo["shared_prefix"] - 1
    sharing = long_prompt[:cut] + prompt_of(geo["long_prompt"] - cut, "S")
    ids["sharing"] = stack.generate(sharing, SHORT_NEW)
    with ThreadPoolExecutor(CONCURRENT) as pool:
        # map() re-raises a worker's failure when its result is read.
        ids["concurrent"] = list(pool.map(
            lambda i: stack.generate(f"c{i}", CONCURRENT_NEW),
            range(CONCURRENT),
        ))
    say(f"   pass: short {len(ids['short'])} tok x2 identical, stream "
        f"{events} events, long {geo['long_prompt']}-token prompt, sharing "
        f"prompt ({geo['shared_prefix']} shared), {CONCURRENT} "
        f"concurrent x {CONCURRENT_NEW} new")
    return ids


def serving_leg(leg: str, serving: dict, geo: dict, rehearsal: bool) -> dict:
    say(f"== leg {leg}")
    devices = serving["mesh"]["tensor"]
    mesh_shape = "single" if devices == 1 else f"tensor={devices}"
    paged = serving["batching"]["paged_kv"] == "on"
    with Stack(leg, serving, geo, rehearsal) as stack:
        info = stack.call(MODEL_INFO, {})
        platform = info.get("platform", "")
        kind = info.get("deviceKind", "")
        say(f"   {leg}: platform={platform} device_kind={kind!r} "
            f"devices={num(info, 'numDevices')} model={info.get('modelId')}"
            f" — ready in {stack.ready_s:.0f} s (set-up: load + warm-up "
            f"compile on {kind} x{num(info, 'numDevices')})")
        check(platform == ("cpu" if rehearsal else "tpu"),
              f"{leg}: the stack reports platform {platform!r}")
        check(bool(kind), f"{leg}: no device_kind reported")
        check(num(info, "numDevices") == devices,
              f"{leg}: {num(info, 'numDevices')} devices, wanted {devices}")
        check(info.get("modelId") == geo["model"], f"{leg}: {info}")

        ready = stack.stats()
        first = request_pass(stack, geo)  # the probe batch: may compile
        probe = stack.stats()
        second = request_pass(stack, geo)
        stats = stack.stats()
        mem = stack.memory()

        say(f"   attention: {num(stats, 'attnKernelPrograms')} program(s) "
            f"traced with the Pallas kernel ("
            f"{num(probe, 'attnKernelPrograms') - num(ready, 'attnKernelPrograms')}"
            f" of them by the probe batch's long prompts), "
            f"{num(stats, 'attnKernelFallbacks')} fell back to XLA")
        moved = num(stats, "compilePostWarmup") - num(
            probe, "compilePostWarmup"
        )
        say(f"   compiles: {num(stats, 'compileCount')} in all, "
            f"{num(probe, 'compilePostWarmup')} after warm-up during the "
            f"probe batch, {moved} after it; compile cache hits="
            f"{num(stats, 'compileCacheHits')} misses="
            f"{num(stats, 'compileCacheMisses')}")
        if paged:
            # The second pass reuses pages (the short prompt copies the
            # long prompt's first page for the one BOS token they
            # share): a different program, so different roundings.
            say(f"   cold prefill vs page reuse, same greedy ids: short="
                f"{second['short'] == first['short']} long="
                f"{second['long'] == first['long']}")
        else:
            # No reuse without pages: each of these takes the same
            # programs in both passes. (The concurrent burst does not:
            # how it splits into single-row and full-pool admissions
            # depends on arrival timing.)
            for key in ("short", "long", "sharing"):
                check(second[key] == first[key],
                      f"{leg}: {key} greedy ids changed between passes: "
                      f"{first[key]} then {second[key]}")
        for key in ("shedRequests", "replayedRequests", "replayExhausted",
                    "timedOut", "meshSpecDowngrades",
                    "attnKernelFallbacks"):
            check(num(stats, key) == 0, f"{leg}: {key} = {num(stats, key)}")
        check(stats.get("meshShape") == mesh_shape,
              f"{leg}: mesh_shape {stats.get('meshShape')!r}, "
              f"wanted {mesh_shape!r}")
        if paged:
            check(num(stats, "pagedPagesReused") > 0,
                  f"{leg}: paged_pages_reused is 0 after prompts sharing "
                  f"{geo['shared_prefix']} tokens")
        if not rehearsal:
            # Chunked admission prefills into a contiguous mini cache
            # in every KV mode, so every leg's long prompt takes it.
            check(num(stats, "attnKernelPrograms") > 0,
                  f"{leg}: the long prefill did not take the compiled "
                  f"Pallas kernel (attn_kernel_programs is 0)")
        if moved:
            late = [c.get("fnName") for c in mem.get("compiles", [])
                    if c.get("postWarmup")][-moved:]
            raise SmokeFailure(
                f"{leg}: compile_post_warmup rose by {moved} after the "
                f"probe batch; latest post-warm-up programs: {late}"
            )
        # The census is exact only between ticks: a pipelined tick
        # still draining holds its token arrays for a moment.
        for _ in range(10):
            if num(mem, "unattributedBytes") == 0:
                break
            time.sleep(0.5)
            mem = stack.memory()
        per_device = [int(x) for x in mem.get("deviceBytesInUse", [])]
        say(f"   memory: ledger {num(mem, 'totalBytes')} B, live "
            f"{num(mem, 'liveBytes')} B, unattributed "
            f"{num(mem, 'unattributedBytes')} B; per-device bytes_in_use "
            f"{per_device}")
        check(num(mem, "unattributedBytes") == 0,
              f"{leg}: {num(mem, 'unattributedBytes')} unattributed bytes "
              f"in {num(mem, 'unattributedArrays')} arrays")
        if devices > 1 and not rehearsal:
            check(len(per_device) == devices, f"{leg}: {per_device}")
            ratio = max(per_device) / max(min(per_device), 1)
            say(f"   per-device max/min = {ratio:.3f}")
            check(ratio <= 1.3, f"{leg}: device memory skew {ratio:.2f} "
                  f"> 1.3: {per_device}")
    return first


# ---------------------------------------------------------------------------
# Parent
# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--cpu-rehearsal", action="store_true",
        help="run the control flow on the CPU at tiny-llama size; "
        "NOT a chip result",
    )
    ap.add_argument(
        "--legs", default="",
        help="comma-separated subset of kernel,sparse,keye,experts,serve,"
        "default_kv,tp4 "
        "(debugging; the default is every leg the host can hold)",
    )
    ap.add_argument(
        "--model", default="",
        help="registry key instead of mistral-7b (debugging, e.g. llama-1b)",
    )
    ap.add_argument("--child-kernel", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--child-sparse", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--child-keye", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--child-experts", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--child-jamba", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--child-window", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child_kernel:
        kernel_leg_child(args.cpu_rehearsal)
        return 0
    if args.child_sparse:
        sparse_leg_child(args.cpu_rehearsal)
        return 0
    if args.child_keye:
        keye_leg_child(args.cpu_rehearsal)
        return 0
    if args.child_experts:
        experts_leg_child(args.cpu_rehearsal)
        return 0
    if args.child_jamba:
        jamba_leg_child(args.cpu_rehearsal)
        return 0
    if args.child_window:
        window_leg_child(args.cpu_rehearsal)
        return 0

    rehearsal = args.cpu_rehearsal
    geo = dict(REHEARSAL if rehearsal else CHIP)
    if args.model:
        geo["model"] = args.model
    if rehearsal:
        say("CPU REHEARSAL: control flow at tiny-llama size on the CPU. "
            "This is NOT a chip result.")
    legs = [x for x in args.legs.split(",") if x]

    device = {"platform": "cpu" if rehearsal else "tpu", "kind": "", "count": 1}
    if not legs or "kernel" in legs:
        device = run_kernel_leg(rehearsal)
    if not legs or "sparse" in legs:
        run_child_leg(
            "sparse", "one deepseek-v3.2 expert layer, a sparse chunk and "
            "a sparse decode step, vs the float32 reference layer", rehearsal)
    if not legs or "keye" in legs:
        run_child_leg(
            "keye", "one keye layer (GQA under the indexer's selection "
            "over three planes, softmax-routed experts), a sparse chunk and "
            "a sparse decode step, vs the float32 reference layer", rehearsal)
    if not legs or "jamba" in legs:
        run_child_leg(
            "jamba", "the whole hybrid model (26 Mamba layers, 2 attention "
            "layers), a chunk and 8 decode steps, vs the float32 reference",
            rehearsal)
    if "window" in legs:  # on request (~4 min on the chip)
        run_child_leg(
            "window", "one SmallThinker period (a full layer, three "
            "window layers, ReGLU experts), chunks past the window, decode "
            "on pages of two kinds and a re-admission, vs the float32 "
            "reference", rehearsal)
    if "experts" in legs:  # on request: the keye and sparse legs run the
        # same kernel inside their layers, against the float32 layer
        run_child_leg(
            "experts", "the routed experts of one keye layer, the grouped "
            "SwiGLU kernel vs the XLA task loop vs float32", rehearsal)
    if not legs:
        legs = ["kernel", "sparse", "keye", "jamba", "serve", "default_kv"]
        if device["count"] >= 4 and not rehearsal:
            legs.append("tp4")
    else:
        say(f"NOTE: --legs {','.join(legs)}: a partial run")

    batching = {
        "max_batch_size": geo["slots"], "kv_cache_max_seq": geo["max_seq"],
        "prefill_chunk": geo["chunk"],
    }
    one_chip = {
        "model": geo["model"], "quantize": "int8",
        "synthetic_weights": True,
        # Every axis fixed, product 1: the first device of the host,
        # however many it holds (parallel/mesh.py).
        "mesh": {"tensor": 1},
    }
    paged_ids = None
    if "serve" in legs:
        paged_ids = serving_leg(
            "serve",
            {**one_chip, "batching": {**batching, "paged_kv": "on"}},
            geo, rehearsal,
        )
    if "default_kv" in legs:
        ids = serving_leg(
            "default_kv",
            {**one_chip, "batching": {**batching, "paged_kv": "off"}},
            geo, rehearsal,
        )
        if paged_ids is not None:
            a, b = paged_ids["short"], ids["short"]
            agree = next(
                (i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                min(len(a), len(b)),
            )
            say(f"   short greedy prompt, paged vs contiguous: first "
                f"{agree} of {len(a)} ids agree")
            # The first token comes from the same prefill (a fresh mini
            # cache either way) under the same weights.
            check(agree >= 1, f"paged and contiguous KV disagree on the "
                  f"first greedy token: {a} vs {b}")
    if "tp4" in legs:
        serving_leg(
            "tp4",
            {"model": geo["model"], "mesh": {"tensor": 4},
             "batching": {**batching, "paged_kv": "on"}},
            geo, rehearsal,
        )
    say(f"all legs passed: {','.join(legs)} "
        f"({time.monotonic() - T0:.0f} s in all, set-up included)")
    result = {"ok": True, "device": device}
    if rehearsal:
        result["rehearsal"] = True
    if args.legs:
        result["partial"] = legs  # not the contract's full run
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as failure:
        print(f"CHIP SMOKE FAILED: {failure}", flush=True)
        sys.exit(1)
