"""The plain reference of the latent-attention + routed-experts decoder
(`model_type: deepseek_v3` without q-compression), beside
`reference.py`: straightforward `jax.numpy`, float32,
`default_matmul_precision("highest")`, no cache, no batching, the
expanded form of attention only, every routed (token, expert) pair by a
dense weight mask over all experts. It imports nothing of the program
and reads every size from the configuration file's published keys.

The layer, as published. h = RMSNorm(x), H heads, eps `rms_norm_eps`:

  q = h W_q -> [H, nope + rope];  a = h W_kv_a -> [rank + rope]
  c = RMSNorm(a[:rank]);  k_rope = RoPE(a[rank:]) (one per token)
  W_kv_b c -> [H, nope + v] = k_nope | v;  q_rope = RoPE(q[nope:])
  RoPE rotates the pairs (2i, 2i+1) (`rope_interleave`), theta
  `rope_theta`, no scaling
  scores = (q_nope.k_nope + q_rope.k_rope) / sqrt(nope + rope), causal
  softmax, times v, W_o
  layers < first_k_dense_replace: SwiGLU of width intermediate_size
  the others: s = sigmoid(h W_r); chosen = top-k of s + b; weights =
  s[chosen] / (sum + 1e-20) * routed_scaling_factor; output = sum of
  weight x SwiGLU expert (moe_intermediate_size) + one SwiGLU of width
  n_shared_experts x moe_intermediate_size on every token
  final RMSNorm, untied head.

Weights are data made from a seed (`weights: "family_init"`): leaf i of
the list in `leaf_recipe` is `truncated_normal(split(PRNGKey(seed),
n)[i], -2, 2, shape, float32) * scale`, cast to `torch_dtype`, norm
weights ones: what the served engine draws for this family. They stay
in that dtype on the device (float32 would be 15 GB at the published
widths) and are upcast a layer, or one expert, at a time; attention
goes by blocks of queries; the head runs at the compared positions
only.

Run as a child, after the stack has released the chip:

    python3 benchmark/reference_mla_moe.py <job.json>

Job and result are those of `reference.py` (margins of the returned
tokens, teacher-forced); sequences are taken one at a time, and the
result also lists each sequence's own reading (`per_sequence`).
"""

from __future__ import annotations

import json
import math
import os
import sys
import time

QUERY_BLOCK = 512


def _setup_jax(cpu: bool):
    if cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    elif os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        del os.environ["JAX_PLATFORMS"]
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        jax.config.update(
            "jax_compilation_cache_dir", os.path.join(root, ".jax_cache")
        )
    return jax


def leaf_recipe(m: dict) -> list:
    """(name, shape, scale, dtype name) of every drawn leaf, in draw
    order. "dense." leaves stack the leading dense layers, "moe." the
    expert layers."""
    d, h, v = m["hidden_size"], m["num_attention_heads"], m["vocab_size"]
    rank, nope = m["kv_lora_rank"], m["qk_nope_head_dim"]
    rope, vd = m["qk_rope_head_dim"], m["v_head_dim"]
    kd = m["first_k_dense_replace"]
    km = m["num_hidden_layers"] - kd
    f, fd, e = m["moe_intermediate_size"], m["intermediate_size"], m["n_routed_experts"]
    fs = m["n_shared_experts"] * f
    dt = m.get("torch_dtype", "bfloat16")
    attn = [
        ("wq", (d, h * (nope + rope)), d**-0.5),
        ("wkv_a", (d, rank + rope), d**-0.5),
        ("wkv_b", (rank, h * (nope + vd)), rank**-0.5),
        ("wo", (h * vd, d), (h * vd) ** -0.5),
    ]
    out = [("embed", (v, d), 0.02, dt)]
    for stack, n in (("dense", kd), ("moe", km)):
        out += [(f"{stack}.{name}", (n, *shape), scale, dt)
                for name, shape, scale in attn]
    out += [
        ("dense.w_gate", (kd, d, fd), d**-0.5, dt),
        ("dense.w_up", (kd, d, fd), d**-0.5, dt),
        ("dense.w_down", (kd, fd, d), fd**-0.5, dt),
        ("moe.router", (km, d, e), d**-0.5, "float32"),
        ("moe.router_bias", (km, e), 0.1, "float32"),
        ("moe.w_gate", (km, e, d, f), d**-0.5, dt),
        ("moe.w_up", (km, e, d, f), d**-0.5, dt),
        ("moe.w_down", (km, e, f, d), f**-0.5, dt),
        ("moe.ws_gate", (km, d, fs), d**-0.5, dt),
        ("moe.ws_up", (km, d, fs), d**-0.5, dt),
        ("moe.ws_down", (km, fs, d), fs**-0.5, dt),
        ("lm_head", (d, v), d**-0.5, dt),
    ]
    return out


def family_init_weights(jax, m: dict, key_seed: int = 0) -> dict:
    """One jitted draw a leaf, so that no float32 copy of a stacked
    expert bank is ever held."""
    jnp = jax.numpy
    recipe = leaf_recipe(m)
    keys = jax.random.split(jax.random.PRNGKey(key_seed), len(recipe))
    out = {}
    for k, (name, shape, scale, dt) in zip(keys, recipe):
        out[name] = jax.jit(
            lambda k, shape=shape, scale=scale, dt=dt: (
                jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32)
                * scale
            ).astype(dt)
        )(k)
    return out


def make_layers(jax, m: dict):
    """(dense_layer, expert_layer, head): jitted, float32, one sequence
    [S, D] at a time, a layer's weights passed in their stored dtype."""
    jnp = jax.numpy
    f32 = jnp.float32
    h = m["num_attention_heads"]
    rank, nope = m["kv_lora_rank"], m["qk_nope_head_dim"]
    rope, vd = m["qk_rope_head_dim"], m["v_head_dim"]
    eps = float(m["rms_norm_eps"])
    theta = float(m["rope_theta"])
    top_k, scaling = m["num_experts_per_tok"], float(m["routed_scaling_factor"])
    assert m.get("rope_scaling") is None and m.get("q_lora_rank") is None
    assert m["n_group"] == 1 and m["topk_group"] == 1
    assert m["scoring_func"] == "sigmoid" and m["norm_topk_prob"]

    def rms(x):  # every norm weight of the recipe is one
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)

    def rope_pairs(x, pos):  # x [S, ..., rope]: rotate (2i, 2i+1)
        inv = 1.0 / theta ** (jnp.arange(0, rope, 2, dtype=f32) / rope)
        ang = pos.astype(f32)[:, None] * inv[None, :]  # [S, rope/2]
        shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (rope // 2,)
        cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
        x0, x1 = x[..., 0::2], x[..., 1::2]
        return jnp.stack(
            [x0 * cos - x1 * sin, x1 * cos + x0 * sin], axis=-1
        ).reshape(x.shape)

    def swiglu(x, gate, up, down):
        return (jax.nn.silu(x @ gate.astype(f32)) * (x @ up.astype(f32))) @ (
            down.astype(f32))

    def attention(x, w):
        s = x.shape[0]
        pos = jnp.arange(s)
        n = rms(x)
        q = (n @ w["wq"].astype(f32)).reshape(s, h, nope + rope)
        a = n @ w["wkv_a"].astype(f32)
        c = rms(a[:, :rank])
        k_rope = rope_pairs(a[:, rank:], pos)  # [S, rope]
        kv = (c @ w["wkv_b"].astype(f32)).reshape(s, h, nope + vd)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        q_nope, q_rope = q[..., :nope], rope_pairs(q[..., nope:], pos)
        pad = -s % QUERY_BLOCK

        def block(args):
            qn, qr, qpos = args  # [QB, H, nope], [QB, H, rope], [QB]
            scores = (
                jnp.einsum("qhd,khd->hqk", qn, k_nope)
                + jnp.einsum("qhr,kr->hqk", qr, k_rope)
            ) / math.sqrt(nope + rope)
            scores = jnp.where(
                pos[None, None, :] <= qpos[None, :, None], scores, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)

        def blocks(t):
            t = jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
            return t.reshape((s + pad) // QUERY_BLOCK, QUERY_BLOCK, *t.shape[1:])

        att = jax.lax.map(block, (blocks(q_nope), blocks(q_rope), blocks(pos)))
        att = att.reshape(s + pad, h * vd)[:s]
        return x + att @ w["wo"].astype(f32)

    def dense_layer(x, w):
        x = attention(x, w)
        return x + swiglu(rms(x), w["w_gate"], w["w_up"], w["w_down"])

    def expert_layer(x, w):
        x = attention(x, w)
        n = rms(x)
        scores = jax.nn.sigmoid(n @ w["router"].astype(f32))  # [S, E]
        _, chosen = jax.lax.top_k(scores + w["router_bias"], top_k)
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20) * scaling
        rows = jnp.arange(x.shape[0])[:, None]
        weight = jnp.zeros_like(scores).at[rows, chosen].set(picked)

        def one(acc, ew):  # every expert on every token, masked by weight
            gate, up, down, wt = ew
            return acc + wt[:, None] * swiglu(n, gate, up, down), None

        routed, _ = jax.lax.scan(
            one, jnp.zeros_like(x),
            (w["w_gate"], w["w_up"], w["w_down"], weight.T))
        return x + routed + swiglu(n, w["ws_gate"], w["ws_up"], w["ws_down"])

    def head(x, lm_head, chosen):  # x [n, D] at the compared positions
        logits = rms(x) @ lm_head.astype(f32)
        took = jnp.take_along_axis(logits, chosen[:, None], axis=-1)[:, 0]
        return jnp.max(logits, -1) - took, jnp.std(logits, -1)

    def high(fn):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(run)

    return high(dense_layer), high(expert_layer), high(head)


def hidden_states(jax, m: dict, w: dict, layers, ids):
    """Final hidden states [S, D] (before the last norm) of one
    sequence."""
    jnp = jax.numpy
    dense_layer, expert_layer, _ = layers
    kd = m["first_k_dense_replace"]
    x = w["embed"][jnp.asarray(ids)].astype(jnp.float32)
    for stack, n, fn in (("dense", kd, dense_layer),
                         ("moe", m["num_hidden_layers"] - kd, expert_layer)):
        for i in range(n):
            x = fn(x, {k.split(".", 1)[1]: v[i] for k, v in w.items()
                       if k.startswith(stack + ".")})
    return x


LONG_STEP = 4 * QUERY_BLOCK


def padded_len(n: int) -> int:
    """A power of two up to one block of queries, whole blocks up to
    four, whole groups of four blocks above: the document sessions
    (6k-13k tokens) compile four lengths, not one a session."""
    if n <= QUERY_BLOCK:
        return max(32, 1 << (n - 1).bit_length())
    step = QUERY_BLOCK if n <= LONG_STEP else LONG_STEP
    return -(-n // step) * step


def summary(margin, sigma) -> dict:
    """The statistics of `reference.py` over the compared tokens."""
    import numpy as np

    rel = margin / np.maximum(sigma, 1e-30)
    some = bool(rel.size)
    return {
        "tokens": int(rel.size),
        "mean_margin_sigma": float(rel.mean()) if some else None,
        "max_margin_sigma": float(rel.max()) if some else None,
        "flip_share": float((margin > 0).mean()) if some else None,
        "mean_sq_margin_sigma": float((rel ** 2).mean()) if some else None,
        "finite": bool(np.isfinite(rel).all()),
    }


def main(job_path: str) -> int:
    with open(job_path) as f:
        job = json.load(f)
    jax = _setup_jax(bool(job.get("cpu")))
    import numpy as np

    jnp = jax.numpy
    with open(job["config_file"]) as f:
        model = json.load(f)
    dev = jax.devices()[0]
    if not job.get("cpu") and dev.platform != "tpu":
        print(f"reference: no TPU (found {dev.platform})", file=sys.stderr)
        return 1
    t0 = time.monotonic()
    if model.get("weights") != "family_init":
        print(f"reference: no weights recipe for {model.get('weights')!r}",
              file=sys.stderr)
        return 1
    weights = family_init_weights(jax, model)
    layers = make_layers(jax, model)
    margins, sigmas = [], []
    for seq in job["sequences"]:
        ids = seq["ids"]
        # token i is predicted at position i - 1
        at = sorted({i - 1 for start, end in seq["compare"]
                     for i in range(start, end)})
        if not at:
            continue
        # Round the length up so that few shapes compile; the padding
        # follows every compared position and cannot reach it (causal).
        x = hidden_states(jax, model, weights, layers,
                          ids + [0] * (padded_len(len(ids)) - len(ids)))
        n_at = max(8, 1 << (len(at) - 1).bit_length())
        rows = np.asarray(at + [at[-1]] * (n_at - len(at)))
        margin, sigma = layers[2](
            x[rows], weights["lm_head"],
            jnp.asarray([ids[i + 1] for i in rows.tolist()]))
        margins.append(np.asarray(margin)[: len(at)])
        sigmas.append(np.asarray(sigma)[: len(at)])
    margin = np.concatenate(margins) if margins else np.zeros((0,))
    sigma = np.concatenate(sigmas) if sigmas else np.ones((0,))
    print(json.dumps({
        **summary(margin, sigma),
        # each session's own reading, in the sample's order: how far
        # the statistic swings between sessions is in every run's log
        "per_sequence": [
            {k: v for k, v in summary(m, s).items()
             if k in ("tokens", "flip_share", "mean_sq_margin_sigma")}
            for m, s in zip(margins, sigmas)],
        "seconds": time.monotonic() - t0,
        "platform": dev.platform, "kind": dev.device_kind,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
