"""The plain reference: a Mistral/Llama-shaped decoder in straightforward
`jax.numpy`, float32, `default_matmul_precision("highest")`, with no
cache, no batching tricks and no kernel. It follows the published
equations (pre-norm RMSNorm, GQA with RoPE on half-split pairs, causal
sliding-window softmax attention, SwiGLU) for the configuration's own
sizes. It imports nothing of the program.

Weights are data made from a seed, not something the program hands
over: `synthetic_int8_weights` repeats the recipe of the served
`serving.synthetic_weights` mode (uniform int8 values, small positive
bf16 scales, split of one PRNG key over the leaves in tree order), so
the same key gives the same numbers here and in the sidecar. The
sidecar has no weights seed (it always takes PRNGKey(0)): see PERF.md,
Open questions.

Run as a child, after the stack has released the chip:

    python3 benchmark/reference.py <job.json>

The job names the configuration file and a sample of sequences with the
ids the server returned. Teacher-forced along those ids, the reference
computes at each returned token its margin: the reference's largest
logit minus the reference's logit of the token the server chose, in
units of that position's logit standard deviation. A server that
computes the same mathematics in bf16 picks the reference's own argmax
except where two logits lie within rounding of each other, so its
margins are 0 or tiny; a lower precision picks worse tokens more often
and by more. The last line printed is one JSON object.
"""

from __future__ import annotations

import json
import math
import os
import sys
import time


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


def leaf_shapes(m: dict) -> list:
    """(name, shape, is_int8) of every weight leaf in the served tree's
    flatten order: dict keys sorted, (q, scale) per quantized leaf."""
    d, layers, v = m["hidden_size"], m["num_hidden_layers"], m["vocab_size"]
    f, h, kvh = (m["intermediate_size"], m["num_attention_heads"],
                 m["num_key_value_heads"])
    hd = m.get("head_dim") or d // h
    qkv = (h + 2 * kvh) * hd
    return [
        ("embed.q", (v, d), True), ("embed.scale", (v, 1), False),
        ("final_norm", (d,), False),
        ("attn_norm", (layers, d), False), ("mlp_norm", (layers, d), False),
        ("w_down.q", (layers, f, d), True),
        ("w_down.scale", (layers, 1, d), False),
        ("w_gate.q", (layers, d, f), True),
        ("w_gate.scale", (layers, 1, f), False),
        ("w_up.q", (layers, d, f), True),
        ("w_up.scale", (layers, 1, f), False),
        ("wo.q", (layers, h * hd, d), True),
        ("wo.scale", (layers, 1, d), False),
        ("wqkv.q", (layers, d, qkv), True),
        ("wqkv.scale", (layers, 1, qkv), False),
        ("lm_head.q", (d, v), True), ("lm_head.scale", (1, v), False),
    ]


def synthetic_int8_weights(jax, m: dict, key_seed: int = 0) -> dict:
    """All leaves in one jitted call from one key, in the served dtype."""
    jnp = jax.numpy
    dtype = jnp.dtype(m.get("torch_dtype", "bfloat16"))
    leaves = leaf_shapes(m)

    def gen(key):
        keys = jax.random.split(key, len(leaves))
        out = {}
        for k, (name, shape, is_int8) in zip(keys, leaves):
            if is_int8:
                out[name] = jax.random.randint(
                    k, shape, -127, 128, jnp.int32
                ).astype(jnp.int8)
            else:
                out[name] = (
                    0.02 * jnp.abs(jax.random.normal(k, shape)).astype(dtype)
                    + jnp.asarray(1e-3, dtype)
                )
        return out

    return jax.jit(gen)(jax.random.PRNGKey(key_seed))


def make_forward(jax, m: dict):
    """tokens [B, S] -> (margin, sigma) [B, S]: at position i, the
    reference logits for token i+1, reduced against `chosen[:, i]`."""
    jnp = jax.numpy
    f32 = jnp.float32
    h, kvh = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m.get("head_dim") or m["hidden_size"] // h
    eps = float(m.get("rms_norm_eps", 1e-5))
    theta = float(m.get("rope_theta", 10000.0))
    window = m.get("sliding_window")

    def deq(w, name):
        return w[name + ".q"].astype(f32) * w[name + ".scale"].astype(f32)

    def rms(x, weight):
        return x * jax.lax.rsqrt(
            jnp.mean(x * x, axis=-1, keepdims=True) + eps
        ) * weight.astype(f32)

    def rope(x, pos):  # x [B, S, N, hd]
        inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=f32) / hd)
        ang = pos[:, None].astype(f32) * inv[None, :]  # [S, hd/2]
        cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
        x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def forward(w, tokens, chosen):
        b, s = tokens.shape
        x = w["embed.q"][tokens].astype(f32) * w["embed.scale"][tokens].astype(f32)
        pos = jnp.arange(s)
        visible = pos[None, :] <= pos[:, None]
        if window:
            visible &= pos[None, :] > pos[:, None] - int(window)

        def layer(x, lw):
            n = rms(x, lw["attn_norm"])
            qkv = n @ deq(lw, "wqkv")
            q = rope(qkv[..., : h * hd].reshape(b, s, h, hd), pos)
            k = rope(qkv[..., h * hd: (h + kvh) * hd].reshape(b, s, kvh, hd), pos)
            v = qkv[..., (h + kvh) * hd:].reshape(b, s, kvh, hd)
            k = jnp.repeat(k, h // kvh, axis=2)
            v = jnp.repeat(v, h // kvh, axis=2)
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
            scores = jnp.where(visible[None, None], scores, -jnp.inf)
            att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
            x = x + att.reshape(b, s, h * hd) @ deq(lw, "wo")
            n = rms(x, lw["mlp_norm"])
            gate = jax.nn.silu(n @ deq(lw, "w_gate"))
            x = x + (gate * (n @ deq(lw, "w_up"))) @ deq(lw, "w_down")
            return x, None

        stacked = {
            k: v for k, v in w.items()
            if k.split(".")[0] in ("attn_norm", "mlp_norm", "w_down",
                                   "w_gate", "w_up", "wo", "wqkv")
        }
        x, _ = jax.lax.scan(layer, x, stacked)
        logits = rms(x, w["final_norm"]) @ deq(w, "lm_head")  # [B, S, V]
        top = jnp.max(logits, axis=-1)
        took = jnp.take_along_axis(logits, chosen[..., None], axis=-1)[..., 0]
        return top - took, jnp.std(logits, axis=-1)

    def run(w, tokens, chosen):
        with jax.default_matmul_precision("highest"):
            return forward(w, tokens, chosen)

    return jax.jit(run)


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
    if model.get("weights") != "synthetic_int8":
        print(f"reference: no weights recipe for {model.get('weights')!r}",
              file=sys.stderr)
        return 1
    weights = synthetic_int8_weights(jax, model)
    fwd = make_forward(jax, model)
    rows, width = int(job["rows"]), int(job["width"])
    seqs = job["sequences"]  # [{"ids": [...], "compare": [[start, end], ...]}]
    margins, sigmas = [], []
    for at in range(0, len(seqs), rows):
        chunk = seqs[at: at + rows]
        tokens = np.zeros((rows, width), np.int32)
        chosen = np.zeros((rows, width), np.int32)
        mask = np.zeros((rows, width), bool)
        for r, seq in enumerate(chunk):
            ids = seq["ids"]
            tokens[r, : len(ids)] = ids
            chosen[r, : len(ids) - 1] = ids[1:]
            for start, end in seq["compare"]:
                # token i is predicted at position i - 1
                mask[r, start - 1: end - 1] = True
        margin, sigma = fwd(weights, jnp.asarray(tokens), jnp.asarray(chosen))
        margins.append(np.asarray(margin)[mask])
        sigmas.append(np.asarray(sigma)[mask])
    margin = np.concatenate(margins) if margins else np.zeros((0,))
    sigma = np.concatenate(sigmas) if sigmas else np.ones((0,))
    rel = margin / np.maximum(sigma, 1e-30)
    print(json.dumps({
        "tokens": int(rel.size),
        "mean_margin_sigma": float(rel.mean()) if rel.size else None,
        "max_margin_sigma": float(rel.max()) if rel.size else None,
        "flip_share": float((margin > 0).mean()) if rel.size else None,
        "mean_sq_margin_sigma": float((rel ** 2).mean()) if rel.size else None,
        "finite": bool(np.isfinite(rel).all()),
        "seconds": time.monotonic() - t0,
        "platform": dev.platform, "kind": dev.device_kind,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
