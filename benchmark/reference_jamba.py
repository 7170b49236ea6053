"""The plain reference of `model_type: jamba` (AI21-Jamba2-3B): runs of
Mamba-1 state-space layers around a few attention layers. Beside
`reference_keye.py`, in the same manner: straightforward `jax.numpy`,
float32, `default_matmul_precision("highest")`, a sequential scan a
token, no cache, no kernel, no batching, no snapshot. It imports
nothing of the program and reads every size from the configuration
file's keys.

Layer i is attention iff i % `attn_layer_period` == `attn_layer_offset`
(`assumed.layer_order`), else Mamba; every layer is
x += mixer(RMSNorm(x)); x += MLP(RMSNorm(x)), eps `rms_norm_eps`, the
MLP a dense SwiGLU of width `intermediate_size` (`num_experts` 1).

  attention: q = n W_q -> [H, Dh]; k, v = n W_k, n W_v -> [G, Dh], no
  bias, NO rotary (`assumed.rotary`); causal softmax(q . k Dh^-0.5) v;
  x + W_o o.
  Mamba (C = `mamba_expand` x `hidden_size` channels, N =
  `mamba_d_state`, R = `mamba_dt_rank`, K = `mamba_d_conv`):
  [u, z] = n W_in;  u_t = silu(b + sum_k w[k] u_{t - (K-1) + k}), a
  depthwise causal convolution (inputs before the sequence are zero);
  [dt, B, C] = u W_x, each under its own RMSNorm (`assumed.inner_norms`);
  dt = softplus(dt W_dt + b_dt);  A = -exp(A_log) [N, C];
  h_t = exp(dt_t A) * h_{t-1} + (dt_t u_t) B_t^T, h [N, C] float32,
  h_{-1} = 0;  y_t = C_t h_t + D * u_t;  x + ((y * silu(z)) W_out).
  final RMSNorm; the head is the embedding (`tie_word_embeddings`).

Weights are data made from a seed (`weights: "family_init"`): leaf i of
`leaf_recipe` is `truncated_normal(split(PRNGKey(seed), n)[i], -2, 2,
shape, float32) * scale`, cast to `torch_dtype`; norm weights ones, the
convolution's bias zeros, A_log = log(1 .. N) a channel, D ones and
b_dt the inverse softplus of exp(linspace(log 1e-3, log 1e-1, C)), all
three float32 (`assumed.weights`). They wait in host memory and a
layer's leaves are on the device while that layer runs.

    python3 benchmark/reference_jamba.py <job.json>

Job and result are those of `reference_keye.py`.
"""

from __future__ import annotations

import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference_dsv32 import (  # noqa: E402
    _setup_jax,
    padded_len,
    summary,
)
QUERY_BLOCK = 128

def sizes(m: dict) -> dict:
    assert m["num_experts"] == 1 and m["tie_word_embeddings"]
    assert not m["mamba_proj_bias"] and m["mamba_conv_bias"]
    assert m["hidden_size"] % m["num_attention_heads"] == 0
    layers = m["num_hidden_layers"]
    attn = [i for i in range(layers)
            if i % m["attn_layer_period"] == m["attn_layer_offset"]]
    return dict(
        d=m["hidden_size"], h=m["num_attention_heads"],
        g=m["num_key_value_heads"],
        hd=m["hidden_size"] // m["num_attention_heads"], v=m["vocab_size"],
        f=m["intermediate_size"], layers=layers, attn=attn,
        c=m["mamba_expand"] * m["hidden_size"], n=m["mamba_d_state"],
        r=m["mamba_dt_rank"], k=m["mamba_d_conv"],
        eps=float(m["rms_norm_eps"]), dt=m.get("torch_dtype", "bfloat16"),
    )


def leaf_recipe(m: dict) -> list:
    """(name, shape, scale, dtype name) of every drawn leaf, in draw
    order; "mamba." leaves are stacked over the Mamba layers, "attn."
    over the attention layers."""
    z = sizes(m)
    d, c, f, n, r, k, h, g, hd, dt = (
        z[key] for key in ("d", "c", "f", "n", "r", "k", "h", "g", "hd", "dt"))
    la = len(z["attn"])
    lm = z["layers"] - la

    def mlp(group, count):
        return [(f"{group}.w_gate", (count, d, f), d**-0.5, dt),
                (f"{group}.w_up", (count, d, f), d**-0.5, dt),
                (f"{group}.w_down", (count, f, d), f**-0.5, dt)]

    return [
        ("embed", (z["v"], d), 0.02, dt),
        ("mamba.in_proj", (lm, d, 2 * c), d**-0.5, dt),
        ("mamba.conv_w", (lm, k, c), k**-0.5, dt),
        ("mamba.x_proj", (lm, c, r + 2 * n), c**-0.5, dt),
        ("mamba.dt_proj", (lm, r, c), r**-0.5, dt),
        ("mamba.out_proj", (lm, c, d), c**-0.5, dt),
        *mlp("mamba", lm),
        ("attn.wqkv", (la, d, (h + 2 * g) * hd), d**-0.5, dt),
        ("attn.wo", (la, h * hd, d), (h * hd) ** -0.5, dt),
        *mlp("attn", la),
    ]


def family_init_weights(jax, m: dict, key_seed: int = 0) -> dict:
    jnp = jax.numpy
    recipe = leaf_recipe(m)
    keys = jax.random.split(jax.random.PRNGKey(key_seed), len(recipe))
    out = {}
    for key, (name, shape, scale, dt) in zip(keys, recipe):
        out[name] = jax.jit(
            lambda key, shape=shape, scale=scale, dt=dt: (
                jax.random.truncated_normal(
                    key, -2.0, 2.0, shape, jnp.float32) * scale
            ).astype(dt)
        )(key)
    return out


def to_host(jax, m: dict, w: dict) -> dict:
    """The drawn weights off the device, a dict of its own leaves for
    each layer in the stack's order ("kind": "mamba" or "attn"); the
    embedding stays on the device."""
    import numpy as np

    z = sizes(m)
    host = {}
    for name in list(w):
        leaf = w.pop(name)
        host[name] = leaf if name == "embed" else np.asarray(leaf)
    layers, at = [], {"mamba": 0, "attn": 0}
    for i in range(z["layers"]):
        kind = "attn" if i in z["attn"] else "mamba"
        layers.append({"kind": kind, **{
            name.split(".", 1)[1]: leaf[at[kind]]
            for name, leaf in host.items() if name.startswith(kind + ".")}})
        at[kind] += 1
    return {"embed": host["embed"], "layers": layers}


def make_layers(jax, m: dict):
    """(mamba, attention, head): jitted, float32, one sequence [S, D]
    at a time, a layer's weights passed in their stored dtype."""
    jnp = jax.numpy
    f32 = jnp.float32
    z = sizes(m)
    h, g, hd, c, n, r, taps, eps = (
        z[key] for key in ("h", "g", "hd", "c", "n", "r", "k", "eps"))

    def rms(x):  # every norm weight of the recipe is one
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)

    def mlp(x, w):
        nx = rms(x)
        return x + (jax.nn.silu(nx @ w["w_gate"].astype(f32)) * (
            nx @ w["w_up"].astype(f32))) @ w["w_down"].astype(f32)

    def mamba(x, w):
        s = x.shape[0]
        u, gate = jnp.split(rms(x) @ w["in_proj"].astype(f32), 2, axis=-1)
        before = jnp.concatenate([jnp.zeros((taps - 1, c), f32), u])
        conv_w = w["conv_w"].astype(f32)
        u = jax.nn.silu(sum(before[k: k + s] * conv_w[k] for k in range(taps)))
        dt, b_m, c_m = jnp.split(
            u @ w["x_proj"].astype(f32), [r, r + n], axis=-1)
        step_dt = jnp.exp(jnp.linspace(math.log(1e-3), math.log(1e-1), c))
        dt_bias = step_dt + jnp.log(-jnp.expm1(-step_dt))
        dt = jax.nn.softplus(rms(dt) @ w["dt_proj"].astype(f32) + dt_bias)
        b_m, c_m = rms(b_m), rms(c_m)
        a = -jnp.arange(1, n + 1, dtype=f32)[:, None]  # -exp(A_log) [N, 1]

        def token(state_h, xs):
            dt_t, u_t, b_t, c_t = xs
            state_h = jnp.exp(dt_t[None, :] * a) * state_h + (
                (dt_t * u_t)[None, :] * b_t[:, None])
            return state_h, (state_h * c_t[:, None]).sum(0)

        _, y = jax.lax.scan(token, jnp.zeros((n, c), f32), (dt, u, b_m, c_m))
        y = y + u  # D is ones
        return mlp(x + (y * jax.nn.silu(gate)) @ w["out_proj"].astype(f32), w)

    def attention(x, w):
        s = x.shape[0]
        pos = jnp.arange(s)
        q, k, v = jnp.split(
            rms(x) @ w["wqkv"].astype(f32), [h * hd, (h + g) * hd], axis=-1)
        k, v = k.reshape(s, g, hd), v.reshape(s, g, hd)
        pad = -s % QUERY_BLOCK

        def block(args):
            q_b, qpos = args
            scores = jnp.einsum(
                "qgrd,kgd->grqk", q_b.reshape(-1, g, h // g, hd), k) * hd**-0.5
            scores = jnp.where(
                (pos[None, :] <= qpos[:, None])[None, None], scores, -jnp.inf)
            return jnp.einsum(
                "grqk,kgd->qgrd", jax.nn.softmax(scores, -1), v
            ).reshape(-1, h * hd)

        def blocks(t):
            t = jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
            return t.reshape((s + pad) // QUERY_BLOCK, QUERY_BLOCK, *t.shape[1:])

        att = jax.lax.map(block, (blocks(q), blocks(pos)))
        att = att.reshape(s + pad, -1)[:s] @ w["wo"].astype(f32)
        return mlp(x + att, w)

    def head(x, embed, chosen):  # x [n, D] at the compared positions
        logits = rms(x) @ embed.astype(f32).T
        took = jnp.take_along_axis(logits, chosen[:, None], axis=-1)[:, 0]
        return jnp.max(logits, -1) - took, jnp.std(logits, -1)

    def high(fn):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(run)

    return high(mamba), high(attention), high(head)


def hidden_states(jax, w: dict, fns: tuple, ids):
    """Final hidden states [S, D] (before the last norm) of one
    sequence, a layer's weights on the device while it runs. `w` is
    `to_host`'s form of the drawn weights, `fns` `make_layers`'."""
    jnp = jax.numpy
    x = w["embed"][jnp.asarray(ids)].astype(jnp.float32)
    for layer in w["layers"]:
        leaves = {k: jnp.asarray(v) for k, v in layer.items() if k != "kind"}
        x = fns[0 if layer["kind"] == "mamba" else 1](x, leaves)
    return x


def logits_of(jax, m: dict, w: dict, ids):
    """Float32 logits [S, V] of one sequence: what the CPU tests hold
    the program's forward to."""
    jnp = jax.numpy
    x = hidden_states(jax, w, make_layers(jax, m), ids)
    eps = float(m["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
        return x @ w["embed"].astype(jnp.float32).T


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
    weights = to_host(jax, model, family_init_weights(jax, model))
    fns = make_layers(jax, model)
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
        x = hidden_states(
            jax, weights, fns, ids + [0] * (padded_len(len(ids)) - len(ids)))
        n_at = max(8, 1 << (len(at) - 1).bit_length())
        rows = np.asarray(at + [at[-1]] * (n_at - len(at)))
        chosen = jnp.asarray([ids[i + 1] for i in rows.tolist()])
        margin, sigma = fns[2](x[rows], weights["embed"], chosen)
        margins.append(np.asarray(margin)[: len(at)])
        sigmas.append(np.asarray(sigma)[: len(at)])

    def whole(parts, empty):
        return np.concatenate(parts) if parts else empty((0,))

    keep = ("tokens", "flip_share", "mean_sq_margin_sigma")
    print(json.dumps({
        **summary(whole(margins, np.zeros), whole(sigmas, np.ones)),
        "per_sequence": [
            {k: v for k, v in summary(mg, sg).items() if k in keep}
            for mg, sg in zip(margins, sigmas)],
        "seconds": time.monotonic() - t0,
        "platform": dev.platform, "kind": dev.device_kind,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
