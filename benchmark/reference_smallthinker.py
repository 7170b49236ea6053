"""The plain reference of `model_name: smallthinker_*`
(SmallThinker-21BA3B-Instruct): grouped-query attention in two kinds of
layer, ReGLU experts routed from the attention block's input. Beside
`reference_keye.py`, in the same manner: straightforward `jax.numpy`,
float32, `default_matmul_precision("highest")`, no kernel, no cache, no
batching, the window as a mask over one softmax, every expert on every
token under a dense weight mask. It imports nothing of the program and
reads every size from the configuration file's keys.

Layer l of the served `num_hidden_layers`; x is [T, D]; H heads, G KV
heads, Dh wide; eps `rms_norm_eps`:

  h = RMSNorm(x)                      (every norm weight is one)
  r = float32(h) W_r                  [T, E]: the router reads h, BEFORE
                                      the attention (`assumed.router_input`)
  q = h W_q -> [H, Dh];  k = h W_k, v = h W_v -> [G, Dh]; no bias, no
  per-head norm
  if rope_layout[l] == 1: q, k turned by RoPE at the token's position
  over the whole head, half-split pairs (i, i + Dh/2), theta
  `rope_theta`; rope_layout 0: no positional encoding at all
  a[t, h] = sum_s softmax_s(q[t, h] . k[s, h // (H/G)] Dh^-0.5) v[s, ..]
  over s <= t, and where sliding_window_layout[l] == 1 also
  s > t - `sliding_window_size` (the query itself included)
  x1 = x + a W_o;  h2 = RMSNorm(x1)
  e_1..e_k = the top `moe_num_active_primary_experts` of r;
  g = softmax(r[e_1..e_k]) (`moe_primary_router_apply_softmax`;
  `norm_topk_prob` divides by their sum, which is 1)
  x2 = x1 + sum_j g_j (relu(h2 W_gate,e_j) * (h2 W_up,e_j)) W_down,e_j
  then a final RMSNorm and an untied head.

What the config has no key for is stated in the configuration file
under `assumed`, one key each.

Weights are data made from a seed (`weights: "family_init"`): leaf i of
`leaf_recipe` is `truncated_normal(split(PRNGKey(seed), n)[i], -2, 2,
shape, float32) * scale`, cast to `torch_dtype`; W_q | W_k | W_v are one
drawn matrix, side by side; norm weights ones. At the published widths
they wait in host memory and a layer's leaves are on the device while
that layer runs; attention goes by blocks of queries over all keys, the
head runs at the compared positions only.

    python3 benchmark/reference_smallthinker.py <job.json>

Job and result are those of `reference_keye.py`. `no_window: true`
lets the window layers attend every visible key: the proof that the
check's sample crossed the window (the served tokens must then fail).
There is no second, lower-precision reference here (`reference_keye.py`
has one): on the chip this model's margins are made by routing flips,
which a reference with float8 K and V does not follow any better than
the float32 one (PERF.md section 6, PR 53: the ratio read 0.91-1.02
sound and 1.00 served from float8 pages), so the float8 control is
told by `flip_share` instead.
"""

from __future__ import annotations

import json
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
from benchmark.reference_keye import layer_weights, to_host  # noqa: E402

QUERY_BLOCK = 128


def sizes(m: dict) -> dict:
    n = m["num_hidden_layers"]
    assert m["moe_primary_router_apply_softmax"] and m["norm_topk_prob"]
    assert m["rope_layout"][:n] == m["sliding_window_layout"][:n]
    assert m.get("rope_scaling") is None
    return dict(
        d=m["hidden_size"], h=m["num_attention_heads"],
        g=m["num_key_value_heads"], hd=m["head_dim"], v=m["vocab_size"],
        layers=n, e=m["moe_num_primary_experts"],
        top=m["moe_num_active_primary_experts"], f=m["moe_ffn_hidden_size"],
        window=m["sliding_window_size"], eps=float(m["rms_norm_eps"]),
        theta=float(m["rope_theta"]),
        kinds=list(m["sliding_window_layout"][:n]),
        dt=m.get("torch_dtype", "bfloat16"),
    )


def leaf_recipe(m: dict) -> list:
    """(name, shape, scale, dtype name) of every drawn leaf, in draw
    order; "layers." leaves are stacked over the layers."""
    z = sizes(m)
    d, h, g, hd, n, e, f, dt = (
        z[k] for k in ("d", "h", "g", "hd", "layers", "e", "f", "dt"))
    return [
        ("embed", (z["v"], d), 0.02, dt),
        ("layers.wqkv", (n, d, (h + 2 * g) * hd), d**-0.5, dt),
        ("layers.wo", (n, h * hd, d), (h * hd) ** -0.5, dt),
        ("layers.router", (n, d, e), d**-0.5, "float32"),
        ("layers.w_gate", (n, e, d, f), d**-0.5, dt),
        ("layers.w_up", (n, e, d, f), d**-0.5, dt),
        ("layers.w_down", (n, e, f, d), f**-0.5, dt),
        ("lm_head", (d, z["v"]), d**-0.5, dt),
    ]


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


def make_layers(jax, m: dict, window: bool = True):
    """({0: full layer, 1: window layer}, head): jitted, float32, one
    sequence [S, D] at a time, a layer's weights passed in their stored
    dtype. `window` False lets a window layer attend every visible key
    (the check's proof; its RoPE stays)."""
    jnp = jax.numpy
    f32 = jnp.float32
    z = sizes(m)
    h, g, hd, eps = (z[k] for k in ("h", "g", "hd", "eps"))

    def rms(x):  # every norm weight of the recipe is one
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)

    def rope(x, pos):
        """x [S, N, Dh]: rotate the pairs (i, i + Dh/2)."""
        freq = 1.0 / z["theta"] ** (jnp.arange(0, hd, 2, dtype=f32) / hd)
        ang = pos.astype(f32)[:, None] * freq[None, :]
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x0, x1 = x[..., : hd // 2], x[..., hd // 2:]
        return jnp.concatenate([x0 * cos - x1 * sin, x1 * cos + x0 * sin], -1)

    def attention(x, n, w, kind):
        s = x.shape[0]
        pos = jnp.arange(s)
        wq, wk, wv = jnp.split(
            w["wqkv"].astype(f32), [h * hd, (h + g) * hd], axis=1)
        k = (n @ wk).reshape(s, g, hd)
        v = (n @ wv).reshape(s, g, hd)
        if kind:
            k = rope(k, pos)
        pad = -s % QUERY_BLOCK

        def block(args):  # a block of queries, from the normed states on
            nq, qpos = args
            q = (nq @ wq).reshape(-1, h, hd)
            if kind:
                q = rope(q, qpos)
            visible = pos[None, :] <= qpos[:, None]  # [QB, S]
            if kind and window:
                visible &= pos[None, :] > qpos[:, None] - z["window"]
            scores = jnp.einsum(
                "qgrd,kgd->grqk", q.reshape(-1, g, h // g, hd), k) * hd**-0.5
            scores = jnp.where(visible[None, None], scores, -jnp.inf)
            att = jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(scores, -1), v)
            return att.reshape(-1, h * hd) @ w["wo"].astype(f32)

        def blocks(t):
            t = jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
            return t.reshape((s + pad) // QUERY_BLOCK, QUERY_BLOCK, *t.shape[1:])

        att = jax.lax.map(block, (blocks(n), blocks(pos)))
        return x + att.reshape(s + pad, -1)[:s]

    def route(n, w):
        """[S, E] float32: each token's weight on each expert, from the
        attention block's normed input."""
        r = n @ w["router"].astype(f32)
        picked, chosen = jax.lax.top_k(r, z["top"])
        gates = jax.nn.softmax(picked, axis=-1)
        rows = jnp.arange(n.shape[0])[:, None]
        return jnp.zeros_like(r).at[rows, chosen].set(gates)

    def layer(kind):
        def run(x, w):
            n = rms(x)
            weights = route(n, w)
            x = attention(x, n, w, kind)
            n2 = rms(x)

            def one(acc, ew):  # every expert on every token, by weight
                gate, up, down, wt = ew
                y = (jax.nn.relu(n2 @ gate.astype(f32))
                     * (n2 @ up.astype(f32))) @ down.astype(f32)
                return acc + wt[:, None] * y, None

            routed, _ = jax.lax.scan(
                one, jnp.zeros_like(x),
                (w["w_gate"], w["w_up"], w["w_down"], weights.T))
            return x + routed
        return run

    def head(x, lm_head, chosen):  # x [n, D] at the compared positions
        logits = rms(x) @ lm_head.astype(f32)
        took = jnp.take_along_axis(logits, chosen[:, None], axis=-1)[:, 0]
        return jnp.max(logits, -1) - took, jnp.std(logits, -1)

    def high(fn):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(run)

    return {0: high(layer(0)), 1: high(layer(1))}, high(head)


def hidden_states(jax, m: dict, w: dict, layers, ids):
    """Final hidden states [S, D] (before the last norm) of one
    sequence under `layers` (a `make_layers` result), a layer's weights
    on the device while it runs. `w` is the drawn weights, or
    `to_host`'s form of them."""
    jnp = jax.numpy
    x = w["embed"][jnp.asarray(ids)].astype(jnp.float32)
    for kind, leaves in zip(
            sizes(m)["kinds"], w.get("layers") or layer_weights(m, w)):
        x = layers[0][kind](x, {k: jnp.asarray(v) for k, v in leaves.items()})
    return x


def logits_of(jax, m: dict, w: dict, ids, **kw):
    """[S, V] float32 logits of one sequence (the CPU tests' yardstick
    for the program)."""
    x = hidden_states(jax, m, w, make_layers(jax, m, **kw), ids)
    jnp = jax.numpy
    with jax.default_matmul_precision("highest"):
        n = x * jax.lax.rsqrt(
            jnp.mean(x * x, -1, keepdims=True) + float(m["rms_norm_eps"]))
        return n @ jnp.asarray(w["lm_head"]).astype(jnp.float32)


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
    layers = make_layers(jax, model, window=not job.get("no_window"))
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
            jax, model, weights, layers,
            ids + [0] * (padded_len(len(ids)) - len(ids)))
        n_at = max(8, 1 << (len(at) - 1).bit_length())
        rows = np.asarray(at + [at[-1]] * (n_at - len(at)))
        chosen = jnp.asarray([ids[i + 1] for i in rows.tolist()])
        margin, sigma = layers[1](x[rows], weights["lm_head"], chosen)
        margins.append(np.asarray(margin)[: len(at)])
        sigmas.append(np.asarray(sigma)[: len(at)])
    keep = ("tokens", "flip_share", "mean_sq_margin_sigma")
    print(json.dumps({
        **summary(np.concatenate(margins) if margins else np.zeros((0,)),
                  np.concatenate(sigmas) if sigmas else np.ones((0,))),
        "window": not job.get("no_window"),
        "per_sequence": [
            {k: v for k, v in summary(m, s).items() if k in keep}
            for m, s in zip(margins, sigmas)],
        "seconds": time.monotonic() - t0,
        "platform": dev.platform, "kind": dev.device_kind,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
