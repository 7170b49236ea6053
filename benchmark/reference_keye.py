"""The plain reference of `model_type: KeyeVL2` (the language model of
Keye-VL-2.0-30B-A3B): grouped-query attention under a learned
sparse-attention indexer, softmax-routed experts in every layer. Beside
`reference_dsv32.py`, in the same manner: straightforward `jax.numpy`,
float32, `default_matmul_precision("highest")`, no kernel, no cache, no
batching, the selection as a mask over one softmax, every expert on
every token under a dense weight mask. It imports nothing of the
program and reads every size from the configuration file's keys.

The layer (all alike: `decoder_sparse_step` 1, `mlp_only_layers` []).
n = RMSNorm(x), eps `rms_norm_eps`; H heads, G KV heads, Dh wide:

  q = n W_q -> [H, Dh];  k = n W_k, v = n W_v -> [G, Dh], no bias
  q, k: RMSNorm over Dh, a head at a time (`assumed.qk_norm`), then
  RoPE at the token's position over the whole head, half-split pairs
  (i, i + Dh/2), theta `rope_theta` (`mrope_section` gives each
  frequency one of three position streams; they coincide for text)
  indexer: qI = n W_qI -> [HI, DI];  kI = LayerNorm(n W_kI) (DI, weight
  and bias, eps `rms_norm_eps`), ONE a token;  w = n W_w (HI) x HI^-0.5
  DI^-0.5;  RoPE with the attention's theta on the first
  `assumed.indexer_rope_dim` values of qI and kI, half-split pairs
  I[t, s] = sum_j w[t, j] ReLU(qI[t, j] . kI[s]), s <= t
  S_t = the min(topk, t + 1) positions of largest I[t, .], exact, ties
  to the lower position
  o[t, h] = sum_{s in S_t} softmax_s(q[t, h] . k[s, h // (H/G)]
  Dh^-0.5) v[s, h // (H/G)];  x + W_o o
  experts: p = softmax(n W_r) over `num_experts`, float32; the top
  `num_experts_per_tok`; weights p / (sum of those + 1e-20)
  (`norm_topk_prob`); sum_e w_e W_down,e (silu(W_gate,e n) * W_up,e n).
  No shared expert, no bias, no scaling, no groups, no dense layer.
  final RMSNorm, untied head.

Departures from the published model, each stated in the configuration
file under `assumed` (one key each, so a correction is a number):
(1) the per-head RMSNorm on q and k is the convention of the
Qwen3-MoE-shaped key set, the config has no key for it; (2) where
`sa_config` is silent the indexer follows DeepSeek-V3.2's published
form: LayerNorm with weight and bias on kI, w and the queries from the
normed hidden states (there is no q-compression), RoPE on the first 32
of 64 values, keys in the weights' precision with no FP8 plane and no
Hadamard rotation (orthogonal: it cancels in qI . kI); (3)
`q_chunk_size` / `kv_chunk_size` are the published kernel's tiles and do
not enter the equations: selection is by token; (4) the vision tower is
not served: a call carries text tokens.

Weights are data made from a seed (`weights: "family_init"`): leaf i of
`leaf_recipe` is `truncated_normal(split(PRNGKey(seed), n)[i], -2, 2,
shape, float32) * scale`, cast to `torch_dtype`; norm weights ones, the
LayerNorm's bias zeros. At the published widths they wait in host
memory and a layer's leaves are on the device while that layer runs;
attention goes by blocks of queries over all keys, the head runs at the
compared positions only.

    python3 benchmark/reference_keye.py <job.json>

Job and result are those of `reference_dsv32.py`. `no_selection: true`
attends every visible key instead: the proof that the check sees the
mechanism (the served tokens must then fail). `lower_planes: <dtype>`
(`float8_e4m3fn`) teacher-forces the same tokens through a second
reference as well, float32 but for K, V and the indexer's keys, which
are rounded to that dtype as a cache of it would hand them back: the
nearest precision below the configuration's. The result then carries
`mean_sq_margin_sigma_lower` and `sq_margin_vs_lower`, the float32
reference's mean square margin over the lower one's: the same tokens
under both, so what makes one prefix flip four times the tokens of
another (3.7 x in `mean_sq_margin_sigma` over the sound seeds) divides
out. Below 1 the served tokens are the float32 reference's more than
the lower one's; tokens served from float8 planes read above it.
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

QUERY_BLOCK = 128


def sizes(m: dict) -> dict:
    sa, assumed = m["sa_config"], m["assumed"]
    assert m["num_experts"] == m["num_local_experts"] and m["norm_topk_prob"]
    assert sa["indexer_num_kv_heads"] == 1 and not m["attention_bias"]
    assert m["decoder_sparse_step"] == 1 and not m["mlp_only_layers"]
    return dict(
        d=m["hidden_size"], h=m["num_attention_heads"],
        g=m["num_key_value_heads"], hd=m["head_dim"], v=m["vocab_size"],
        layers=m["num_hidden_layers"], e=m["num_experts"],
        top=m["num_experts_per_tok"], f=m["moe_intermediate_size"],
        hi=sa["indexer_num_heads"], di=sa["indexer_head_dim"],
        topk=sa["topk"], eps=float(m["rms_norm_eps"]),
        theta=float(m["rope_theta"]),
        qk_norm=bool(assumed["qk_norm"]["value"]),
        idx_rope=int(assumed["indexer_rope_dim"]["value"]),
        dt=m.get("torch_dtype", "bfloat16"),
    )


def leaf_recipe(m: dict) -> list:
    """(name, shape, scale, dtype name) of every drawn leaf, in draw
    order; "layers." leaves are stacked over the layers."""
    z = sizes(m)
    d, h, g, hd, n, e, f, dt = (
        z[k] for k in ("d", "h", "g", "hd", "layers", "e", "f", "dt"))
    per_layer = [
        ("wq", (d, h * hd), d**-0.5), ("wk", (d, g * hd), d**-0.5),
        ("wv", (d, g * hd), d**-0.5), ("wo", (h * hd, d), (h * hd) ** -0.5),
        ("idx_wq", (d, z["hi"] * z["di"]), d**-0.5),
        ("idx_wk", (d, z["di"]), d**-0.5), ("idx_ww", (d, z["hi"]), d**-0.5),
    ]
    return [
        ("embed", (z["v"], d), 0.02, dt),
        *((f"layers.{name}", (n, *shape), scale, dt)
          for name, shape, scale in per_layer),
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


def layer_weights(m: dict, w: dict) -> list:
    """A dict of its own leaves for each layer, in layer order."""
    return [
        {k.split(".", 1)[1]: v[i] for k, v in w.items()
         if k.startswith("layers.")}
        for i in range(m["num_hidden_layers"])]


def to_host(jax, m: dict, w: dict) -> dict:
    """The drawn weights off the device (8.75 GB at the published
    widths, and a 24k-token sequence's float32 score blocks need the
    room): a layer's leaves come back a layer at a time
    (`hidden_states`). Embedding and head stay on the device."""
    import numpy as np

    host = {}
    for name in list(w):  # a leaf at a time; popped, the device's goes
        leaf = w.pop(name)
        host[name] = leaf if name in ("embed", "lm_head") else np.asarray(leaf)
    return {"embed": host["embed"], "lm_head": host["lm_head"],
            "layers": layer_weights(m, host)}


def make_layers(jax, m: dict, select: bool = True, by_rank: bool = False,
                planes: str = ""):
    """(layer, head, attention): jitted, float32, one sequence [S, D] at a time, a
    layer's weights passed in their stored dtype. `select` False
    attends every visible key (the check's proof). `planes` (a dtype
    name) rounds K, V and the indexer's keys to it and back, as a cache
    of that dtype hands them to attention: the check's lower
    reference; everything else stays float32. `by_rank` writes
    S_t as its definition reads, a key's rank among the query's keys
    (two stable argsorts a block of queries); the default finds the
    same set from the topk-th largest score and the ties' positions
    (one sort of the values), which is what a 24k-token prefix can
    afford inside a run's time limit; tests/test_keye.py holds the two
    equal on scores with ties at the cut."""
    jnp = jax.numpy
    f32 = jnp.float32
    z = sizes(m)
    h, g, hd, hi, di, topk, eps = (
        z[k] for k in ("h", "g", "hd", "hi", "di", "topk", "eps"))

    def rms(x):  # every norm weight of the recipe is one
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)

    def rope_halves(x, pos, width):
        """x [S, N, W]: rotate the pairs (i, i + width/2) of x[..., :width]."""
        freq = 1.0 / z["theta"] ** (
            jnp.arange(0, width, 2, dtype=f32) / width)
        ang = pos.astype(f32)[:, None] * freq[None, :]  # [S, width/2]
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x0, x1 = x[..., : width // 2], x[..., width // 2: width]
        return jnp.concatenate(
            [x0 * cos - x1 * sin, x1 * cos + x0 * sin, x[..., width:]], axis=-1)

    def selected(index):
        """[QB, S] bool: each query's `topk` largest index scores, the
        lower position first among equals (-inf: not visible)."""
        if by_rank:  # a key's rank among the query's keys
            order = jnp.argsort(-index, axis=-1, stable=True)
            return jnp.argsort(order, axis=-1, stable=True) < topk
        cut = -jnp.sort(-index, axis=-1)[:, topk - 1: topk]
        above = index > cut
        tied = (index == cut) & (index > -jnp.inf)
        room = topk - above.sum(-1, keepdims=True)
        return above | (tied & (jnp.cumsum(tied, axis=-1) <= room))

    def attention(x, w):
        s = x.shape[0]
        pos = jnp.arange(s)
        n = rms(x)
        k = (n @ w["wk"].astype(f32)).reshape(s, g, hd)
        v = (n @ w["wv"].astype(f32)).reshape(s, g, hd)
        if z["qk_norm"]:
            k = rms(k)
        k = rope_halves(k, pos, hd)
        # the indexer's key, one a token (LayerNorm weight one, bias zero)
        k_i = n @ w["idx_wk"].astype(f32)
        k_i = k_i - k_i.mean(-1, keepdims=True)
        k_i = k_i * jax.lax.rsqrt((k_i * k_i).mean(-1, keepdims=True) + eps)
        k_i = rope_halves(k_i[:, None], pos, z["idx_rope"])[:, 0]
        if planes:  # what a cache of that precision would hand back
            k, v, k_i = (t.astype(planes).astype(f32) for t in (k, v, k_i))
        pad = -s % QUERY_BLOCK

        def block(args):  # a block of queries, from the normed states on
            nq, qpos = args
            q = (nq @ w["wq"].astype(f32)).reshape(-1, h, hd)
            if z["qk_norm"]:
                q = rms(q)
            q = rope_halves(q, qpos, hd)
            visible = pos[None, :] <= qpos[:, None]  # [QB, S]
            if select and s > topk:
                qi = rope_halves(
                    (nq @ w["idx_wq"].astype(f32)).reshape(-1, hi, di),
                    qpos, z["idx_rope"])
                wi = (nq @ w["idx_ww"].astype(f32)) * (hi**-0.5 * di**-0.5)
                index = (
                    jax.nn.relu(jnp.einsum("qhd,kd->qhk", qi, k_i))
                    * wi[:, :, None]).sum(1)  # [QB, S]
                visible &= selected(jnp.where(visible, index, -jnp.inf))
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
        """[S, E] float32: each token's weight on each expert."""
        p = jax.nn.softmax(n @ w["router"].astype(f32), axis=-1)
        picked, chosen = jax.lax.top_k(p, z["top"])
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
        rows = jnp.arange(n.shape[0])[:, None]
        return jnp.zeros_like(p).at[rows, chosen].set(picked)

    def layer(x, w):
        x = attention(x, w)
        n = rms(x)

        def one(acc, ew):  # every expert on every token, by weight
            gate, up, down, wt = ew
            y = (jax.nn.silu(n @ gate.astype(f32)) * (n @ up.astype(f32))) @ (
                down.astype(f32))
            return acc + wt[:, None] * y, None

        routed, _ = jax.lax.scan(
            one, jnp.zeros_like(x),
            (w["w_gate"], w["w_up"], w["w_down"], route(n, w).T))
        return x + routed

    def head(x, lm_head, chosen):  # x [n, D] at the compared positions
        logits = rms(x) @ lm_head.astype(f32)
        took = jnp.take_along_axis(logits, chosen[:, None], axis=-1)[:, 0]
        return jnp.max(logits, -1) - took, jnp.std(logits, -1)

    def high(fn):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(run)

    return high(layer), high(head), high(attention)


def hidden_states_each(jax, m: dict, w: dict, variants: list, ids) -> list:
    """Final hidden states [S, D] (before the last norm) of one
    sequence under each of `variants` (`make_layers` results), a
    layer's weights on the device once for all of them. `w` is the
    drawn weights, or `to_host`'s form of them."""
    jnp = jax.numpy
    xs = [w["embed"][jnp.asarray(ids)].astype(jnp.float32)] * len(variants)
    for leaves in w.get("layers") or layer_weights(m, w):
        leaves = {k: jnp.asarray(v) for k, v in leaves.items()}
        xs = [layers[0](x, leaves) for layers, x in zip(variants, xs)]
    return xs


def hidden_states(jax, m: dict, w: dict, layers, ids):
    return hidden_states_each(jax, m, w, [layers], ids)[0]


# Where the lower reference's mean square margin is under this, the
# tokens disagree with neither reference enough to tell them apart
# (one sound prefix in two dozen on the chip: 4 flipped tokens of
# 1,700, 1e-4 under both, a ratio of 0.97 made of nothing): the ratio
# is then counted over this floor, a twentieth of what an ordinary
# prefix reads under the lower reference (0.04-0.06).
LOWER_FLOOR = 2e-3


def sq_ratio(above: dict, below: dict):
    """`above`'s mean square margin over `below`'s (two `summary`s of
    the same tokens), `below` no smaller than LOWER_FLOOR."""
    a, b = above["mean_sq_margin_sigma"], below["mean_sq_margin_sigma"]
    return None if a is None or b is None else a / max(b, LOWER_FLOOR)


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
    # The float32 reference and, where the job names `lower_planes`,
    # the same reference with K, V and the indexer's keys rounded to
    # that dtype: the precision below the configuration's cache.
    lower = job.get("lower_planes") or ""
    variants = [
        make_layers(jax, model, select=not job.get("no_selection"), planes=p)
        for p in ([""] + ([lower] if lower else []))]
    margins, sigmas = [[] for _ in variants], [[] for _ in variants]
    for seq in job["sequences"]:
        ids = seq["ids"]
        # token i is predicted at position i - 1
        at = sorted({i - 1 for start, end in seq["compare"]
                     for i in range(start, end)})
        if not at:
            continue
        # Round the length up so that few shapes compile; the padding
        # follows every compared position and cannot reach it (causal).
        xs = hidden_states_each(
            jax, model, weights, variants,
            ids + [0] * (padded_len(len(ids)) - len(ids)))
        n_at = max(8, 1 << (len(at) - 1).bit_length())
        rows = np.asarray(at + [at[-1]] * (n_at - len(at)))
        chosen = jnp.asarray([ids[i + 1] for i in rows.tolist()])
        for k, (layers, x) in enumerate(zip(variants, xs)):
            margin, sigma = layers[1](x[rows], weights["lm_head"], chosen)
            margins[k].append(np.asarray(margin)[: len(at)])
            sigmas[k].append(np.asarray(sigma)[: len(at)])

    def whole(parts, empty):
        return np.concatenate(parts) if parts else empty((0,))

    def both(per):  # the float32 summary, with the ratio where paired
        out = dict(per[0])
        if lower:
            out["mean_sq_margin_sigma_lower"] = per[1]["mean_sq_margin_sigma"]
            out["sq_margin_vs_lower"] = sq_ratio(per[0], per[1])
        return out

    total = both([summary(whole(m, np.zeros), whole(s, np.ones))
                  for m, s in zip(margins, sigmas)])
    keep = ("tokens", "flip_share", "mean_sq_margin_sigma",
            "mean_sq_margin_sigma_lower", "sq_margin_vs_lower")
    print(json.dumps({
        **total,
        "selection": not job.get("no_selection"),
        "lower_planes": lower,
        "per_sequence": [
            {k: v for k, v in both(
                [summary(m[i], s[i]) for m, s in zip(margins, sigmas)]
            ).items() if k in keep}
            for i in range(len(margins[0]))],
        "seconds": time.monotonic() - t0,
        "platform": dev.platform, "kind": dev.device_kind,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
