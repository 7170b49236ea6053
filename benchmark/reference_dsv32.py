"""The plain reference of `model_type: deepseek_v32` (DeepSeek-V3.2):
q-compressed latent attention, a learned sparse-attention indexer,
group-limited sigmoid routing, one chip's share of the experts. Beside
`reference_mla_moe.py`, in the same manner: straightforward
`jax.numpy`, float32, `default_matmul_precision("highest")`, no cache,
no batching, the expanded form of attention with the selection as a
mask, every held expert on every token under a dense weight mask. It
imports nothing of the program and reads every size from the
configuration file's keys.

The layer, as this reference reads the published config. h =
RMSNorm(x), eps `rms_norm_eps`, H heads:

  c_q = RMSNorm(h W_qa) (q_lora_rank);  q = c_q W_qb -> [H, nope + rope]
  a = h W_kva -> [rank + rope];  c = RMSNorm(a[:rank]);  k_rope =
  RoPE(a[rank:]), one a token;  W_kvb c -> [H, nope + v] = k_nope | v
  RoPE on the pairs (2i, 2i+1), theta `rope_theta`, YaRN: pair i's
  frequency is f_i (1 - r_i) + f_i / factor r_i, r the linear ramp
  between the correction dimensions of beta_fast and beta_slow
  rotations over `original_max_position_embeddings`; cos / sin
  unscaled (mscale = mscale_all_dim)
  softmax scale (nope + rope)^-0.5 x (0.1 ln factor + 1)^2

  indexer (its own weights a layer): q_I = c_q W_Iq -> [HI, DI];
  k_I = LayerNorm(h W_Ik) (DI, weight and bias, eps 1e-6), one a
  token; RoPE with the same frequencies on the first `rope` values of
  each, half-split pairs (i, i + rope/2); w = h W_Iw (HI) x HI^-0.5
  DI^-0.5;  I[t, s] = sum_j w[t, j] ReLU(q_I[t, j] . k_I[s]), s <= t
  S_t = the min(index_topk, t + 1) positions of largest I[t, .],
  exact, ties to the lower position
  attention: causal softmax over s in S_t only, times v(s), W_o

  layers < first_k_dense_replace: SwiGLU of width intermediate_size
  the others: s = sigmoid(h W_r) over ALL `deployment.n_routed_experts`
  experts, u = s + b; a group (consecutive experts, n_group groups)
  scores the sum of its two largest u; the best topk_group groups
  stay; the top `num_experts_per_tok` of u inside them are chosen;
  weights s[chosen] / (sum + 1e-20) x routed_scaling_factor. THE SHARE:
  the file's `n_routed_experts` experts from `deployment.experts_first`
  on are held; a chosen expert that is not held adds nothing (its part
  of the sum is another chip's). Plus one SwiGLU of width
  n_shared_experts x moe_intermediate_size on every token.
  final RMSNorm, untied head over the file's `vocab_size` rows.

Departures from the published model, both stated in the configuration
file: the indexer runs in the weights' precision without the Hadamard
rotation that precedes its FP8 form (orthogonal: it cancels in
q_I . k_I); the multi-token-prediction module is left out (it does not
enter the main model's logits).

Weights are data made from a seed (`weights: "family_init"`): leaf i of
`leaf_recipe` is `truncated_normal(split(PRNGKey(seed), n)[i], -2, 2,
shape, float32) * scale`, cast to `torch_dtype`; norm weights ones, the
LayerNorm's bias zeros. They stay in that dtype on the device and are
upcast a layer, or one expert, at a time; at the published widths
they wait in host memory and a layer's leaves are on the device while
that layer runs (`to_host`); attention goes by blocks of queries, each
block's queries made inside it; the dense FFN by blocks of rows; the
head runs at the compared positions only.

    python3 benchmark/reference_dsv32.py <job.json>

Job and result are those of `reference_mla_moe.py`. `no_selection:
true` in the job attends every visible key instead: the proof that the
check sees the mechanism (the served tokens must then fail).
"""

from __future__ import annotations

import json
import math
import os
import sys
import time

QUERY_BLOCK = 64
ROW_BLOCK = 2048


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


def share(m: dict) -> tuple:
    """(experts the router scores, first held, held)."""
    dep = m.get("deployment") or {}
    held = m["n_routed_experts"]
    return (int(dep.get("n_routed_experts", held)),
            int(dep.get("experts_first", 0)), held)


def leaf_recipe(m: dict) -> list:
    """(name, shape, scale, dtype name) of every drawn leaf, in draw
    order. "dense." leaves stack the leading dense layers, "moe." the
    expert layers; the expert banks hold the share only."""
    d, h, v = m["hidden_size"], m["num_attention_heads"], m["vocab_size"]
    rank, nope = m["kv_lora_rank"], m["qk_nope_head_dim"]
    rope, vd = m["qk_rope_head_dim"], m["v_head_dim"]
    qr, hi, di = m["q_lora_rank"], m["index_n_heads"], m["index_head_dim"]
    kd = m["first_k_dense_replace"]
    km = m["num_hidden_layers"] - kd
    f, fd = m["moe_intermediate_size"], m["intermediate_size"]
    e, _, held = share(m)
    fs = m["n_shared_experts"] * f
    dt = m.get("torch_dtype", "bfloat16")
    attn = [
        ("wq_a", (d, qr), d**-0.5),
        ("wq_b", (qr, h * (nope + rope)), qr**-0.5),
        ("idx_wq", (qr, hi * di), qr**-0.5),
        ("idx_wk", (d, di), d**-0.5),
        ("idx_ww", (d, hi), d**-0.5),
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
        ("moe.w_gate", (km, held, d, f), d**-0.5, dt),
        ("moe.w_up", (km, held, d, f), d**-0.5, dt),
        ("moe.w_down", (km, held, f, d), f**-0.5, dt),
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


def layer_weights(m: dict, w: dict) -> list:
    """[(kind, {leaf name: that layer's array})] in layer order, each
    layer's leaves cut out of the stacked draw."""
    kd = m["first_k_dense_replace"]
    out = []
    for stack, n in (("dense", kd), ("moe", m["num_hidden_layers"] - kd)):
        out += [(stack, {k.split(".", 1)[1]: v[i] for k, v in w.items()
                         if k.startswith(stack + ".")}) for i in range(n)]
    return out


def to_host(jax, m: dict, w: dict) -> dict:
    """The drawn weights off the device: at the published widths they
    are 9.3 GB, and a 16k-token sequence's float32 keys, values and
    score blocks need the room. A layer's leaves come back one layer at
    a time (`hidden_states`). Embedding and head stay on the device."""
    import numpy as np

    host = {}
    for name in list(w):  # a leaf at a time; popped, the device's goes
        leaf = w.pop(name)
        host[name] = leaf if name in ("embed", "lm_head") else np.asarray(leaf)
    return {"embed": host["embed"], "lm_head": host["lm_head"],
            "layers": layer_weights(m, host)}


def yarn_inv_freq(jnp, m: dict):
    """[rope / 2] float32: the YaRN blend of f_i and f_i / factor."""
    rope, theta = m["qk_rope_head_dim"], float(m["rope_theta"])
    sc = m["rope_scaling"]
    assert sc["type"] == "yarn" and sc["mscale"] == sc["mscale_all_dim"]
    factor, orig = float(sc["factor"]), sc["original_max_position_embeddings"]
    freq = 1.0 / theta ** (jnp.arange(0, rope, 2, dtype=jnp.float32) / rope)

    def correction(rotations):
        return rope * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction(sc["beta_fast"])), 0)
    high = min(math.ceil(correction(sc["beta_slow"])), rope - 1)
    ramp = jnp.clip(
        (jnp.arange(rope // 2, dtype=jnp.float32) - low)
        / max(high - low, 1e-3), 0.0, 1.0)
    return freq / factor * ramp + freq * (1.0 - ramp)


def make_layers(jax, m: dict, select: bool = True):
    """(dense_layer, expert_layer, head): jitted, float32, one sequence
    [S, D] at a time, a layer's weights passed in their stored dtype.
    `select` False attends every visible key (the check's proof)."""
    jnp = jax.numpy
    f32 = jnp.float32
    h = m["num_attention_heads"]
    rank, nope = m["kv_lora_rank"], m["qk_nope_head_dim"]
    rope, vd = m["qk_rope_head_dim"], m["v_head_dim"]
    hi, di, topk = m["index_n_heads"], m["index_head_dim"], m["index_topk"]
    eps = float(m["rms_norm_eps"])
    top_k, scaling = m["num_experts_per_tok"], float(m["routed_scaling_factor"])
    n_group, topk_group = m["n_group"], m["topk_group"]
    e, first, held = share(m)
    assert m["scoring_func"] == "sigmoid" and m["norm_topk_prob"]
    assert e % n_group == 0 and first + held <= e
    inv_freq = yarn_inv_freq(jnp, m)
    gain = 0.1 * math.log(float(m["rope_scaling"]["factor"])) + 1.0
    softmax_scale = (nope + rope) ** -0.5 * gain * gain

    def rms(x):  # every norm weight of the recipe is one
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)

    def angles(x, pos):
        ang = pos.astype(f32)[:, None] * inv_freq[None, :]  # [S, rope/2]
        shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (rope // 2,)
        return jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)

    def rope_pairs(x, pos):  # x [S, ..., rope]: rotate (2i, 2i+1)
        cos, sin = angles(x, pos)
        x0, x1 = x[..., 0::2], x[..., 1::2]
        return jnp.stack(
            [x0 * cos - x1 * sin, x1 * cos + x0 * sin], axis=-1
        ).reshape(x.shape)

    def rope_halves(x, pos):  # x [S, ..., DI]: (i, i + rope/2) of [:rope]
        cos, sin = angles(x, pos)
        x0, x1 = x[..., : rope // 2], x[..., rope // 2: rope]
        return jnp.concatenate(
            [x0 * cos - x1 * sin, x1 * cos + x0 * sin, x[..., rope:]], axis=-1)

    def swiglu(x, gate, up, down):
        return (jax.nn.silu(x @ gate.astype(f32)) * (x @ up.astype(f32))) @ (
            down.astype(f32))

    def by_rows(fn, x):
        """`fn` over blocks of ROW_BLOCK rows of x [S, D]: bounds a
        wide layer's float32 intermediates ([S, 18432] three times)."""
        rows = x.shape[0]
        if rows <= ROW_BLOCK:
            return fn(x)
        pad = -rows % ROW_BLOCK
        xb = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, ROW_BLOCK, x.shape[1])
        return jax.lax.map(fn, xb).reshape(rows + pad, -1)[:rows]

    def attention(x, w):
        s = x.shape[0]
        pos = jnp.arange(s)
        n = rms(x)
        c_q = rms(n @ w["wq_a"].astype(f32))  # [S, q_lora_rank]
        a = n @ w["wkv_a"].astype(f32)
        c = rms(a[:, :rank])
        k_rope = rope_pairs(a[:, rank:], pos)  # [S, rope]
        kv = (c @ w["wkv_b"].astype(f32)).reshape(s, h, nope + vd)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        # the indexer's key, one a token
        k_i = n @ w["idx_wk"].astype(f32)
        k_i = k_i - k_i.mean(-1, keepdims=True)
        k_i = k_i * jax.lax.rsqrt((k_i * k_i).mean(-1, keepdims=True) + 1e-6)
        k_i = rope_halves(k_i, pos)  # LayerNorm weight one, bias zero
        pad = -s % QUERY_BLOCK

        def block(args):  # a block of queries, from c_q on
            cq, nq, qpos = args
            q = (cq @ w["wq_b"].astype(f32)).reshape(-1, h, nope + rope)
            qn, qr = q[..., :nope], rope_pairs(q[..., nope:], qpos)
            visible = pos[None, :] <= qpos[:, None]  # [QB, S]
            if select and s > topk:
                qi = rope_halves(
                    (cq @ w["idx_wq"].astype(f32)).reshape(-1, hi, di), qpos)
                wi = (nq @ w["idx_ww"].astype(f32)) * (hi**-0.5 * di**-0.5)
                index = (
                    jax.nn.relu(jnp.einsum("qhd,kd->qhk", qi, k_i))
                    * wi[:, :, None]).sum(1)  # [QB, S]
                index = jnp.where(visible, index, -jnp.inf)
                # a key's rank among the query's keys, largest first,
                # the lower position first among equals
                order = jnp.argsort(-index, axis=-1, stable=True)
                rank_of = jnp.argsort(order, axis=-1, stable=True)
                visible &= rank_of < topk
            scores = (
                jnp.einsum("qhd,khd->hqk", qn, k_nope)
                + jnp.einsum("qhr,kr->hqk", qr, k_rope)
            ) * softmax_scale
            scores = jnp.where(visible[None], scores, -jnp.inf)
            att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
            return att.reshape(-1, h * vd) @ w["wo"].astype(f32)

        def blocks(t):
            t = jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
            return t.reshape((s + pad) // QUERY_BLOCK, QUERY_BLOCK, *t.shape[1:])

        att = jax.lax.map(block, (blocks(c_q), blocks(n), blocks(pos)))
        return x + att.reshape(s + pad, -1)[:s]

    def dense_layer(x, w):
        x = attention(x, w)
        return x + by_rows(lambda r: swiglu(
            r, w["w_gate"], w["w_up"], w["w_down"]), rms(x))

    def route(n, w):
        """[S, E] float32: each token's weight on each of ALL experts."""
        scores = jax.nn.sigmoid(n @ w["router"].astype(f32))  # [S, E]
        u = scores + w["router_bias"]
        if n_group > 1:
            groups = u.reshape(-1, n_group, e // n_group)
            top2 = jnp.sort(groups, axis=-1)[..., -2:].sum(-1)  # [S, G]
            # the topk_group best groups, the lower group among equals
            order = jnp.argsort(-top2, axis=-1, stable=True)
            kept = jnp.argsort(order, axis=-1, stable=True) < topk_group
            u = jnp.where(kept[..., None], groups, -jnp.inf).reshape(u.shape)
        _, chosen = jax.lax.top_k(u, top_k)
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20) * scaling
        rows = jnp.arange(n.shape[0])[:, None]
        return jnp.zeros_like(scores).at[rows, chosen].set(picked)

    def expert_layer(x, w):
        x = attention(x, w)
        n = rms(x)
        weight = route(n, w)[:, first: first + held]  # the share's part

        def one(acc, ew):  # every held expert on every token, by weight
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
    sequence. `w` is the drawn weights, or `to_host`'s form of them."""
    jnp = jax.numpy
    fns = {"dense": layers[0], "moe": layers[1]}
    x = w["embed"][jnp.asarray(ids)].astype(jnp.float32)
    for kind, leaves in w.get("layers") or layer_weights(m, w):
        x = fns[kind](x, {k: jnp.asarray(v) for k, v in leaves.items()})
    return x


LONG_STEP = 2048


def padded_len(n: int) -> int:
    """A power of two up to 512, then whole steps of 2,048: the agent
    sessions' prefixes (8k-28k tokens) compile a handful of lengths."""
    if n <= 512:
        return max(32, 1 << (n - 1).bit_length())
    return -(-n // LONG_STEP) * LONG_STEP


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
    weights = to_host(jax, model, family_init_weights(jax, model))
    layers = make_layers(jax, model, select=not job.get("no_selection"))
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
        "selection": not job.get("no_selection"),
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
