"""The bytes a decode step of the `deepseek_v32` member has to move,
beside `roofline_mla_moe.py` (whose step reads every live latent). The
yardstick for `sparse_mla_step_roofline`; sizes are read from the
configuration file's keys, weights at `torch_dtype` width.

A step reads: the weights that do not depend on routing (attention
with its low-rank query path, the indexer, the norms, the dense layers'
FFN, each expert layer's router and shared expert, the head over the
vocabulary slice); the held experts it hit; and, for each live row in
each layer, the row's indexer keys (index_head_dim values a token: the
selection has to score every visible key) and the latents it selected
(at most index_topk of rank + rope values).
"""

from __future__ import annotations

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _width(model: dict) -> int:
    return BYTES[model.get("torch_dtype", "bfloat16")]


def attention_params(model: dict) -> int:
    """W_qa, W_qb, W_kva, W_kvb, W_o."""
    d, h = model["hidden_size"], model["num_attention_heads"]
    rank, rope = model["kv_lora_rank"], model["qk_rope_head_dim"]
    nope, vd, qr = model["qk_nope_head_dim"], model["v_head_dim"], model["q_lora_rank"]
    return (d * qr + qr * h * (nope + rope) + d * (rank + rope)
            + rank * h * (nope + vd) + h * vd * d)


def indexer_params(model: dict) -> int:
    """W_Iq, W_Ik, W_Iw."""
    d, qr = model["hidden_size"], model["q_lora_rank"]
    hi, di = model["index_n_heads"], model["index_head_dim"]
    return qr * hi * di + d * di + d * hi


def expert_bytes(model: dict) -> float:
    """One routed expert: gate, up and down."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"] * _width(model)


def fixed_weight_bytes(model: dict) -> float:
    """What every decode step reads whatever the routing. The router
    scores all of the deployment's experts (float32); the embedding is
    gathered (a few rows) and left out."""
    d, layers = model["hidden_size"], model["num_hidden_layers"]
    kd = model["first_k_dense_replace"]
    km = layers - kd
    experts = (model.get("deployment") or {}).get(
        "n_routed_experts", model["n_routed_experts"])
    norms = layers * (2 * d + model["kv_lora_rank"] + model["q_lora_rank"]
                      + 2 * model["index_head_dim"]) + d
    shared = 3 * d * model["n_shared_experts"] * model["moe_intermediate_size"]
    return (
        (layers * (attention_params(model) + indexer_params(model)) + norms
         + kd * 3 * d * model["intermediate_size"] + km * shared
         + d * model["vocab_size"]) * _width(model)
        + km * (d * experts + experts) * 4
    )


def index_key_bytes_per_token(model: dict) -> float:
    """One token's indexer keys over all layers."""
    return model["num_hidden_layers"] * model["index_head_dim"] * _width(model)


def latent_bytes_per_token(model: dict) -> float:
    """One selected token's latent over all layers: rank + rope values
    (the plane's lane padding is not required work)."""
    return (model["num_hidden_layers"]
            * (model["kv_lora_rank"] + model["qk_rope_head_dim"]) * _width(model))


def tokens_per_step(calls: list, decode_steps: float, topk: int) -> tuple:
    """(visible, selected): mean over the window's decode steps of the
    cached tokens the decoding rows could see, and of those they
    attend (at most `topk` a row). A call with prompt p that decoded n
    tokens saw p + i keys at its i-th step; counted, like the calls, by
    completion inside the window."""
    visible = selected = 0
    for c in calls:
        p, n = len(c.prompt), c.completion_tokens
        visible += p * n + n * (n + 1) // 2
        selected += sum(min(p + i, topk) for i in range(1, n + 1))
    if decode_steps <= 0:
        return 0.0, 0.0
    return visible / decode_steps, selected / decode_steps


def step_bytes(model: dict, experts_hit: float, visible: float,
               selected: float) -> float:
    """`experts_hit`: distinct held experts a step reads, summed over
    its expert layers."""
    return (fixed_weight_bytes(model) + experts_hit * expert_bytes(model)
            + visible * index_key_bytes_per_token(model)
            + selected * latent_bytes_per_token(model))


def step_floor_ms(model: dict, device_kind: str, experts_hit: float,
                  visible: float, selected: float) -> float:
    """Bytes over the chip's peak bytes/s: a decode step at 8 rows is
    bound by memory bandwidth."""
    from benchmark import roofline

    return (step_bytes(model, experts_hit, visible, selected)
            / roofline.peak(device_kind)["hbm_bytes_per_s"] * 1000.0)
