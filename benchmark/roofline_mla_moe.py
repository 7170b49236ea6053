"""The bytes a decode step of the latent-attention + routed-experts
family has to move, beside `roofline.py` (whose bytes are llama's). The
yardstick for `mla_moe_step_roofline`; sizes are read from the
configuration file's published keys, weights at `torch_dtype` width.
"""

from __future__ import annotations

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _width(model: dict) -> int:
    return BYTES[model.get("torch_dtype", "bfloat16")]


def attention_params(model: dict) -> int:
    d, h = model["hidden_size"], model["num_attention_heads"]
    rank, rope = model["kv_lora_rank"], model["qk_rope_head_dim"]
    nope, vd = model["qk_nope_head_dim"], model["v_head_dim"]
    return (d * h * (nope + rope) + d * (rank + rope)
            + rank * h * (nope + vd) + h * vd * d)


def expert_bytes(model: dict) -> float:
    """One routed expert: gate, up and down."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"] * _width(model)


def fixed_weight_bytes(model: dict) -> float:
    """What every decode step reads whatever the routing: attention and
    norms of every layer, the dense layers' FFN, the shared experts and
    the router (float32) of every expert layer, the final norm and the
    head. The embedding is gathered (a few rows) and left out."""
    d, layers = model["hidden_size"], model["num_hidden_layers"]
    kd = model["first_k_dense_replace"]
    km = layers - kd
    w = _width(model)
    norms = layers * (2 * d + model["kv_lora_rank"]) + d
    shared = 3 * d * model["n_shared_experts"] * model["moe_intermediate_size"]
    router = d * model["n_routed_experts"] + model["n_routed_experts"]
    return (
        (layers * attention_params(model) + norms
         + kd * 3 * d * model["intermediate_size"] + km * shared
         + d * model["vocab_size"]) * w
        + km * router * 4
    )


def latent_bytes_per_token(model: dict) -> float:
    """The cached latent of one token over all layers: rank + rope
    values at the weights' width (what attention has to read; the
    cache's lane padding is not counted as required work)."""
    return (model["num_hidden_layers"]
            * (model["kv_lora_rank"] + model["qk_rope_head_dim"]) * _width(model))


def step_bytes(model: dict, experts_hit: float, live_tokens: float) -> float:
    """`experts_hit`: distinct routed experts a step reads, summed over
    its expert layers. `live_tokens`: cached tokens its rows attend."""
    return (fixed_weight_bytes(model) + experts_hit * expert_bytes(model)
            + live_tokens * latent_bytes_per_token(model))


def step_floor_ms(model: dict, device_kind: str, experts_hit: float,
                  live_tokens: float) -> float:
    """Bytes over the chip's peak bytes/s: a decode step at 16 rows is
    bound by memory bandwidth."""
    from benchmark import roofline

    return (step_bytes(model, experts_hit, live_tokens)
            / roofline.peak(device_kind)["hbm_bytes_per_s"] * 1000.0)
