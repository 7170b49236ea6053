"""The bytes a decode step of `model_type: KeyeVL2` has to move, beside
`roofline_dsv32.py`. The yardstick for `sparse_gqa_step_roofline`; sizes
are read from the configuration file's keys, weights at `torch_dtype`
width.

A step reads: the weights that do not depend on routing (attention, the
indexer, the norms, each layer's router, the head over the whole
vocabulary); the experts it hit; and, for each live row in each layer,
the row's indexer keys (`indexer_head_dim` values a token: the
selection has to score every visible key) and the K and V of the
tokens it selected (at most `topk`, `num_key_value_heads` x `head_dim`
values each of K and of V).
"""

from __future__ import annotations

from benchmark.roofline_dsv32 import BYTES, tokens_per_step  # noqa: F401


def _width(model: dict) -> int:
    return BYTES[model.get("torch_dtype", "bfloat16")]


def attention_params(model: dict) -> int:
    """W_q, W_k, W_v, W_o."""
    d, h = model["hidden_size"], model["num_attention_heads"]
    g, hd = model["num_key_value_heads"], model["head_dim"]
    return d * h * hd + 2 * d * g * hd + h * hd * d


def indexer_params(model: dict) -> int:
    """W_qI, W_kI, W_w."""
    d, sa = model["hidden_size"], model["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return d * hi * di + d * di + d * hi


def expert_bytes(model: dict) -> float:
    """One routed expert: gate, up and down."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"] * _width(model)


def fixed_weight_bytes(model: dict) -> float:
    """What every decode step reads whatever the routing. The router is
    float32; the embedding is gathered (a few rows) and left out."""
    d, layers = model["hidden_size"], model["num_hidden_layers"]
    norms = layers * (2 * d + 2 * model["head_dim"]
                      + 2 * model["sa_config"]["indexer_head_dim"]) + d
    return (
        (layers * (attention_params(model) + indexer_params(model)) + norms
         + d * model["vocab_size"]) * _width(model)
        + layers * d * model["num_experts"] * 4
    )


def index_key_bytes_per_token(model: dict) -> float:
    """One token's indexer keys over all layers."""
    return (model["num_hidden_layers"] * model["sa_config"]["indexer_head_dim"]
            * _width(model))


def kv_bytes_per_token(model: dict) -> float:
    """One selected token's K and V over all layers."""
    return (model["num_hidden_layers"] * 2 * model["num_key_value_heads"]
            * model["head_dim"] * _width(model))


def step_bytes(model: dict, experts_hit: float, visible: float,
               selected: float) -> float:
    """`experts_hit`: distinct experts a step reads, summed over its
    layers; `visible` / `selected`: `tokens_per_step`."""
    return (fixed_weight_bytes(model) + experts_hit * expert_bytes(model)
            + visible * index_key_bytes_per_token(model)
            + selected * kv_bytes_per_token(model))


def step_floor_ms(model: dict, device_kind: str, experts_hit: float,
                  visible: float, selected: float) -> float:
    """Bytes over the chip's peak bytes/s: a decode step at 8 rows is
    bound by memory bandwidth."""
    from benchmark import roofline

    return (step_bytes(model, experts_hit, visible, selected)
            / roofline.peak(device_kind)["hbm_bytes_per_s"] * 1000.0)
