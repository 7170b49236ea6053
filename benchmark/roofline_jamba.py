"""The bytes a decode step of `model_type: jamba` has to move, beside
`roofline_keye.py`. The yardstick for `ssm_step_roofline`; sizes are
read from the configuration file's keys, weights at `torch_dtype` width
(`A_log`, `D` and `dt_proj`'s bias float32).

A step reads every weight once (the embedding too: it is the head,
`tie_word_embeddings`); reads AND writes each decoding row's recurrent
state in every Mamba layer (the convolution's `mamba_d_conv - 1` last
inputs in the weights' dtype and `h` `[mamba_d_state, C]` float32, C =
`mamba_expand` x `hidden_size`); and reads the K and V of every live
token in the attention layers (`num_key_value_heads` x head values each,
layers i with i % `attn_layer_period` == `attn_layer_offset`).
"""

from __future__ import annotations

from benchmark.roofline import live_tokens_per_step  # noqa: F401
from benchmark.roofline_dsv32 import BYTES


def _width(model: dict) -> int:
    return BYTES[model.get("torch_dtype", "bfloat16")]


def layer_counts(model: dict) -> tuple:
    """(Mamba layers, attention layers)."""
    layers = model["num_hidden_layers"]
    attn = sum(1 for i in range(layers)
               if i % model["attn_layer_period"] == model["attn_layer_offset"])
    return layers - attn, attn


def channels(model: dict) -> int:
    return model["mamba_expand"] * model["hidden_size"]


def mamba_params(model: dict) -> tuple:
    """(values in the weights' dtype, float32 values) of one Mamba
    mixer: W_in, the convolution and its bias, W_x, the three inner
    norms, W_dt, W_out, the layer's norm; b_dt, A_log, D."""
    d, c, n = model["hidden_size"], channels(model), model["mamba_d_state"]
    r, k = model["mamba_dt_rank"], model["mamba_d_conv"]
    narrow = (d * 2 * c + k * c + c + c * (r + 2 * n) + (r + 2 * n)
              + r * c + c * d + d)
    return narrow, c + n * c + c


def attention_params(model: dict) -> int:
    """W_qkv, W_o and the layer's norm."""
    d, h = model["hidden_size"], model["num_attention_heads"]
    g, hd = model["num_key_value_heads"], d // h
    return d * (h + 2 * g) * hd + h * hd * d + d


def mlp_params(model: dict) -> int:
    """Gate, up, down and the norm before them."""
    return 3 * model["hidden_size"] * model["intermediate_size"] + (
        model["hidden_size"])


def weight_bytes(model: dict) -> float:
    lm, la = layer_counts(model)
    narrow, wide = mamba_params(model)
    d = model["hidden_size"]
    return (
        (lm * narrow + la * attention_params(model)
         + (lm + la) * mlp_params(model) + model["vocab_size"] * d + d
         ) * _width(model) + lm * wide * 4)


def state_bytes_per_row(model: dict) -> float:
    """One row's recurrent state over all Mamba layers."""
    lm, _ = layer_counts(model)
    c = channels(model)
    return lm * ((model["mamba_d_conv"] - 1) * c * _width(model)
                 + model["mamba_d_state"] * c * 4)


def kv_bytes_per_token(model: dict) -> float:
    """One token's K and V over the attention layers."""
    _, la = layer_counts(model)
    hd = model["hidden_size"] // model["num_attention_heads"]
    return la * 2 * model["num_key_value_heads"] * hd * _width(model)


def rows_per_step(calls: list, decode_steps: float) -> float:
    """Mean number of rows that decode in one step: a call that decoded
    n tokens was a row of n steps; counted, like the calls, by
    completion inside the window."""
    if decode_steps <= 0:
        return 0.0
    return sum(c.completion_tokens for c in calls) / decode_steps


def step_bytes(model: dict, rows: float, live_tokens: float) -> float:
    return (weight_bytes(model) + 2 * rows * state_bytes_per_row(model)
            + live_tokens * kv_bytes_per_token(model))


def step_floor_ms(model: dict, device_kind: str, rows: float,
                  live_tokens: float) -> float:
    """Bytes over the chip's peak bytes/s: a decode step at 32 rows is
    bound by memory bandwidth."""
    from benchmark import roofline

    return (step_bytes(model, rows, live_tokens)
            / roofline.peak(device_kind)["hbm_bytes_per_s"] * 1000.0)
