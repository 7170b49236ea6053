"""The bytes a decode step of `model_name: smallthinker_*` has to move,
beside `roofline_keye.py`. The yardstick for `window_step_roofline`;
sizes are read from the configuration file's keys, weights at
`torch_dtype` width.

A step reads: the weights that do not depend on routing (attention,
the norms, each layer's float32 router, the head over the whole
vocabulary); the experts it hit; and, for each decoding row, its K and
V: the whole context in a full layer (`sliding_window_layout` 0),
min(context, `sliding_window_size`) keys in a window layer. What a
window layer's cache KEEPS behind the window is not required work and
is not counted, whatever implements the step.
"""

from __future__ import annotations

from benchmark.roofline_dsv32 import BYTES


def _width(model: dict) -> int:
    return BYTES[model.get("torch_dtype", "bfloat16")]


def layer_counts(model: dict) -> tuple:
    """(full layers, window layers) of the layers that are served."""
    layout = model["sliding_window_layout"][: model["num_hidden_layers"]]
    return layout.count(0), layout.count(1)


def attention_params(model: dict) -> int:
    """W_q, W_k, W_v, W_o."""
    d, h = model["hidden_size"], model["num_attention_heads"]
    g, hd = model["num_key_value_heads"], model["head_dim"]
    return d * h * hd + 2 * d * g * hd + h * hd * d


def expert_bytes(model: dict) -> float:
    """One routed expert: gate, up and down."""
    return 3 * model["hidden_size"] * model["moe_ffn_hidden_size"] * _width(model)


def fixed_weight_bytes(model: dict) -> float:
    """What every decode step reads whatever the routing. The router is
    float32; the embedding is gathered (a few rows) and left out."""
    d, layers = model["hidden_size"], model["num_hidden_layers"]
    norms = layers * 2 * d + d
    return (
        (layers * attention_params(model) + norms + d * model["vocab_size"])
        * _width(model)
        + layers * d * model["moe_num_primary_experts"] * 4
    )


def kv_bytes_per_key(model: dict) -> float:
    """One key's K and V in ONE layer."""
    return 2 * model["num_key_value_heads"] * model["head_dim"] * _width(model)


def keys_per_step(calls: list, decode_steps: float, window: int) -> tuple:
    """(context, windowed): mean over the window's decode steps of the
    keys the decoding rows hold in context, and of min(context,
    `window`) a row. A call with prompt p that decoded n tokens saw
    p + i keys at its i-th step; counted, like the calls, by completion
    inside the window (`roofline_dsv32.tokens_per_step`'s rule)."""
    context = windowed = 0
    for c in calls:
        p, n = len(c.prompt), c.completion_tokens
        context += p * n + n * (n + 1) // 2
        windowed += sum(min(p + i, window) for i in range(1, n + 1))
    if decode_steps <= 0:
        return 0.0, 0.0
    return context / decode_steps, windowed / decode_steps


def step_bytes(model: dict, experts_hit: float, context: float,
               windowed: float) -> float:
    """`experts_hit`: distinct experts a step reads, summed over its
    layers; `context` / `windowed`: `keys_per_step`."""
    full, win = layer_counts(model)
    return (fixed_weight_bytes(model) + experts_hit * expert_bytes(model)
            + (full * context + win * windowed) * kv_bytes_per_key(model))


def step_floor_ms(model: dict, device_kind: str, experts_hit: float,
                  context: float, windowed: float) -> float:
    """Bytes over the chip's peak bytes/s: a decode step at 32 rows is
    bound by memory bandwidth."""
    from benchmark import roofline

    return (step_bytes(model, experts_hit, context, windowed)
            / roofline.peak(device_kind)["hbm_bytes_per_s"] * 1000.0)
