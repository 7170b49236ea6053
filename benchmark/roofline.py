"""Peaks of the chips and the bytes a decode step has to move. The
yardstick for `decode_step_roofline`; kept with the benchmark so that
no PR that claims a gain can change it.
"""

from __future__ import annotations

# Keyed by `device_kind` as JAX reports it. A kind that is not here is
# an error, never a default.
PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip",
    },
}


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peak figures for device kind {device_kind!r}; add it to "
            f"benchmark/roofline.py with its source"
        )
    return PEAKS[device_kind]


def _sizes(model: dict) -> tuple:
    d, layers = model["hidden_size"], model["num_hidden_layers"]
    h, kvh = model["num_attention_heads"], model["num_key_value_heads"]
    hd = model.get("head_dim") or d // h
    return d, layers, h, kvh, hd, model["intermediate_size"], model["vocab_size"]


def decode_weight_bytes(model: dict, chips: int = 1) -> float:
    """Bytes of weights one decode step reads on one chip: every layer
    matrix and the output head once (tensor-parallel shards them evenly
    over `chips`), the norms, and, for int8 weights, the bf16 scales.
    The embedding table is gathered (a few rows), not streamed, and is
    left out."""
    d, layers, h, kvh, hd, f, v = _sizes(model)
    qkv = (h + 2 * kvh) * hd
    matrix_elems = layers * (d * qkv + h * hd * d + 3 * d * f) + d * v
    scale_elems = layers * (qkv + d + 2 * f + d) + v
    norm_bytes = (2 * layers * d + d) * 2
    if model.get("weights") == "synthetic_int8" or model.get("quantize") == "int8":
        total = matrix_elems * 1 + scale_elems * 2
    else:
        total = matrix_elems * 2
    return total / chips + norm_bytes


def kv_bytes_per_token(model: dict, chips: int = 1) -> float:
    """bf16 K and V of one token over all layers, on one chip (KV heads
    shard over the tensor axis)."""
    _, layers, _, kvh, hd, _, _ = _sizes(model)
    return 2 * layers * kvh * hd * 2 / chips


def live_tokens_per_step(calls: list, decode_steps: float) -> float:
    """Mean number of cached tokens the decoding rows attend to in one
    step: a call with prompt p that decoded n tokens read
    sum_{i=1..n}(p + i) cached tokens over its life; counted, like the
    calls themselves, by completion inside the window."""
    reads = sum(
        len(c.prompt) * c.completion_tokens
        + c.completion_tokens * (c.completion_tokens + 1) / 2
        for c in calls
    )
    return reads / decode_steps if decode_steps > 0 else 0.0


def decode_step_floor_ms(model: dict, device_kind: str, live_tokens: float,
                         chips: int = 1) -> float:
    """The least time one decode step can take on this chip: a decode
    step at these batch sizes is bound by memory bandwidth, so bytes
    over peak bytes/s."""
    moved = decode_weight_bytes(model, chips) + live_tokens * kv_bytes_per_token(
        model, chips
    )
    return moved / peak(device_kind)["hbm_bytes_per_s"] * 1000.0
