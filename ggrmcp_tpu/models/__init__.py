"""Model registry: name → (family, config).

The serving sidecar resolves `ServingConfig.model` here. Families:
"llama" (dense generation), "moe" (Mixtral-style sparse-MoE generation),
"mla_moe" (latent attention + sigmoid-routed and shared experts; its
`deepseek_v32` members add q-compression and a sparse-attention indexer
whose key rides the page's second plane), "keye" (GQA K and V per head +
the same indexer with its key as a THIRD plane of every page +
softmax-routed experts, every layer an expert layer), "jamba" (runs of
Mamba-1 state-space layers around a few attention layers without
rotary: only those cache K/V, and every row keeps a recurrent state in
a pool beside the pages), "smallthinker" (one full-attention layer
without positional encoding to three sliding-window layers with RoPE,
each kind with an arena and a block table of its own, and ReGLU experts
routed from the attention block's input), all six served by the same
engine, and "bert" (embeddings).
"""

from __future__ import annotations

from typing import Any

from ggrmcp_tpu.models import (
    bert,
    jamba,
    keye,
    llama,
    mla_moe,
    moe,
    smallthinker,
)

_FAMILIES = {
    "llama": llama, "moe": moe, "mla_moe": mla_moe, "keye": keye,
    "jamba": jamba, "smallthinker": smallthinker, "bert": bert,
}


_BY_CONFIG_MODULE = {
    m.__name__: m for n, m in _FAMILIES.items() if n != "bert"}


def get_model(name: str) -> tuple[str, Any]:
    for family, module in _FAMILIES.items():
        if name in module.CONFIGS:
            return family, module.CONFIGS[name]
    raise KeyError(
        f"unknown model {name!r}; available: {available_models()}"
    )


def available_models() -> list[str]:
    return sorted(n for m in _FAMILIES.values() for n in m.CONFIGS)


def family_module(cfg):
    """The decoder family module implementing the shared init_params /
    param_specs / forward / cache_specs contract for `cfg`: the module
    that defines the config's class, or the nearest class above it
    that a family defines (a LlamaConfig subclass of a test is the
    dense family's). Single dispatch point — engines, trainers and the
    pipeline all resolve the family here."""
    for cls in type(cfg).__mro__:
        module = _BY_CONFIG_MODULE.get(cls.__module__)
        if module is not None:
            return module
    return llama


def family_name(cfg) -> str:
    module = family_module(cfg)
    return next(n for n, m in _FAMILIES.items() if m is module)
