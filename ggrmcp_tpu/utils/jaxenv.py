"""Where JAX runs and where it keeps compiled programs: the two rules
every entry point that touches JAX applies before it builds anything
(`init_runtime`).

Platform. The serving plane is written for a TPU. With libtpu installed
and no chip, JAX falls back to the CPU without a word, and a sidecar on
that fallback serves happily at a speed nobody deploys. So the sidecar,
the trainer and the bench scripts refuse to start on a non-TPU backend
unless the operator asked for the CPU with `JAX_PLATFORMS=cpu` (tests
and local verification do).

Compile cache. The warm-up ladder is most of a cold start at 7B, so a
second start must find the first one's programs. The cache lives where
`JAX_COMPILATION_CACHE_DIR` says when it is set — JAX reads that
variable itself, so nothing is set in code — and otherwise at
`<checkout>/.jax_cache`: a fixed path, because the path is part of the
cache key.
"""

from __future__ import annotations

import logging
import os

logger = logging.getLogger("ggrmcp.utils.jaxenv")

CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_COMPILE_CACHE = os.path.join(CHECKOUT, ".jax_cache")


def cpu_requested() -> bool:
    """True when the operator asked for the CPU backend by name."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def require_accelerator(what: str) -> None:
    """Refuse to run `what` on a non-TPU backend the operator did not
    ask for. Initializes the JAX backend."""
    import jax

    device = jax.devices()[0]
    if device.platform == "tpu" or cpu_requested():
        return
    raise RuntimeError(
        f"{what}: JAX found no TPU and fell back to "
        f"{device.platform!r} ({device.device_kind}). This program "
        f"refuses a non-TPU backend unless it is asked for: set "
        f"JAX_PLATFORMS=cpu to run on the CPU on purpose."
    )


def configure_compile_cache() -> str:
    """Place the persistent compile cache; returns the directory in
    use. With JAX_COMPILATION_CACHE_DIR set JAX already points there,
    so this sets nothing."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE)
    return DEFAULT_COMPILE_CACHE


def init_runtime(what: str) -> None:
    """Both rules, in the order that fails fastest: the platform check
    first (no point caching for a backend we refuse)."""
    require_accelerator(what)
    cache_dir = configure_compile_cache()
    logger.info("%s: compile cache at %s", what, cache_dir)
