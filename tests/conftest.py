"""Test configuration.

JAX tests run on a virtual 8-device CPU mesh (SURVEY.md §4 multi-node
story): the env vars must be set before jax is first imported anywhere.
"""

import os
import sys

# Tests ask for the CPU by name (the platform rule in utils/jaxenv.py
# refuses an unrequested CPU fallback) and run on a virtual 8-device
# CPU mesh, whatever the machine holds.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Persistent XLA compile cache for the suite: the tests build many
# batchers/engines whose device programs are byte-identical HLO
# (same tiny models, same shapes, same meshes) — the disk cache dedups
# those compiles within a run and across runs, which is what keeps the
# tier-1 wall clock inside its budget as the suite grows. Keyed on HLO,
# so it can never change a test's numerics. Same place as every entry
# point's default (utils/jaxenv.py); JAX_COMPILATION_CACHE_DIR in the
# environment overrides, and child processes the tests spawn inherit it.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ggrmcp_tpu.utils.jaxenv import DEFAULT_COMPILE_CACHE  # noqa: E402

os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", DEFAULT_COMPILE_CACHE)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")

import asyncio  # noqa: E402
import inspect  # noqa: E402

import pytest  # noqa: E402


def pytest_pyfunc_call(pyfuncitem):
    """Run `async def` tests via asyncio.run — pytest-asyncio is not
    available in this environment. Async fixtures are not supported;
    tests use async context managers for setup instead."""
    func = pyfuncitem.obj
    if inspect.iscoroutinefunction(func):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        asyncio.run(func(**kwargs))
        return True
    return None


@pytest.fixture(scope="session")
def testdata_dir():
    return os.path.join(os.path.dirname(os.path.abspath(__file__)), "testdata")


@pytest.fixture
def latent_prefill_on_tpu(monkeypatch):
    """What the latent-prefill dispatch (ops/attention.py
    `latent_prefill`) sees on the chip, here: the platform answers TPU
    and the kernel it then picks runs interpreted. The program has no
    option for it."""
    from ggrmcp_tpu.ops import attention

    compiled = attention.latent_prefill_attention
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(
        attention, "latent_prefill_attention",
        lambda *a, **kw: compiled(*a, **{**kw, "interpret": True}))
