"""The latent-prefill kernel compiled at the served widths for a v5e
that is described, not attached (the TPU's compiler is installed here):
what the interpreter cannot refuse, Mosaic can (a slab that does not
tile, a broadcast it has no lowering for, more VMEM than a kernel may
use). Nothing runs, and no time or result is read off it.

The topology is described inside a fixture, in this one file: only the
worker that is given the file loads the TPU's library, and every worker
collects the same tests (see the on-chip-measurement guide, section 2).
Marker `paged` (tier-1)."""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from ggrmcp_tpu.core.config import MeshConfig
from ggrmcp_tpu.ops import attention as A
from ggrmcp_tpu.parallel import mesh as mesh_mod

pytestmark = pytest.mark.paged


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no compiler for it in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def uncached():
    """A program compiled for a described chip is written to the
    persistent cache and cannot be read back without one (a warning a
    compile): off around these."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def operands(place, heads, s_max, selected, rows=1, layers=5):
    """Shapes of one chunk of 512 queries at the published latent
    widths (a 640-wide plane, 512-wide values), bf16."""
    def shape(dims, dtype, spec):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=place(spec))

    row = P(("data", "fsdp"))
    args = [
        shape((rows, 512, heads, 640), jnp.bfloat16,
              P(("data", "fsdp"), None, "tensor", None)),
        shape((layers, rows, s_max, 640), jnp.bfloat16,
              P(None, ("data", "fsdp"), None, None)),
        shape((), jnp.int32, P()),
        shape((rows,), jnp.int32, row), shape((rows,), jnp.int32, row),
        shape((rows,), jnp.int32, row),
    ]
    if selected:
        args.append(shape((rows, 512, s_max), jnp.bool_, row))
    return args


CHUNKS = {
    # the kanana cell's chunk; the agent-longctx cell's, 4 queries a tile
    "heads_32": dict(heads=32, s_max=16384, selected=False),
    "heads_32_selected": dict(heads=32, s_max=16384, selected=True),
    "heads_128_selected": dict(heads=128, s_max=32768, selected=True),
}


@pytest.mark.parametrize("case", CHUNKS, ids=list(CHUNKS))
def test_the_latent_prefill_kernel_compiles_for_a_v5e(topo, uncached, case):
    one_chip = SingleDeviceSharding(topo.devices[0])
    compiled = jax.jit(functools.partial(
        A.latent_prefill_attention, value_width=512, scale=0.07,
    )).lower(*operands(lambda spec: one_chip, **CHUNKS[case])).compile()
    assert "latent_attention_prefill" in compiled.as_text()


def test_per_shard_with_a_selection_compiles_for_a_2x2(topo, uncached):
    """Rows over `data`, 128 heads over `tensor`: 64 heads a shard, 8
    queries a tile, the selection whole for every shard of heads. No
    `deepseek_v32` member is served on a mesh (`engine._UNSUPPORTED`):
    compiled here, never run."""
    mesh = mesh_mod.build_mesh(MeshConfig(data=2, tensor=2), topo.devices)
    compiled = jax.jit(lambda *a: A.latent_prefill_attention_sharded(
        *a[:6], mesh, *a[6:], value_width=512, scale=0.07,
    )).lower(*operands(
        lambda spec: NamedSharding(mesh, spec), heads=128, s_max=32768,
        selected=True, rows=2,
    )).compile()
    assert "latent_attention_prefill" in compiled.as_text()
