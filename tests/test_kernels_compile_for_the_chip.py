"""The latent-prefill kernel, the dense family's prefill kernel and the
grouped-experts kernel compiled at the served widths for a v5e that is
described, not attached (the TPU's compiler is installed here):
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


# The dense family's prefill kernel at the two shapes the benchmark's
# cells reach: a chunk of the `[8, 4, 512]` admission grid over its
# 2,048-position mini cache (mistral's window rides along and cuts
# nothing there), and one row of 256 tokens, fresh or the suffix of a
# reused prefix in a mini cache of the full width.
FLASH_CALLS = {
    "8_rows_512_on_2048_window_4096": dict(
        rows=8, sq=512, sk=2048, window=4096),
    "1_row_256_on_2048_window_4096": dict(
        rows=1, sq=256, sk=2048, window=4096),
    "1_row_256_fresh": dict(rows=1, sq=256, sk=256, window=None),
    # the hybrid family's two attention layers: 20 query heads on ONE
    # KV head (a query group of 20), a 512-token chunk and a 128-token
    # suffix over the 8,192-position mini cache
    "1_row_512_on_8192_group_20": dict(
        rows=1, sq=512, sk=8192, window=None, heads=20, kv_heads=1),
    "4_rows_128_on_8192_group_20": dict(
        rows=4, sq=128, sk=8192, window=None, heads=20, kv_heads=1),
    # the two kinds of layer of models/smallthinker.py: 28 query heads
    # on 4 KV heads (a group of 7), a 512-token chunk and a 256-token
    # suffix over the 16,384-position mini cache, with the window that
    # binds there and without one
    "1_row_512_on_16384_window_4096_group_7": dict(
        rows=1, sq=512, sk=16384, window=4096, heads=28, kv_heads=4),
    "1_row_512_on_16384_group_7": dict(
        rows=1, sq=512, sk=16384, window=None, heads=28, kv_heads=4),
    "1_row_256_on_16384_window_4096_group_7": dict(
        rows=1, sq=256, sk=16384, window=4096, heads=28, kv_heads=4),
}


@pytest.mark.parametrize("case", FLASH_CALLS, ids=list(FLASH_CALLS))
def test_the_flash_kernel_compiles_for_a_v5e(topo, uncached, case):
    call = {"heads": 32, "kv_heads": 8, **FLASH_CALLS[case]}
    rows, sq, sk, window = (call[k] for k in ("rows", "sq", "sk", "window"))
    heads, kv_heads = call["heads"], call["kv_heads"]
    one_chip = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = jax.jit(
        functools.partial(A.flash_attention, causal=True, window=window)
    ).lower(
        shape((rows, sq, heads, 128)), shape((rows, sk, kv_heads, 128)),
        shape((rows, sk, kv_heads, 128)),
        q_offset=shape((rows,), jnp.int32), kv_len=shape((rows,), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


# The grouped-experts kernel (ops/experts.py) at the served widths:
# (hidden, intermediate, experts held, layers stacked) x the pairs of a
# decode step, a short suffix and a 512-token chunk; `loop_block` is
# what `mla_moe._task_block` gives the XLA loop there, the kernel's own
# row tile that or `experts.MIN_ROWS`.
EXPERT_WIDTHS = {
    "kanana_keye": dict(d=2048, f=768, held=128, experts=128, layers=6),
    "dsv32": dict(d=7168, f=2048, held=16, experts=256, layers=4),
    # ReGLU (the gate's activation is the caller's): 11.8 MB an expert,
    # the largest the whole-expert slot has held
    "smallthinker": dict(
        d=2560, f=768, held=64, experts=64, layers=8, act="relu"),
}
EXPERT_CALLS = {
    "kanana_keye-64_pairs": ("kanana_keye", 64, 8),
    "kanana_keye-96_pairs": ("kanana_keye", 96, 8),
    "kanana_keye-3072_pairs": ("kanana_keye", 3072, 32),
    "kanana_keye-4096_pairs": ("kanana_keye", 4096, 32),
    "dsv32-64_pairs": ("dsv32", 64, 8),
    "dsv32-4096_pairs": ("dsv32", 4096, 16),
    # float32 tokens and banks are the chooser's kind too: half an
    # expert a slot (two tiles of F)
    "kanana_keye-64_pairs-float32": ("kanana_keye", 64, 8, jnp.float32),
    "kanana_keye-3072_pairs-float32": ("kanana_keye", 3072, 32, jnp.float32),
    # 32 decoding rows x 6, a 128-token suffix, a 512-token chunk
    "smallthinker-192_pairs": ("smallthinker", 192, 8),
    "smallthinker-768_pairs": ("smallthinker", 768, 16),
    "smallthinker-3072_pairs": ("smallthinker", 3072, 64),
}


@pytest.mark.parametrize("case", EXPERT_CALLS, ids=list(EXPERT_CALLS))
def test_the_grouped_experts_kernel_compiles_for_a_v5e(topo, uncached, case):
    from ggrmcp_tpu.models import mla_moe
    from ggrmcp_tpu.ops import experts

    widths, pairs, loop_block, *dtype = EXPERT_CALLS[case]
    dtype = dtype[0] if dtype else jnp.bfloat16
    sizes = dict(EXPERT_WIDTHS[widths])
    act = sizes.pop("act", "silu")
    d, f, held, n_experts, layers = sizes.values()
    if widths == "smallthinker":  # an expert whole, just inside a slot
        assert experts._f_tile(d, f, 2) == f
        assert 3 * d * f * 2 <= experts._SLOT_BYTES < 3 * d * (f + 128) * 2
    assert mla_moe._task_block(pairs, n_experts) == loop_block
    tile = max(loop_block, experts.MIN_ROWS)
    max_tasks = min(pairs // tile + held, pairs)
    one_chip = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype=dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = jax.jit(
        functools.partial(experts.grouped_swiglu, block=tile, act=act)
    ).lower(
        shape((max_tasks * tile, d)), shape((layers, held, d, f)),
        shape((layers, held, d, f)), shape((layers, held, f, d)),
        shape((), jnp.int32), shape((), jnp.int32),
        shape((max_tasks,), jnp.int32), shape((max_tasks,), jnp.int32),
    ).compile()
    assert "grouped_experts_swiglu" in compiled.as_text()


# The paged-decode kernel at the two arenas of models/smallthinker.py, a
# tick of 32 rows: the full layers' walk over every page of a row, and
# the window layers' over a table whose entries behind the window are
# unmapped (the walk starts at the window's first block and the row's
# liveness is read off the page of its newest key).
PAGED_DECODE_CALLS = {
    "full_2_layers_1024_pages_a_row": dict(
        layers=2, pages=32 * 1024, window=None),
    "window_6_layers_292_pages_a_row": dict(
        layers=6, pages=32 * 292, window=4096),
}


@pytest.mark.parametrize(
    "case", PAGED_DECODE_CALLS, ids=list(PAGED_DECODE_CALLS))
def test_the_paged_decode_kernel_compiles_for_a_v5e(topo, uncached, case):
    layers, pages, window = PAGED_DECODE_CALLS[case].values()
    one_chip = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    arena = shape((layers, pages, 16, 4, 128))
    compiled = jax.jit(
        functools.partial(A.paged_decode_attention, window=window)
    ).lower(
        shape((32, 1, 28, 128)), arena, arena, shape((32, 1024), jnp.int32),
        shape((32,), jnp.int32), shape((), jnp.int32),
    ).compile()
    assert "paged_decode_attention" in compiled.as_text()
