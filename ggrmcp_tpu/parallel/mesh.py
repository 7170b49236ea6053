"""Device mesh construction and sharding helpers.

The TPU-native substrate for the serving plane (SURVEY.md §2.4, §5.8):
a named `jax.sharding.Mesh` over the available devices with the
scaling-book axis vocabulary — data / fsdp / tensor / sequence /
expert / stage — and `NamedSharding` helpers the models use to place
parameters and activations. Collectives are never hand-rolled: layouts
are annotated and XLA inserts the ICI collectives.

No reference analogue: the Go gateway had no model execution; its
"distributed backend" was one gRPC channel (pkg/grpc/connection.go).
"""

from __future__ import annotations

import logging
import math
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ggrmcp_tpu.core.config import MeshConfig

logger = logging.getLogger("ggrmcp.parallel.mesh")

# Canonical axis order. Axes of size 1 are still present in the mesh —
# XLA treats them as free, and specs stay stable across topologies.
AXES = ("data", "fsdp", "tensor", "sequence", "expert", "stage")


def resolve_axis_sizes(
    cfg: MeshConfig, n_devices: Optional[int] = None
) -> dict[str, int]:
    """Fill in zero ("infer") axes so the product equals n_devices."""
    n = n_devices if n_devices is not None else len(jax.devices())
    sizes = {
        "data": cfg.data,
        "fsdp": cfg.fsdp,
        "tensor": cfg.tensor,
        "sequence": cfg.sequence,
        "expert": cfg.expert,
        "stage": cfg.stage,
    }
    fixed = math.prod(v for v in sizes.values() if v > 0)
    free = [k for k, v in sizes.items() if v == 0]
    if n % max(fixed, 1) != 0:
        raise ValueError(
            f"device count {n} not divisible by fixed axis product {fixed}"
        )
    remaining = n // max(fixed, 1)
    if not free:
        if fixed != n:
            raise ValueError(
                f"axis product {fixed} != device count {n}; set one axis "
                f"to 0 to infer it"
            )
    else:
        # First free axis soaks up the remainder; the rest get 1.
        sizes[free[0]] = remaining
        for k in free[1:]:
            sizes[k] = 1
    return sizes


def build_mesh(
    cfg: Optional[MeshConfig] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build the named mesh over `devices` (default: all). A config
    whose axes are all fixed and multiply to FEWER devices than the
    host holds takes the first N and says which — how a four-chip host
    is asked for one chip (`tensor: 1`). An inferred axis (0) still
    soaks up every device."""
    cfg = cfg or MeshConfig()
    devs = list(devices) if devices is not None else list(jax.devices())
    want = math.prod(
        getattr(cfg, a) for a in AXES
    )  # 0 when any axis is inferred
    if 0 < want < len(devs):
        logger.info(
            "mesh: config asks for %d of %d visible device(s); using "
            "ids %s", want, len(devs), [d.id for d in devs[:want]],
        )
        devs = devs[:want]
    sizes = resolve_axis_sizes(cfg, len(devs))
    shape = tuple(sizes[a] for a in AXES)
    arr = np.array(devs).reshape(shape)
    mesh = Mesh(arr, AXES)
    logger.info(
        "mesh: %s over %d %s device(s)",
        {a: s for a, s in zip(AXES, shape) if s > 1} or {"(single)": 1},
        len(devs),
        devs[0].platform,
    )
    return mesh


# ---------------------------------------------------------------------------
# Sharding helpers
# ---------------------------------------------------------------------------


def named(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_batch(mesh: Mesh) -> NamedSharding:
    """Batch-dim sharding over the data axis (DP)."""
    return NamedSharding(mesh, P(("data", "fsdp")))


def axis_size(mesh: Mesh, name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape))[name]


def with_sharding_constraint(x, mesh: Mesh, *spec):
    """Annotate an intermediate's layout inside jit."""
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def compatible_spec(
    spec: P, shape: tuple[int, ...], mesh: Mesh, on_downgrade=None
) -> P:
    """Drop spec axes whose mesh size doesn't divide the corresponding
    array dimension (e.g. batch=1 over data=2 → replicate that dim).
    Keeps small-shape paths (streaming batch 1, tiny tests) runnable on
    big meshes without special-casing every call site.

    `on_downgrade(dim_index, entry, dim_size, axis_size)` is invoked for
    every REAL downgrade — a named axis of product > 1 replaced by
    replication. Silent downgrades are how a replicated-weights fallback
    masquerades as tensor-parallel serving: the engine threads a counter
    through here so every drop is logged at init and exported as the
    `mesh_spec_downgrades` gauge (docs/tensor_parallel_serving.md)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def axis_product(entry) -> int:
        if entry is None:
            return 1
        names = entry if isinstance(entry, tuple) else (entry,)
        return math.prod(sizes.get(n, 1) for n in names)

    out = []
    for i, (dim, entry) in enumerate(
        zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec)))
    ):
        product = max(axis_product(entry), 1)
        if dim % product == 0:
            out.append(entry)
        else:
            # product > 1 here by construction (dim % 1 == 0 always),
            # so every drop is a genuine sharding loss.
            if on_downgrade is not None:
                on_downgrade(i, entry, dim, product)
            out.append(None)
    return P(*out)


def mesh_shape_str(mesh: Mesh) -> str:
    """Human-readable mesh shape ("tensor=8", "data=2,tensor=4", or
    "single" for one device) — the ServingStats `mesh_shape` label and
    the bench artifact's mesh field."""
    parts = [
        f"{a}={s}" for a, s in zip(mesh.axis_names, mesh.devices.shape)
        if s > 1
    ]
    return ",".join(parts) or "single"
