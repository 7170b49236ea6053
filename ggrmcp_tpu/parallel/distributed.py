"""Multi-host runtime initialization (DCN / multi-slice scale-out).

The reference's distributed story was a single gRPC channel
(SURVEY.md §5.8); the TPU-native story has three tiers:

1. intra-slice: ICI collectives, implicit in pjit/shard_map — nothing
   to initialize, the mesh covers it;
2. inter-host within a multi-host deployment: the JAX multi-controller
   runtime (`jax.distributed.initialize`) — wrapped here with env-based
   autodetection so every host runs the same command;
3. gateway ↔ TPU hosts: plain gRPC over DCN via the discoverer's
   backend pool (rpc/discovery.py).

Each host runs its own sidecar; the gateway pools them. For SPMD
programs spanning hosts, `global_mesh()` builds the mesh over ALL
processes' devices.
"""

from __future__ import annotations

import logging
import os
from typing import Optional, Sequence

import jax

from ggrmcp_tpu.core.config import MeshConfig
from ggrmcp_tpu.parallel import mesh as mesh_mod

logger = logging.getLogger("ggrmcp.parallel.distributed")


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> bool:
    """Join the JAX multi-controller runtime.

    Arguments fall back to GGRMCP_COORDINATOR / GGRMCP_NUM_PROCESSES /
    GGRMCP_PROCESS_ID, then to JAX's own autodetection (TPU metadata on
    Cloud TPU VMs). Returns True if a multi-process runtime was
    initialized, False for single-process operation.
    """
    coordinator_address = coordinator_address or os.environ.get(
        "GGRMCP_COORDINATOR"
    )
    env_np = os.environ.get("GGRMCP_NUM_PROCESSES")
    env_pid = os.environ.get("GGRMCP_PROCESS_ID")
    num_processes = num_processes if num_processes is not None else (
        int(env_np) if env_np else None
    )
    process_id = process_id if process_id is not None else (
        int(env_pid) if env_pid else None
    )
    if coordinator_address is None and num_processes is None:
        logger.info("single-process runtime (no coordinator configured)")
        return False
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    # After, not before: the backend must come up inside the
    # multi-controller runtime, and the platform rule initializes it.
    from ggrmcp_tpu.utils.jaxenv import require_accelerator

    require_accelerator("multi-host runtime")
    logger.info(
        "joined multi-controller runtime: process %d/%d, %d local + %d global devices",
        jax.process_index(), jax.process_count(),
        jax.local_device_count(), jax.device_count(),
    )
    return True


def global_mesh(
    cfg: Optional[MeshConfig] = None,
) -> "jax.sharding.Mesh":
    """Mesh over every device in the (possibly multi-process) runtime.

    Axis layout follows mesh.AXES; sizing uses the global device count,
    so e.g. tensor=8 on a 2-host v5e-16 puts TP inside each slice (ICI)
    and the inferred data axis across hosts (DCN) — the bandwidth-
    correct default per the scaling-book recipe.
    """
    return mesh_mod.build_mesh(cfg, jax.devices())
