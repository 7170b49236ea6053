"""TPU co-launch mode: gateway + sidecar in one process tree.

The north star's `cmd/grmcp --tpu` (BASELINE.json): the gateway
co-launches a JAX serving sidecar, waits for it to come up, and
registers it through the ordinary Service Discoverer — from the MCP
client's perspective it is just another discovered gRPC backend.

The sidecar is SUPERVISED, not merely co-launched (the PR 12 fix): the
original `_run` only stopped the sidecar when the gateway exited, so a
sidecar dying mid-flight left the gateway serving a dead backend
forever. Now a watcher task awaits the sidecar server's termination
and, when it dies while the gateway is still up, restarts it with the
fleet's exponential-backoff policy (cfg.fleet backoff knobs,
serving/fleet.py discipline) — bounded by restart_max_attempts, after
which the whole process exits LOUDLY with a typed
SidecarSupervisionError instead of limping along backendless.
"""

from __future__ import annotations

import asyncio
import logging
import os
import random
import tempfile
from typing import Callable, Optional

from ggrmcp_tpu.core.config import Config
from ggrmcp_tpu.gateway.app import Gateway, setup_logging

logger = logging.getLogger("ggrmcp.serving.launcher")


class SidecarSupervisionError(RuntimeError):
    """The co-launched sidecar died and could not be restarted within
    the bounded retry budget — the launcher exits typed rather than
    serving a dead backend forever."""

    def __init__(self, attempts: int, last_error: str):
        super().__init__(
            f"co-launched sidecar died and {attempts} restart attempts "
            f"failed (last: {last_error}); exiting — a gateway without "
            f"its sidecar serves nothing but errors"
        )
        self.attempts = attempts


def resolve_colaunch_transport(cfg: Config) -> None:
    """Pick the gateway→sidecar hop for co-launch, in place.

    The co-launched hop never leaves the host, so ride a private UDS:
    cheaper per call than TCP loopback on the shared core and no port
    to collide with. An explicitly configured serving.port (or
    uds_path) wins over this default —
    pinning a port means something external (grpcurl, another gateway)
    intends to dial the sidecar over TCP."""
    default_port = type(cfg.serving)().port
    if (
        cfg.serving.colaunch_uds
        and not cfg.serving.uds_path
        and cfg.serving.port == default_port
    ):
        cfg.serving.uds_path = os.path.join(
            tempfile.gettempdir(), f"ggrmcp-sidecar-{os.getpid()}.sock"
        )


async def _supervise_sidecar(
    state: dict,
    factory: Callable[[], object],
    cfg: Config,
    gateway: Gateway,
) -> None:
    """Watch the co-launched sidecar; restart it with backoff when it
    dies. Runs until cancelled (clean shutdown cancels BEFORE stopping
    the sidecar, so a deliberate stop is never mistaken for a death).
    Raises SidecarSupervisionError when the retry budget is exhausted.

    `state["sidecar"]` always holds the live sidecar (the finally in
    _run stops whatever is current). Restart keeps the same listen
    target (the UDS path / pinned port), so the gateway's existing
    channel reconnects; rediscovery re-stamps methods and roles."""
    fleet = cfg.fleet
    rng = random.Random(0)
    while True:
        sidecar = state["sidecar"]
        # Shielded: cancelling this watcher (clean shutdown) must not
        # cancel the server's own shutdown future, which the stop()
        # that follows awaits.
        await asyncio.shield(sidecar.server.wait_for_termination())
        logger.error(
            "co-launched sidecar on %s terminated unexpectedly; "
            "restarting (max %d attempts)",
            sidecar.target, fleet.restart_max_attempts,
        )
        last_error = "unknown"
        for attempt in range(fleet.restart_max_attempts):
            delay = min(
                fleet.backoff_max_s,
                fleet.backoff_base_s * (2.0 ** attempt),
            ) * (1.0 + fleet.backoff_jitter * rng.random())
            await asyncio.sleep(delay)
            try:
                try:
                    await state["sidecar"].stop()
                except asyncio.CancelledError:
                    raise
                except Exception:  # noqa: BLE001 — already dead is fine
                    pass
                replacement = factory()
                await replacement.start(cfg.serving.port)
                state["sidecar"] = replacement
                # Nudge the discoverer instead of waiting a watchdog
                # period: reconnect the backend on the (unchanged)
                # target, then rediscover so methods/roles re-stamp.
                backend = next(
                    (
                        b for b in gateway.discoverer.backends
                        if b.target == replacement.target
                    ),
                    None,
                )
                if backend is not None:
                    await backend.connect(cfg.grpc.connect_timeout_s)
                await gateway.discoverer.discover_services()
                logger.warning(
                    "co-launched sidecar restarted on %s "
                    "(attempt %d/%d)",
                    replacement.target, attempt + 1,
                    fleet.restart_max_attempts,
                )
                break
            except asyncio.CancelledError:
                raise
            except Exception as exc:  # noqa: BLE001 — retry typed below
                last_error = str(exc)
                logger.error(
                    "sidecar restart attempt %d/%d failed: %s",
                    attempt + 1, fleet.restart_max_attempts, exc,
                )
        else:
            raise SidecarSupervisionError(
                fleet.restart_max_attempts, last_error
            )


async def _run(
    cfg: Config,
    extra_targets: list[str],
    sidecar_factory: Optional[Callable[[], object]] = None,
) -> None:
    if sidecar_factory is None:
        from ggrmcp_tpu.serving.sidecar import Sidecar

        def sidecar_factory() -> object:
            return Sidecar(cfg.serving)

        resolve_colaunch_transport(cfg)
    state = {"sidecar": sidecar_factory()}
    await state["sidecar"].start(cfg.serving.port)
    # Callers pass only explicitly configured external backends
    # (__main__.py decides placeholder-vs-explicit from flags + config).
    targets = [state["sidecar"].target]
    for target in extra_targets:
        if target not in targets:
            targets.append(target)
    logger.info(
        "co-launched sidecar on %s; gateway backends: %s",
        state["sidecar"].target, targets,
    )

    gateway = Gateway(cfg, targets=targets)
    watcher = asyncio.get_running_loop().create_task(
        _supervise_sidecar(state, sidecar_factory, cfg, gateway)
    )
    gw_task = asyncio.get_running_loop().create_task(
        gateway.run_forever()
    )
    try:
        done, _pending = await asyncio.wait(
            {watcher, gw_task}, return_when=asyncio.FIRST_COMPLETED
        )
        if watcher in done:
            # The watcher only finishes by raising (budget exhausted):
            # tear the gateway down and let the typed error escape.
            gw_task.cancel()
            try:
                await gw_task
            except asyncio.CancelledError:
                pass
            watcher.result()  # raises SidecarSupervisionError
        else:
            await gw_task  # propagate a gateway crash, if any
    finally:
        # Cancel supervision BEFORE stopping the sidecar, or the clean
        # shutdown reads as a death and races a restart against it.
        watcher.cancel()
        try:
            await watcher
        except (asyncio.CancelledError, SidecarSupervisionError):
            pass
        await state["sidecar"].stop()


def run_gateway_with_sidecar(cfg: Config, extra_targets: list[str] | None = None) -> None:
    from ggrmcp_tpu.utils.jaxenv import init_runtime

    setup_logging(cfg)
    init_runtime("gateway --tpu")
    asyncio.run(_run(cfg, extra_targets or []))
