"""Device-memory ledger: named, reconcilable accounting of every
persistent device allocation in the serving plane — "phase attribution
for bytes" (the PR 9 design discipline applied to HBM instead of time).

The two scarce resources a TPU window spends are bytes and compiles,
and until this module the tree exported exactly one memory number
(`kv_cache_bytes`) while weights, the paged arena, block tables,
grammar tables, LoRA factors, and the interleave mini all went
unaccounted. vLLM's startup memory profiler is the prior art: it
walks what is actually resident and attributes it, instead of trusting
a config-derived estimate.

Design (mirrors the tick-phase partition + closure contract):

* Every owner of a persistent device allocation REGISTERS a named
  component with a zero-arg supplier that returns the live array tree
  (``ledger.register("kv_arena", lambda: (self.cache.k, ...))``). The
  supplier reads the owner's current attributes, so cache rebuilds
  after a tick failure are accounted automatically — the ledger can
  never hold a stale pointer, only a stale read.
* ``component_bytes()`` sums ``nbytes`` over each supplier's jax-array
  leaves. Device shapes are fixed for a component's lifetime (the
  whole-lifetime-allocation invariant, docs/paged_kv.md), so a short
  TTL cache makes the per-tick snapshot for the timeline counter
  tracks effectively free.
* ``reconcile()`` is the closure test: it partitions
  ``jax.live_arrays()`` by ARRAY IDENTITY against the registered
  components, so ``attributed + unattributed == live`` holds exactly
  by construction and a component whose supplier drifted from the real
  allocation shows up as unattributed bytes, never as silent
  double-counting (a leaf claimed by two components is attributed once
  and counted in ``double_registered``).

Obs-off (serving.observability.enabled=false): ``register`` stores
nothing and every query returns empty — the ledger allocates and
computes nothing, like the flight recorder's disabled hooks.

Enforcement: the graftlint rule ``ledger-unregistered``
(ggrmcp_tpu/analysis/rules.py) keeps future persistent allocations in
serving modules from bypassing the ledger.

Threading: registration happens at construction time; queries run from
the stats/scrape/debug paths and read host attributes the batcher's
executor mutates — the usual lock-free stale-read contract. The TTL
cache takes a micro-lock.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Optional

# (scope, component) ordering for stable output; unknown components
# append after these.
CORE_COMPONENTS = (
    "weights", "lora", "kv_arena", "block_tables", "ilv_mini",
    "grammar_arena", "tick_state",
)


def _jax_leaves(tree: Any) -> list:
    """Flatten a supplier's tree to the jax.Array leaves it holds
    (QuantizedArray and KVCache namedtuples are pytrees; None prunes)."""
    if tree is None:
        return []
    import jax

    return [
        leaf for leaf in jax.tree_util.tree_leaves(tree)
        if isinstance(leaf, jax.Array)
    ]


class MemoryLedger:
    """Registry of named persistent device allocations for ONE engine
    and the batchers built over it (per-tier scopes)."""

    def __init__(self, enabled: bool = True):
        self.enabled = bool(enabled)
        # (scope, component) -> supplier returning the live array tree.
        self._suppliers: dict[tuple[str, str], Callable[[], Any]] = {}
        # (scope, component) -> supplier returning a HOST-memory info
        # dict (bytes/entries/budget_bytes/file_*) or None when the
        # component is off — host bytes live outside jax.live_arrays(),
        # so they ride beside the device closure, never inside it.
        self._host_suppliers: dict[tuple[str, str], Callable[[], Any]] = {}
        self._lock = threading.Lock()
        self._cache: tuple[float, dict] = (0.0, {})

    def register(
        self, component: str, supplier: Callable[[], Any], scope: str = ""
    ) -> None:
        """Attach a component. `scope` separates per-tier instances of
        the same component ("" = engine-level / the flat pool);
        re-registering a key replaces its supplier (rebuild paths)."""
        if not self.enabled:
            return
        self._suppliers[(scope, component)] = supplier

    def register_host(
        self, component: str, supplier: Callable[[], Any], scope: str = ""
    ) -> None:
        """Attach a HOST-memory component (e.g. the host-tier KV page
        pool, serving/host_pool.py). The supplier returns a dict with
        at least `bytes` and `entries` (plus budget/file fields), or
        None when the component is disabled. Host bytes are exact by
        construction — the owner counts what it stores — so they have
        no reconcile pass; they render as the `host` section of
        GET /debug/memory. Same obs-off contract as register()."""
        if not self.enabled:
            return
        self._host_suppliers[(scope, component)] = supplier

    # -- queries -------------------------------------------------------------

    def component_arrays(self) -> dict[tuple[str, str], list]:
        """Live jax-array leaves per (scope, component). Supplier
        errors are the owner's bug — surfaced, never swallowed into a
        silently-short ledger."""
        return {
            key: _jax_leaves(supplier())
            for key, supplier in self._suppliers.items()
        }

    def component_bytes(self, max_age_s: float = 0.0) -> dict:
        """(scope, component) -> bytes. `max_age_s` > 0 serves a
        cached snapshot (the per-tick timeline counter path): sizes
        only change on rebuild/alloc events, so a ~1s TTL loses
        nothing a per-tick walk would see."""
        if not self.enabled:
            return {}
        now = time.monotonic()
        with self._lock:
            stamp, cached = self._cache
            if max_age_s > 0 and now - stamp < max_age_s:
                return dict(cached)
        out = {
            key: sum(leaf.nbytes for leaf in leaves)
            for key, leaves in self.component_arrays().items()
        }
        with self._lock:
            self._cache = (now, dict(out))
        return out

    def base_bytes(self, max_age_s: float = 0.0) -> dict:
        """component -> bytes summed across scopes (the per-process
        rollup /debug/memory and the bench artifact report)."""
        out: dict[str, int] = {}
        for (_scope, component), b in self.component_bytes(max_age_s).items():
            out[component] = out.get(component, 0) + b
        return out

    def total_bytes(self) -> int:
        return sum(self.component_bytes().values())

    def host_components(self) -> dict[tuple[str, str], dict]:
        """(scope, component) -> host-memory info dict for every
        registered host supplier whose component is live (None
        supplier results — disabled pools — are skipped). Supplier
        errors surface like component_arrays(): an owner bug, never a
        silently-short section."""
        out: dict[tuple[str, str], dict] = {}
        for key, supplier in self._host_suppliers.items():
            info = supplier()
            if info is not None:
                out[key] = info
        return out

    # -- closure -------------------------------------------------------------

    @staticmethod
    def live_ids() -> set:
        """Identity snapshot of the process's live jax arrays — taken
        BEFORE building a stack, it scopes reconcile() to that stack's
        own allocations (other engines in the process stay out of the
        closure)."""
        import jax

        return {id(a) for a in jax.live_arrays()}

    def reconcile(self, baseline_ids: Optional[set] = None) -> dict:
        """Partition the live device buffers by identity against the
        registered components. Returns a dict with per-component bytes,
        attributed/live/unattributed totals, the unattributed arrays'
        summaries, and the double-registration count. The closure
        invariant — attributed + unattributed == live — holds exactly
        by construction; the TEST surface asserts unattributed ≈ 0 at
        a quiescent point (tests/test_memory.py, `make test-mem`)."""
        import jax

        owner_of: dict[int, tuple[str, str]] = {}
        per_comp: dict[tuple[str, str], int] = {}
        double = 0
        for key, leaves in self.component_arrays().items():
            per_comp.setdefault(key, 0)
            for leaf in leaves:
                if id(leaf) in owner_of:
                    double += 1
                    continue  # first registration wins; counted, never summed twice
                owner_of[id(leaf)] = key
        attributed = 0
        live = 0
        unattributed: list[dict] = []
        for arr in jax.live_arrays():
            if baseline_ids is not None and id(arr) in baseline_ids:
                continue
            live += arr.nbytes
            key = owner_of.get(id(arr))
            if key is None:
                unattributed.append({
                    "shape": list(arr.shape),
                    "dtype": str(arr.dtype),
                    "bytes": int(arr.nbytes),
                })
                continue
            attributed += arr.nbytes
            per_comp[key] += arr.nbytes
        unattributed.sort(key=lambda e: -e["bytes"])
        return {
            "components": {
                f"{scope}/{comp}" if scope else comp: b
                for (scope, comp), b in sorted(per_comp.items())
            },
            "attributed_bytes": attributed,
            "live_bytes": live,
            "unattributed_bytes": live - attributed,
            "unattributed_arrays": unattributed,
            "double_registered": double,
        }
