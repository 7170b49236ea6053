"""The graftlint rule catalog. Every rule encodes a REAL shipped bug or
documented invariant of this repo's serving plane — the precedent
string on each rule cites it, and tests/test_graftlint.py proves each
rule fires on the historical pre-fix code shape. Adding a rule without
a precedent (or a fixture showing the failure) is the process bug this
file exists to prevent: docs/static_analysis.md has the checklist.
"""

from __future__ import annotations

import ast
import pathlib
import re

from ggrmcp_tpu.analysis.graftlint import (
    Module,
    Rule,
    call_name,
    exception_names,
    keyword,
    scoped_walk,
)

# ---------------------------------------------------------------------
# 1. sharded-sampling — PR 7's categorical divergence
# ---------------------------------------------------------------------


class ShardedSamplingRule(Rule):
    """Vocab-shaped noise draws are mesh-DEPENDENT: the random-bit
    assignment of a [V]-shaped tensor follows the array's partitioning,
    so the same seed draws different tokens on a vocab-sharded mesh
    than on one chip. jax.random.categorical is the canonical offender;
    gumbel/exponential/uniform with an explicit non-scalar shape are
    the same trick hand-rolled."""

    id = "sharded-sampling"
    title = (
        "mesh-dependent sampling: categorical / vocab-shaped noise "
        "draw in serving or ops code"
    )
    precedent = (
        "PR 7 (CHANGES.md): jax.random.categorical's [V]-shaped noise "
        "follows the logits' partitioning — sampled rows drew DIFFERENT "
        "tokens on a vocab-sharded (column-parallel lm_head) mesh. "
        "Sanctioned path: per-row scalar uniform + CDF inversion "
        "(ops/sampling.py::_invcdf_pick)."
    )

    _NOISE = {"gumbel", "exponential", "uniform", "normal"}

    def applies_to(self, rel: str) -> bool:
        return rel.startswith(("ggrmcp_tpu/ops/", "ggrmcp_tpu/serving/"))

    def check(self, module: Module):
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node)
            parts = name.split(".")
            base = parts[-1]
            if base == "categorical" and (
                len(parts) == 1 or "random" in parts
            ):
                yield self.finding(
                    module.rel, node.lineno,
                    f"{name or 'categorical'}() draws [V]-shaped noise "
                    "that follows the logits' sharding — use the "
                    "scalar-uniform CDF inversion "
                    "(ops/sampling._invcdf_pick) instead",
                )
            elif base in self._NOISE and "random" in parts:
                shape = (
                    node.args[1] if len(node.args) > 1
                    else keyword(node, "shape")
                )
                if isinstance(shape, (ast.Tuple, ast.List)) and shape.elts:
                    yield self.finding(
                        module.rel, node.lineno,
                        f"{name}() with a non-scalar shape: the noise "
                        "tensor's draw follows its sharding, so the "
                        "result differs between a replicated and a "
                        "sharded mesh — draw per-row scalars instead",
                    )


# ---------------------------------------------------------------------
# 2. unsharded-transfer — PR 7's device-0 block tables
# ---------------------------------------------------------------------


class UnshardedTransferRule(Rule):
    """In a mesh-aware serving module, host→device transfers of state
    that persists across ticks must name their placement. A bare
    jax.device_put(x) or a `self.attr = jnp.asarray(...)` snapshot
    commits the array to the default device (device 0): every sharded
    tick then pays a resharding transfer for it, and donation of any
    buffer it aliases breaks."""

    id = "unsharded-transfer"
    title = (
        "host->device transfer without explicit sharding in a "
        "mesh-aware serving module"
    )
    precedent = (
        "PR 7 (CHANGES.md): a bare jnp.asarray landed paged block "
        "tables on device 0, forcing a per-tick resharding transfer "
        "and breaking cache donation under tensor-parallel serving. "
        "Fix shape: serving/batching.py::_sync_tables device_puts the "
        "snapshot REPLICATED onto the engine's mesh."
    )

    _FACTORIES = {"asarray", "array"}
    _ROOTS = {"jnp", "np", "numpy", "jax"}

    def applies_to(self, rel: str) -> bool:
        return rel.startswith(
            ("ggrmcp_tpu/serving/", "ggrmcp_tpu/parallel/", "ggrmcp_tpu/ops/")
        )

    @staticmethod
    def _mesh_aware(module: Module) -> bool:
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Attribute) and node.attr == "mesh":
                return True
            if isinstance(node, ast.Name) and node.id in (
                "mesh", "Mesh", "NamedSharding", "make_array_from_callback",
            ):
                return True
            if isinstance(node, ast.arg) and node.arg == "mesh":
                return True
        return False

    def check(self, module: Module):
        if not self._mesh_aware(module):
            return
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Call):
                name = call_name(node)
                if name.split(".")[-1] == "device_put" and len(
                    node.args
                ) < 2 and keyword(node, "device") is None and keyword(
                    node, "sharding"
                ) is None:
                    yield self.finding(
                        module.rel, node.lineno,
                        f"{name}() without a device/sharding argument "
                        "commits to device 0 — pass "
                        "NamedSharding(mesh, spec) explicitly",
                    )
            elif isinstance(node, ast.Assign):
                # Persistent state: a DIRECT attribute target
                # (`self.x = ...`) whose value STORES a bare-factory
                # array — directly, through a NamedTuple ._replace
                # (the PR 7 block-table shape), or through a cache
                # constructor. Factory arrays passed as INPUTS to a
                # jitted call are transient (the call's output owns
                # its placement) and stay exempt.
                if not any(
                    isinstance(t, ast.Attribute) for t in node.targets
                ):
                    continue
                seen = set()
                for site in self._stored_factories(node.value):
                    key = (site.lineno, site.col_offset)
                    if key in seen:
                        continue
                    seen.add(key)
                    yield self.finding(
                        module.rel, site.lineno,
                        f"persistent device state assigned from bare "
                        f"{call_name(site)}() lands on device 0 — "
                        "device_put it replicated onto the mesh "
                        "(see _sync_tables)",
                    )

    def _is_factory(self, node) -> bool:
        if not isinstance(node, ast.Call):
            return False
        parts = call_name(node).split(".")
        return parts[-1] in self._FACTORIES and parts[0] in self._ROOTS

    def _stored_factories(self, value):
        """Factory calls whose RESULT the assignment stores: the value
        itself, or arguments of an aliasing constructor (`._replace`
        or an Uppercase NamedTuple/dataclass constructor) anywhere in
        the value expression."""
        if self._is_factory(value):
            yield value
        for sub in ast.walk(value):
            if not isinstance(sub, ast.Call):
                continue
            callee = call_name(sub).split(".")[-1]
            if callee != "_replace" and not callee[:1].isupper():
                continue
            for arg in [*sub.args, *(kw.value for kw in sub.keywords)]:
                for inner in [arg, *ast.walk(arg)]:
                    if self._is_factory(inner):
                        yield inner


# ---------------------------------------------------------------------
# 3. alloc-in-jit — PR 6's whole-lifetime-allocation invariant
# ---------------------------------------------------------------------


class AllocInJitRule(Rule):
    """Jitted tick bodies (`_tick_*_impl`) and everything they call
    within their module must not create fresh device arrays or touch
    PageAllocator host state: pages are allocated for a
    request's WHOLE LIFETIME at admission, block tables are host state
    snapshotted between ticks, and the tick's shapes/donation contract
    depend on it."""

    id = "alloc-in-jit"
    title = (
        "fresh allocation or PageAllocator mutation reachable from a "
        "jitted tick body"
    )
    precedent = (
        "PR 6 (CHANGES.md, docs/paged_kv.md): whole-lifetime page "
        "allocation happens at admission; serving/pages.py's "
        "PageAllocator owns ALL mapping state host-side and the jitted "
        "tick only ever sees snapshots. The pre-paged slot pool "
        "re-allocated per admission inside device calls — the exact "
        "shape this rule bans from tick bodies."
    )

    _ROOT_RE = re.compile(r"^_tick\w*_impl$")
    _ALLOC = {
        "zeros", "ones", "empty", "full",
        "zeros_like", "ones_like", "empty_like", "full_like",
    }
    _ALLOC_ROOTS = {"jnp", "np", "numpy", "jax"}
    _HOST_STATE = {"pages", "allocator", "page_allocator"}

    def applies_to(self, rel: str) -> bool:
        return rel.startswith(("ggrmcp_tpu/serving/", "ggrmcp_tpu/ops/"))

    def check(self, module: Module):
        funcs: dict[str, ast.AST] = {}
        for node in ast.walk(module.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                funcs.setdefault(node.name, node)

        # Reachability over the intra-module call graph: edges are
        # bare-name calls and self./cls. method calls that resolve to a
        # function defined in this module.
        def callees(fn: ast.AST):
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                parts = call_name(node).split(".")
                if parts[-1] in funcs and (
                    len(parts) == 1 or parts[0] in ("self", "cls")
                ):
                    yield parts[-1]

        reachable: set[str] = set()
        frontier = [n for n in funcs if self._ROOT_RE.match(n)]
        while frontier:
            name = frontier.pop()
            if name in reachable:
                continue
            reachable.add(name)
            frontier.extend(callees(funcs[name]))

        for name in sorted(reachable):
            for node in ast.walk(funcs[name]):
                if not isinstance(node, ast.Call):
                    continue
                parts = call_name(node).split(".")
                if (
                    parts[-1] in self._ALLOC
                    and parts[0] in self._ALLOC_ROOTS
                ):
                    yield self.finding(
                        module.rel, node.lineno,
                        f"{'.'.join(parts)}() inside '{name}' (reachable "
                        "from a jitted tick body) allocates a fresh "
                        "buffer per tick — allocate at admission and "
                        "thread it through the carry",
                    )
                elif any(p in self._HOST_STATE for p in parts[:-1]):
                    yield self.finding(
                        module.rel, node.lineno,
                        f"{'.'.join(parts)}() inside '{name}': "
                        "PageAllocator state is HOST state — mutating "
                        "it under trace bakes one snapshot into the "
                        "compiled program",
                    )


# ---------------------------------------------------------------------
# 3b. ledger-unregistered — the memory ledger's coverage invariant
# ---------------------------------------------------------------------


class LedgerUnregisteredRule(Rule):
    """Persistent device allocations in serving modules must register
    a component with the memory ledger: an attribute assigned from a
    cache/params factory that no ledger.register() supplier reads is
    HBM the ledger cannot see — the exact drift the closure test
    (reconcile against jax.live_arrays) exists to catch, surfaced at
    lint time instead of as unattributed bytes in a TPU window."""

    id = "ledger-unregistered"
    title = (
        "persistent device allocation not registered with the memory "
        "ledger"
    )
    precedent = (
        "ISSUE 13 (docs/observability.md): before the ledger, the tree "
        "exported exactly one memory number (kv_cache_bytes) while "
        "weights, the paged arena, draft caches, grammar tables, and "
        "block tables were unaccounted — one bad allocation from OOM "
        "in the llama3-8b window with nothing naming the bytes. "
        "serving/memory_ledger.py::MemoryLedger.reconcile is the "
        "runtime closure; this rule is its static complement."
    )

    # Calls whose result is a persistent device allocation when stored
    # on self: the engine's cache/params factories, the batcher's
    # mini/shared-cache builders, replicated host→device snapshots,
    # and jax/jnp zeros-family factories. np is HOST memory — exempt
    # EXCEPT the host-tier page pool (HostPagePool), whose byte-
    # budgeted host buffers are exactly the kind of unaccounted memory
    # the ledger exists for: it must register a host-bytes supplier
    # (ledger.register_host) just as device allocations register
    # device suppliers. asarray/array transfers are the
    # unsharded-transfer rule's territory (usually transient jit
    # inputs, its documented carve-out).
    _ALLOC_TAILS = {
        "make_cache", "make_paged_cache", "_make_mini", "_make_shared_cache", "_snap_dev", "device_put",
        "_sharded_init", "_shard_params", "_synthetic_int8_init",
        "HostPagePool",
    }
    _FACTORY_TAILS = {
        "zeros", "ones", "empty", "full", "zeros_like", "ones_like",
        "empty_like", "full_like",
    }
    _FACTORY_ROOTS = {"jnp", "jax"}

    def applies_to(self, rel: str) -> bool:
        return rel.startswith("ggrmcp_tpu/serving/")

    def _is_alloc(self, node) -> bool:
        if not isinstance(node, ast.Call):
            return False
        parts = call_name(node).split(".")
        if parts[-1] in self._ALLOC_TAILS:
            return True
        return (
            parts[-1] in self._FACTORY_TAILS
            and parts[0] in self._FACTORY_ROOTS
        )

    @staticmethod
    def _attrs_in(node) -> set:
        """Every `self.<x>`-style attribute name under `node`."""
        return {
            n.attr for n in ast.walk(node) if isinstance(n, ast.Attribute)
        }

    def _registered_attrs(self, cls: ast.ClassDef) -> set:
        """Attribute names any ledger.register() / register_host()
        supplier reads — directly (lambda args) or one
        method-reference hop away (`register("weights",
        self._ledger_weights)` scans that method's body)."""
        methods = {
            n.name: n for n in ast.walk(cls)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        }
        out: set = set()
        for node in ast.walk(cls):
            if not isinstance(node, ast.Call):
                continue
            parts = call_name(node).split(".")
            if (
                parts[-1] not in ("register", "register_host")
                or "ledger" not in parts
            ):
                continue
            for arg in [*node.args, *(kw.value for kw in node.keywords)]:
                out |= self._attrs_in(arg)
                # One indirection: a self.<method> / bare-name supplier
                # defined in this class contributes its body's attrs.
                names = self._attrs_in(arg) | {
                    n.id for n in ast.walk(arg)
                    if isinstance(n, ast.Name)
                }
                for name in names & set(methods):
                    out |= self._attrs_in(methods[name])
        return out

    def check(self, module: Module):
        for cls in ast.walk(module.tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            registered = self._registered_attrs(cls)
            flagged: set = set()
            for node in ast.walk(cls):
                if not isinstance(node, ast.Assign):
                    continue
                targets = [
                    t for t in node.targets
                    if isinstance(t, ast.Attribute)
                    and isinstance(t.value, ast.Name)
                    and t.value.id == "self"
                ]
                if not targets:
                    continue
                if not any(
                    self._is_alloc(n) for n in ast.walk(node.value)
                ):
                    continue
                for t in targets:
                    if t.attr in registered or t.attr in flagged:
                        continue
                    flagged.add(t.attr)
                    yield self.finding(
                        module.rel, node.lineno,
                        f"self.{t.attr} holds a persistent device "
                        "allocation but no ledger.register() supplier "
                        "reads it — register a component "
                        "(engine.ledger.register(name, lambda: "
                        f"self.{t.attr})) so reconcile() can close",
                    )


# ---------------------------------------------------------------------
# 4. async-hygiene — PR 2's swallowed CancelledError
# ---------------------------------------------------------------------


class AsyncHygieneRule(Rule):
    """Coroutines must neither block the event loop (time.sleep,
    subprocess, os.system) nor catch broadly around awaits without an
    explicit asyncio.CancelledError arm. The explicit arm is the
    auditable statement that cancellation was considered: bare/
    BaseException handlers genuinely swallow it, and Exception handlers
    rot into one of those under refactoring."""

    id = "async-hygiene"
    title = (
        "blocking call in a coroutine, or a broad except around an "
        "await without a CancelledError arm"
    )
    precedent = (
        "PR 2 (CHANGES.md): discovery.close() swallowed the "
        "CancelledError aimed at close() itself, wedging a cancelled "
        "shutdown half-closed. Fix shape: rpc/discovery.py::close's "
        "explicit `except asyncio.CancelledError` arm that re-raises "
        "unless the awaited task was the thing cancelled."
    )

    _BLOCKING = {
        "time.sleep": "blocks the event loop — use asyncio.sleep",
        "os.system": "blocks the event loop — use asyncio.create_subprocess_*",
        "os.popen": "blocks the event loop — use asyncio.create_subprocess_*",
        "subprocess.run": "blocks the event loop — run_in_executor it",
        "subprocess.call": "blocks the event loop — run_in_executor it",
        "subprocess.check_call": "blocks the event loop — run_in_executor it",
        "subprocess.check_output": "blocks the event loop — run_in_executor it",
        "subprocess.Popen": "spawns blockingly — run_in_executor it",
    }
    _BROAD = {"<bare>", "Exception", "BaseException"}

    def check(self, module: Module):
        for fn in ast.walk(module.tree):
            if not isinstance(fn, ast.AsyncFunctionDef):
                continue
            yield from self._check_coroutine(module, fn)

    def _check_coroutine(self, module: Module, fn: ast.AsyncFunctionDef):
        for node in scoped_walk(fn):
            if isinstance(node, ast.Call):
                name = call_name(node)
                why = self._BLOCKING.get(name)
                if why is not None:
                    yield self.finding(
                        module.rel, node.lineno,
                        f"{name}() in coroutine '{fn.name}': {why}",
                    )
            elif isinstance(node, ast.Try):
                yield from self._check_try(module, fn, node)

    def _check_try(self, module: Module, fn, node: ast.Try):
        has_await = any(
            isinstance(n, (ast.Await, ast.AsyncFor, ast.AsyncWith))
            for stmt in node.body
            for n in [stmt, *scoped_walk(stmt)]
        )
        if not has_await:
            return
        has_cancel_arm = any(
            "CancelledError" in exception_names(h.type)
            for h in node.handlers
        )
        for handler in node.handlers:
            names = exception_names(handler.type)
            if not (set(names) & self._BROAD):
                continue
            reraises = any(
                isinstance(n, ast.Raise) and n.exc is None
                for stmt in handler.body
                for n in [stmt, *scoped_walk(stmt)]
            )
            if has_cancel_arm or reraises:
                continue
            label = "bare except" if "<bare>" in names else (
                f"except {' | '.join(names)}"
            )
            yield self.finding(
                module.rel, handler.lineno,
                f"{label} around an await in coroutine '{fn.name}' "
                "without an `except asyncio.CancelledError` arm — "
                "cancellation must be visibly considered (add the "
                "re-raising arm above this handler)",
            )


# ---------------------------------------------------------------------
# 5. proto-drift — the static half of the runtime drift test
# ---------------------------------------------------------------------


class ProtoDriftRule(Rule):
    """Every scalar numeric ServingStats field must be NAMED in
    gateway/metrics.py's help descriptors (_SERVING_HELP; histogram
    bases in _SERVING_HIST_HELP), every scalar numeric TickRecord
    field — the per-tick surface the flight recorder and the unified
    timeline render — in _TICK_HELP, and no descriptor may name a
    field the proto no longer has. The runtime drift test
    (tests/test_observability.py) proves every field EXPORTS; this
    static complement proves every field is documented — the half a
    runtime test cannot see, because the generic-help fallback exports
    either way."""

    id = "proto-drift"
    title = (
        "ServingStats/TickRecord scalar field missing from (or stale "
        "in) gateway/metrics.py help descriptors"
    )
    precedent = (
        "PR 3 (CHANGES.md): ServingStats gauges were a hand-synced "
        "literal list — the 'added a field, forgot the gauge' class. "
        "Descriptor-driven export killed the gauge half; this rule "
        "kills the surviving help-text half (TickRecord coverage added "
        "with the tick-phase/timeline surface)."
    )

    PROTO = "protos/serving.proto"
    METRICS = "ggrmcp_tpu/gateway/metrics.py"
    _FIELD_RE = re.compile(
        r"^\s*(repeated\s+)?([A-Za-z_][\w.]*)\s+(\w+)\s*=\s*\d+\s*;"
    )

    def _message_fields(self, root: pathlib.Path, message: str):
        """(repeated, type, name) triples of `message` in the serving
        proto, or None when the message is absent (partial fixture
        trees opt out per message)."""
        text = (root / self.PROTO).read_text()
        fields: list[tuple[bool, str, str]] = []
        in_msg = False
        for line in text.splitlines():
            if re.match(rf"\s*message\s+{message}\s*\{{", line):
                in_msg = True
                continue
            if in_msg:
                if line.strip() == "}":
                    return fields
                m = self._FIELD_RE.match(line)
                if m:
                    fields.append(
                        (bool(m.group(1)), m.group(2), m.group(3))
                    )
        return fields if in_msg else None

    def parse_proto(self, root: pathlib.Path):
        """(scalar numeric field names, histogram base names) of
        ServingStatsResponse, mirroring gateway/metrics.py's
        descriptor-driven classification."""
        fields = self._message_fields(root, "ServingStatsResponse") or []
        hist_bases = [
            name[: -len("_bucket")]
            for repeated, _, name in fields
            if repeated and name.endswith("_bucket")
        ]
        members = {"latency_bucket_bounds_ms"}
        for base in hist_bases:
            members.update((f"{base}_sum", f"{base}_count"))
        scalars = [
            name
            for repeated, ftype, name in fields
            if not repeated and name not in members and ftype != "string"
        ]
        return scalars, hist_bases

    def parse_tick(self, root: pathlib.Path):
        """Scalar numeric TickRecord field names (the /debug/ticks and
        timeline record surface _TICK_HELP must cover), or None when
        the proto has no TickRecord message (fixture opt-out)."""
        fields = self._message_fields(root, "TickRecord")
        if fields is None:
            return None
        return [
            name
            for repeated, ftype, name in fields
            if not repeated and ftype != "string"
        ]

    def parse_help_dicts(self, root: pathlib.Path):
        """Keys + line numbers of _SERVING_HELP / _SERVING_HIST_HELP."""
        tree = ast.parse((root / self.METRICS).read_text())
        out = {}
        for node in ast.walk(tree):
            if (
                isinstance(node, ast.Assign)
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id in (
                    "_SERVING_HELP", "_SERVING_HIST_HELP", "_TICK_HELP"
                )
                and isinstance(node.value, ast.Dict)
            ):
                keys = {}
                for k in node.value.keys:
                    if isinstance(k, ast.Constant) and isinstance(
                        k.value, str
                    ):
                        keys[k.value] = k.lineno
                out[node.targets[0].id] = (node.lineno, keys)
        return out

    def check_project(self, root: pathlib.Path):
        root = pathlib.Path(root)
        if not (root / self.PROTO).exists() or not (
            root / self.METRICS
        ).exists():
            return  # partial fixture trees opt out of this contract
        scalars, hist_bases = self.parse_proto(root)
        dicts = self.parse_help_dicts(root)
        tick_scalars = self.parse_tick(root)
        contracts = [
            ("_SERVING_HELP", scalars),
            ("_SERVING_HIST_HELP", hist_bases),
        ]
        if tick_scalars is not None:
            # The TickRecord surface (tick ring → /debug/ticks →
            # timeline) carries the same drift contract: every scalar
            # documented, no descriptor naming a retired field.
            contracts.append(("_TICK_HELP", tick_scalars))
        for dict_name, names in contracts:
            if dict_name not in dicts:
                yield self.finding(
                    self.METRICS, 1,
                    f"{dict_name} dict not found — the descriptor-driven "
                    "export needs its help table",
                )
                continue
            lineno, keys = dicts[dict_name]
            for name in names:
                if name not in keys:
                    yield self.finding(
                        self.METRICS, lineno,
                        f"ServingStats field '{name}' "
                        f"({self.PROTO}) has no {dict_name} entry — "
                        "name it so dashboards inherit real help text",
                    )
            for key, key_line in keys.items():
                if key not in names:
                    yield self.finding(
                        self.METRICS, key_line,
                        f"{dict_name} names '{key}' which is not a "
                        f"matching ServingStats field in {self.PROTO} — "
                        "stale descriptor",
                    )


ALL_RULES = (
    ShardedSamplingRule(),
    UnshardedTransferRule(),
    AllocInJitRule(),
    LedgerUnregisteredRule(),
    AsyncHygieneRule(),
    ProtoDriftRule(),
)
