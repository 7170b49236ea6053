"""graftlint net (marker `analysis`, tier-1; `make test-analysis`).

Three layers, mirroring the gate's claims:

1. RULE FIXTURES — each of the five rule families is proven to (a)
   fire on a minimal fixture, (b) fire on the HISTORICAL pre-fix code
   shape of the shipped bug its precedent cites (PR 7 categorical /
   block tables, PR 6 alloc-in-tick, PR 2 swallowed CancelledError,
   PR 3 hand-synced descriptors), and (c) be suppressed by a justified
   `# graftlint: disable=...` pragma.
2. PRAGMA SELF-POLICING — a pragma without a justification is itself a
   finding, a stale pragma is reported as a cleanup candidate, an
   unknown rule id is rejected, and the standalone-line form covers
   the next source line.
3. SELF-ENFORCEMENT — the analyzer runs over THIS repository and must
   report zero unsuppressed findings (the `make graftlint` gate), and
   scripts/security_scan.py must still trip on a planted HIGH finding
   (the scanner-rot smoke, satellite of the same gate).
"""

from __future__ import annotations

import pathlib
import subprocess
import sys
import textwrap

import pytest

from ggrmcp_tpu.analysis import run
from ggrmcp_tpu.analysis.graftlint import (
    META_MISSING,
    META_STALE,
    META_UNKNOWN,
)

pytestmark = pytest.mark.analysis

REPO = pathlib.Path(__file__).resolve().parents[1]


def lint(tmp_path: pathlib.Path, rel: str, source: str):
    """Write one fixture module into a scratch tree and analyze it."""
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    return run(tmp_path)


def rule_ids(report) -> list[str]:
    return [f.rule for f in report.findings]


# ---------------------------------------------------------------------
# 1a. sharded-sampling (PR 7: categorical on a vocab-sharded mesh)
# ---------------------------------------------------------------------


class TestShardedSampling:
    # The PR 7 pre-fix shape: ops/sampling.py sampled every row with
    # jax.random.categorical over the [V] axis — identical on one chip,
    # divergent once the lm_head went column-parallel.
    HISTORICAL = """
        import jax

        def sample_dynamic(logits, seeds, step):
            key = jax.random.fold_in(jax.random.PRNGKey(0), step)
            return jax.random.categorical(key, logits, axis=-1)
    """

    def test_fires_on_historical_pr7_shape(self, tmp_path):
        report = lint(
            tmp_path, "ggrmcp_tpu/ops/sampling.py", self.HISTORICAL
        )
        assert rule_ids(report) == ["sharded-sampling"]
        assert "categorical" in report.findings[0].message
        assert "PR 7" in report.findings[0].precedent

    def test_fires_on_vocab_shaped_noise(self, tmp_path):
        report = lint(
            tmp_path, "ggrmcp_tpu/serving/sampler.py", """
            import jax

            def gumbel_max(logits, key):
                g = jax.random.gumbel(key, (logits.shape[-1],))
                return (logits + g).argmax(-1)
            """,
        )
        assert rule_ids(report) == ["sharded-sampling"]

    def test_scalar_draws_and_other_dirs_exempt(self, tmp_path):
        # Per-row scalar uniforms (the sanctioned CDF-inversion path)
        # never fire; neither does categorical OUTSIDE ops/serving.
        report = lint(
            tmp_path, "ggrmcp_tpu/ops/sampling.py", """
            import jax

            def draw(key):
                return jax.random.uniform(key, ())
            """,
        )
        assert report.clean
        report = lint(
            tmp_path, "ggrmcp_tpu/models/toy.py", """
            import jax

            def init_sample(key, logits):
                return jax.random.categorical(key, logits)
            """,
        )
        assert report.clean

    def test_pragma_suppresses(self, tmp_path):
        report = lint(
            tmp_path, "ggrmcp_tpu/ops/sampling.py", """
            import jax

            def sample(logits, key):
                return jax.random.categorical(key, logits)  # graftlint: disable=sharded-sampling -- fixture: proves suppression
            """,
        )
        assert report.clean
        assert len(report.suppressed) == 1
        finding, pragma = report.suppressed[0]
        assert finding.rule == "sharded-sampling"
        assert pragma.justification.startswith("fixture:")


# ---------------------------------------------------------------------
# 1b. unsharded-transfer (PR 7: block tables on device 0)
# ---------------------------------------------------------------------


class TestUnshardedTransfer:
    # The PR 7 pre-fix shape, verbatim in structure: the paged block
    # tables snapshotted into the cache NamedTuple with a bare
    # jnp.asarray — landing on device 0 and forcing per-tick resharding.
    HISTORICAL = """
        import jax.numpy as jnp

        class Batcher:
            def _sync_tables(self):
                if self._tables_dirty:
                    mesh = self.engine.mesh
                    self.cache = self.cache._replace(
                        table=jnp.asarray(self.pages.tables)
                    )
                    self._tables_dirty = False
    """

    def test_fires_on_historical_pr7_shape(self, tmp_path):
        report = lint(
            tmp_path, "ggrmcp_tpu/serving/batching.py", self.HISTORICAL
        )
        assert rule_ids(report) == ["unsharded-transfer"]
        assert "device 0" in report.findings[0].message

    def test_fires_on_bare_device_put(self, tmp_path):
        report = lint(
            tmp_path, "ggrmcp_tpu/serving/tensors.py", """
            import jax

            def to_device(x, mesh):
                return jax.device_put(x)
            """,
        )
        assert rule_ids(report) == ["unsharded-transfer"]

    def test_explicit_sharding_and_transient_inputs_exempt(self, tmp_path):
        report = lint(
            tmp_path, "ggrmcp_tpu/serving/batching.py", """
            import jax
            import jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec

            class Batcher:
                def _snap(self, x):
                    return jax.device_put(
                        x, NamedSharding(self.engine.mesh, PartitionSpec())
                    )

                def _dispatch(self):
                    # asarray as a jitted call INPUT is transient — the
                    # call output owns its placement.
                    self.cache = self._tick(
                        jnp.asarray(self.cur_tokens), self.cache
                    )
            """,
        )
        assert report.clean

    def test_meshless_module_exempt(self, tmp_path):
        # No mesh/NamedSharding reference in the module -> the single-
        # device code path, where default placement is the contract.
        report = lint(
            tmp_path, "ggrmcp_tpu/serving/util.py", """
            import jax.numpy as jnp

            class Pool:
                def snap(self, x):
                    self.dev = jnp.asarray(x)
            """,
        )
        assert report.clean

    def test_pragma_suppresses(self, tmp_path):
        report = lint(
            tmp_path, "ggrmcp_tpu/serving/batching.py", """
            import jax

            def to_device(x, mesh):
                # graftlint: disable=unsharded-transfer -- fixture: single-tier scratch, never read by a sharded program
                return jax.device_put(x)
            """,
        )
        assert report.clean
        assert len(report.suppressed) == 1


# ---------------------------------------------------------------------
# 1c. alloc-in-jit (PR 6: whole-lifetime allocation at admission)
# ---------------------------------------------------------------------


class TestAllocInJit:
    # The pre-PR 6 shape: the slot pool conjured fresh KV storage
    # inside the device call instead of writing through pre-admitted
    # pages — exactly what the paged plane's donation contract bans.
    HISTORICAL = """
        import jax.numpy as jnp

        class Batcher:
            def _tick_impl(self, params, tokens, cache):
                fresh = self._grow_row(cache)
                return fresh

            def _grow_row(self, cache):
                return jnp.zeros((4, 128, 8, 64), jnp.bfloat16)
    """

    def test_fires_through_intra_module_reachability(self, tmp_path):
        report = lint(
            tmp_path, "ggrmcp_tpu/serving/batching.py", self.HISTORICAL
        )
        assert rule_ids(report) == ["alloc-in-jit"]
        assert "_grow_row" in report.findings[0].message
        assert "PR 6" in report.findings[0].precedent

    def test_fires_on_allocator_mutation_in_tick_body(self, tmp_path):
        report = lint(
            tmp_path, "ggrmcp_tpu/serving/batching.py", """
            class Batcher:
                def _tick_impl(self, params, tokens, cache):
                    self.pages.admit(2)
                    return tokens
            """,
        )
        assert rule_ids(report) == ["alloc-in-jit"]
        assert "HOST state" in report.findings[0].message

    def test_fires_in_jump_tick_through_core_helper(self, tmp_path):
        # ISSUE 16's multi-token advance is a root too (`_tick_jump_impl`
        # matches the tick-body pattern): a forced-run window conjured
        # fresh inside the advance — instead of concatenated from the
        # traced run-table gathers — fires through the same
        # intra-module reachability as any other tick helper.
        report = lint(
            tmp_path, "ggrmcp_tpu/serving/batching.py", """
            import jax.numpy as jnp

            class Batcher:
                def _tick_jump_impl(self, params, tokens, cache):
                    return self._jump_core(tokens, cache)

                def _jump_core(self, tokens, cache):
                    window = jnp.zeros((4, 9), jnp.int32)
                    return window.at[:, 0].set(tokens), cache
            """,
        )
        assert rule_ids(report) == ["alloc-in-jit"]
        assert "_jump_core" in report.findings[0].message

    def test_jump_window_from_traced_gathers_clean(self, tmp_path):
        # The shipped shape: the window is concatenate/pad over traced
        # inputs and the donated cache is written through — no fresh
        # buffer, nothing to flag.
        report = lint(
            tmp_path, "ggrmcp_tpu/serving/batching.py", """
            import jax.numpy as jnp

            class Batcher:
                def _tick_jump_impl(self, params, tokens, cache, run):
                    window = jnp.concatenate([tokens[:, None], run], axis=1)
                    emit = jnp.pad(run, ((0, 0), (0, 1)))
                    return window, emit, cache._replace(length=cache.length)
            """,
        )
        assert report.clean

    def test_admission_path_exempt(self, tmp_path):
        # Allocation at ADMISSION is the invariant's sanctioned side.
        report = lint(
            tmp_path, "ggrmcp_tpu/serving/batching.py", """
            import jax.numpy as jnp

            class Batcher:
                def _admit_full_impl(self, tokens):
                    mini = jnp.zeros((4, 128), jnp.int32)
                    return mini
            """,
        )
        assert report.clean

    def test_pragma_suppresses(self, tmp_path):
        report = lint(
            tmp_path, "ggrmcp_tpu/serving/batching.py", """
            import jax.numpy as jnp

            class Batcher:
                def _tick_impl(self, cache):
                    mask = jnp.zeros((4,), bool)  # graftlint: disable=alloc-in-jit -- fixture: constant-folded scratch mask
                    return mask
            """,
        )
        assert report.clean
        assert len(report.suppressed) == 1


# ---------------------------------------------------------------------
# 1c-bis. ledger-unregistered (ISSUE 13: HBM the ledger cannot see)
# ---------------------------------------------------------------------


class TestLedgerUnregistered:
    # The pre-ledger shape: a persistent device cache on self with no
    # memory-ledger component reading it — unattributed bytes in the
    # next TPU window instead of a named line in /debug/memory.
    HISTORICAL = """
        class Batcher:
            def __init__(self, engine):
                self.cache = engine.make_cache(4, 256)
    """

    def test_fires_on_unregistered_allocation(self, tmp_path):
        report = lint(
            tmp_path, "ggrmcp_tpu/serving/batching.py", self.HISTORICAL
        )
        assert rule_ids(report) == ["ledger-unregistered"]
        assert "self.cache" in report.findings[0].message
        assert "ISSUE 13" in report.findings[0].precedent

    def test_lambda_registration_passes(self, tmp_path):
        report = lint(
            tmp_path, "ggrmcp_tpu/serving/batching.py", """
            class Batcher:
                def __init__(self, engine):
                    self.cache = engine.make_cache(4, 256)
                    engine.ledger.register(
                        "kv_arena", lambda: self.cache
                    )
            """,
        )
        assert report.clean

    def test_method_supplier_registration_passes(self, tmp_path):
        # One indirection hop: register("weights", self._supplier)
        # scans the supplier method's body (the engine's real shape).
        report = lint(
            tmp_path, "ggrmcp_tpu/serving/engine2.py", """
            class Engine:
                def __init__(self):
                    self.params = _sharded_init(init, None, None)
                    self.ledger.register("weights", self._weights)

                def _weights(self):
                    return [self.params]
            """,
        )
        assert report.clean

    def test_host_numpy_and_other_dirs_exempt(self, tmp_path):
        # np arrays are HOST memory (the ledger partitions device
        # buffers); gateway modules are out of scope wholesale.
        report = lint(
            tmp_path, "ggrmcp_tpu/serving/batching.py", """
            import numpy as np

            class Batcher:
                def __init__(self):
                    self.cur_tokens = np.zeros((4,), np.int32)
            """,
        )
        assert report.clean
        report = lint(
            tmp_path, "ggrmcp_tpu/gateway/cachez.py", self.HISTORICAL
        )
        assert report.clean

    def test_flags_each_attr_once(self, tmp_path):
        # Rebuild paths reassign the same attribute; one component
        # registration covers them all, so one finding names them all.
        report = lint(
            tmp_path, "ggrmcp_tpu/serving/batching.py", """
            class Batcher:
                def __init__(self, engine):
                    self.cache = engine.make_cache(4, 256)

                def _rebuild(self):
                    self.cache = self.engine.make_cache(4, 256)
            """,
        )
        assert rule_ids(report) == ["ledger-unregistered"]

    def test_pragma_suppresses(self, tmp_path):
        report = lint(
            tmp_path, "ggrmcp_tpu/serving/batching.py", """
            class Batcher:
                def __init__(self, engine):
                    self.scratch = engine._snap_dev([0])  # graftlint: disable=ledger-unregistered -- fixture: transient debug scratch, freed next tick
            """,
        )
        assert report.clean
        assert len(report.suppressed) == 1

    # ISSUE 15 extension: the LoRA adapter arena's device factor rows
    # (serving/adapter_arena.py — jnp.zeros working set, row-updated
    # by dynamic loads) are exactly the persistent allocation the
    # ledger's `lora` component must see; the real class registers
    # through its register_ledger method (one indirection hop).
    def test_fires_on_unregistered_adapter_arena(self, tmp_path):
        report = lint(
            tmp_path, "ggrmcp_tpu/serving/adapter_arena2.py", """
            import jax.numpy as jnp

            class AdapterArena:
                def __init__(self, rows):
                    self.a_dev = jnp.zeros((2, rows + 1, 8, 4))
                    self.b_dev = jnp.zeros((2, rows + 1, 4, 16))
            """,
        )
        assert rule_ids(report) == [
            "ledger-unregistered", "ledger-unregistered"
        ]
        flagged = {f.message.split()[0] for f in report.findings}
        assert flagged == {"self.a_dev", "self.b_dev"}

    def test_adapter_arena_register_ledger_passes(self, tmp_path):
        # The shipped AdapterArena shape: allocations in __init__, the
        # supplier attached through a method the engine calls with its
        # ledger — the rule's one-indirection scan covers it.
        report = lint(
            tmp_path, "ggrmcp_tpu/serving/adapter_arena2.py", """
            import jax.numpy as jnp

            class AdapterArena:
                def __init__(self, rows):
                    self.a_dev = jnp.zeros((2, rows + 1, 8, 4))
                    self.b_dev = jnp.zeros((2, rows + 1, 4, 16))

                def register_ledger(self, ledger, scope=""):
                    ledger.register(
                        "lora", lambda: (self.a_dev, self.b_dev),
                        scope=scope,
                    )
            """,
        )
        assert report.clean

    # ISSUE 14 extension: host-pool buffers are byte-budgeted HOST
    # memory — outside jax.live_arrays(), so reconcile() can never
    # catch an unregistered pool. The rule's static complement covers
    # them: a HostPagePool on self must be readable by a
    # ledger.register_host supplier.
    def test_fires_on_unregistered_host_pool(self, tmp_path):
        report = lint(
            tmp_path, "ggrmcp_tpu/serving/batching.py", """
            from ggrmcp_tpu.serving.host_pool import HostPagePool

            class Batcher:
                def __init__(self, engine):
                    self.host_pool = HostPagePool(1 << 20)
            """,
        )
        assert rule_ids(report) == ["ledger-unregistered"]
        assert "self.host_pool" in report.findings[0].message

    def test_register_host_supplier_passes(self, tmp_path):
        report = lint(
            tmp_path, "ggrmcp_tpu/serving/batching.py", """
            from ggrmcp_tpu.serving.host_pool import HostPagePool

            class Batcher:
                def __init__(self, engine):
                    self.host_pool = HostPagePool(1 << 20)
                    engine.ledger.register_host(
                        "host_pool",
                        lambda: self.host_pool.memory_info(),
                    )
            """,
        )
        assert report.clean

    def test_host_pool_pragma_suppresses(self, tmp_path):
        report = lint(
            tmp_path, "ggrmcp_tpu/serving/scratch.py", """
            from ggrmcp_tpu.serving.host_pool import HostPagePool

            class Bench:
                def __init__(self):
                    self.pool = HostPagePool(1 << 20)  # graftlint: disable=ledger-unregistered -- fixture: bench-local pool, process exits after the phase
            """,
        )
        assert report.clean
        assert len(report.suppressed) == 1


# ---------------------------------------------------------------------
# 1d. async-hygiene (PR 2: swallowed CancelledError)
# ---------------------------------------------------------------------


class TestAsyncHygiene:
    # The PR 2 pre-fix discovery.close() shape: cancel the task, await
    # it, and swallow everything — including the CancelledError aimed
    # at close() itself, wedging a cancelled shutdown half-closed.
    HISTORICAL = """
        class Discoverer:
            async def close(self):
                self._task.cancel()
                try:
                    await self._task
                except Exception:
                    pass
    """

    def test_fires_on_historical_pr2_shape(self, tmp_path):
        report = lint(tmp_path, "ggrmcp_tpu/rpc/discovery.py", self.HISTORICAL)
        assert rule_ids(report) == ["async-hygiene"]
        assert "CancelledError" in report.findings[0].message
        assert "PR 2" in report.findings[0].precedent

    def test_cancelled_arm_satisfies(self, tmp_path):
        # The PR 2 post-fix shape (including the conditional re-raise).
        report = lint(
            tmp_path, "ggrmcp_tpu/rpc/discovery.py", """
            import asyncio

            class Discoverer:
                async def close(self):
                    self._task.cancel()
                    try:
                        await self._task
                    except asyncio.CancelledError:
                        if not self._task.cancelled():
                            raise
                    except Exception:
                        pass
            """,
        )
        assert report.clean

    def test_reraise_satisfies_and_sync_exempt(self, tmp_path):
        report = lint(
            tmp_path, "ggrmcp_tpu/gateway/app.py", """
            import logging

            class App:
                async def step(self):
                    try:
                        await self.work()
                    except Exception:
                        logging.exception("step failed")
                        raise

                def sync_step(self):
                    try:
                        self.work_sync()
                    except Exception:
                        pass
            """,
        )
        assert report.clean

    def test_awaitless_try_exempt(self, tmp_path):
        # Broad handlers around pure host code in a coroutine can't
        # swallow a cancellation delivered at an await point.
        report = lint(
            tmp_path, "ggrmcp_tpu/gateway/app.py", """
            class App:
                async def parse(self, raw):
                    try:
                        return int(raw)
                    except Exception:
                        return None
            """,
        )
        assert report.clean

    def test_fires_on_blocking_call(self, tmp_path):
        report = lint(
            tmp_path, "ggrmcp_tpu/serving/launcher.py", """
            import time

            async def backoff():
                time.sleep(0.5)
            """,
        )
        assert rule_ids(report) == ["async-hygiene"]
        assert "blocks the event loop" in report.findings[0].message

    def test_pragma_suppresses(self, tmp_path):
        report = lint(
            tmp_path, "ggrmcp_tpu/rpc/discovery.py", """
            class Discoverer:
                async def close(self):
                    try:
                        await self._task
                    # graftlint: disable=async-hygiene -- fixture: owner-side swallow after its own cancel()
                    except Exception:
                        pass
            """,
        )
        assert report.clean
        assert len(report.suppressed) == 1


# ---------------------------------------------------------------------
# 1e. proto-drift (PR 3: hand-synced descriptor lists)
# ---------------------------------------------------------------------

PROTO_FIXTURE = """
syntax = "proto3";

message ServingStatsResponse {
  int32 active_slots = 1;
  int64 fresh_counter = 2;
  string mesh_shape = 3;
  repeated double latency_bucket_bounds_ms = 4;
  repeated int64 ttft_ms_bucket = 5;
  double ttft_ms_sum = 6;
  int64 ttft_ms_count = 7;
}
"""


class TestProtoDrift:
    def write_tree(self, tmp_path, metrics_src: str):
        (tmp_path / "protos").mkdir(parents=True, exist_ok=True)
        (tmp_path / "protos" / "serving.proto").write_text(PROTO_FIXTURE)
        path = tmp_path / "ggrmcp_tpu" / "gateway" / "metrics.py"
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(metrics_src))
        return run(tmp_path)

    def test_fires_on_missing_and_stale_entries(self, tmp_path):
        # The PR 3 failure class, both directions: a proto field the
        # descriptors never learned about, and a descriptor naming a
        # field the proto no longer has.
        report = self.write_tree(
            tmp_path, """
            _SERVING_HELP = {
                "active_slots": "decode slots generating",
                "retired_field": "gone from the proto",
            }
            _SERVING_HIST_HELP = {"ttft_ms": "time to first token"}
            """,
        )
        assert rule_ids(report) == ["proto-drift", "proto-drift"]
        messages = " | ".join(f.message for f in report.findings)
        assert "fresh_counter" in messages
        assert "retired_field" in messages
        # String fields (mesh_shape) export info-style, histogram
        # members belong to the histogram — neither needs an entry.
        assert "mesh_shape" not in messages
        assert "ttft_ms_sum" not in messages

    def test_complete_descriptors_clean(self, tmp_path):
        report = self.write_tree(
            tmp_path, """
            _SERVING_HELP = {
                "active_slots": "decode slots generating",
                "fresh_counter": "a documented counter",
            }
            _SERVING_HIST_HELP = {"ttft_ms": "time to first token"}
            """,
        )
        assert report.clean

    def test_pragma_suppresses(self, tmp_path):
        report = self.write_tree(
            tmp_path, """
            _SERVING_HELP = {  # graftlint: disable=proto-drift -- fixture: descriptor completion staged in a follow-up
                "active_slots": "decode slots generating",
            }
            _SERVING_HIST_HELP = {"ttft_ms": "time to first token"}
            """,
        )
        assert report.clean
        assert len(report.suppressed) == 1


TICK_PROTO_FIXTURE = PROTO_FIXTURE + """
message TickRecord {
  int64 seq = 1;
  double duration_ms = 2;
  double phase_wait_ms = 3;
  repeated string trace_ids = 4;
  string source = 5;
}
"""

_COMPLETE_SERVING = """
_SERVING_HELP = {
    "active_slots": "decode slots generating",
    "fresh_counter": "a documented counter",
}
_SERVING_HIST_HELP = {"ttft_ms": "time to first token"}
"""


class TestTickRecordDrift:
    """The proto-drift family extended to the per-tick surface (the
    tick ring → /debug/ticks → unified timeline): every scalar numeric
    TickRecord field must be named in metrics.py's _TICK_HELP, stale
    entries flagged — so the timeline cannot silently drift from the
    proto."""

    def write_tree(self, tmp_path, metrics_src: str, proto: str):
        (tmp_path / "protos").mkdir(parents=True, exist_ok=True)
        (tmp_path / "protos" / "serving.proto").write_text(proto)
        path = tmp_path / "ggrmcp_tpu" / "gateway" / "metrics.py"
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(metrics_src))
        return run(tmp_path)

    def test_fires_on_missing_and_stale_tick_entries(self, tmp_path):
        report = self.write_tree(
            tmp_path,
            _COMPLETE_SERVING + """
_TICK_HELP = {
    "seq": "tick sequence number",
    "duration_ms": "attributed tick time",
    "retired_phase_ms": "gone from the proto",
}
""",
            TICK_PROTO_FIXTURE,
        )
        assert rule_ids(report) == ["proto-drift", "proto-drift"]
        messages = " | ".join(f.message for f in report.findings)
        # The phase field added without a descriptor, and the stale
        # descriptor naming a retired field — both directions.
        assert "phase_wait_ms" in messages
        assert "retired_phase_ms" in messages
        # Repeated and string TickRecord fields carry no help contract.
        assert "trace_ids" not in messages
        assert "'source'" not in messages

    def test_complete_tick_descriptors_clean(self, tmp_path):
        report = self.write_tree(
            tmp_path,
            _COMPLETE_SERVING + """
_TICK_HELP = {
    "seq": "tick sequence number",
    "duration_ms": "attributed tick time",
    "phase_wait_ms": "device wait + transfer",
}
""",
            TICK_PROTO_FIXTURE,
        )
        assert report.clean

    def test_missing_tick_dict_is_a_finding(self, tmp_path):
        report = self.write_tree(
            tmp_path, _COMPLETE_SERVING, TICK_PROTO_FIXTURE
        )
        assert rule_ids(report) == ["proto-drift"]
        assert "_TICK_HELP" in report.findings[0].message

    def test_proto_without_tick_message_opts_out(self, tmp_path):
        # Fixture trees whose proto has no TickRecord (the pre-phase
        # shape) carry no _TICK_HELP contract.
        report = self.write_tree(
            tmp_path, _COMPLETE_SERVING, PROTO_FIXTURE
        )
        assert report.clean


# ---------------------------------------------------------------------
# 2. Pragma self-policing
# ---------------------------------------------------------------------


class TestPragmaMechanism:
    DIRTY = """
        import jax

        def sample(logits, key):
            return jax.random.categorical(key, logits){pragma}
    """

    def make(self, tmp_path, pragma: str):
        return lint(
            tmp_path, "ggrmcp_tpu/ops/sampling.py",
            self.DIRTY.format(pragma=pragma),
        )

    def test_missing_justification_is_a_finding(self, tmp_path):
        report = self.make(
            tmp_path, "  # graftlint: disable=sharded-sampling"
        )
        # The target finding is suppressed, but the naked pragma itself
        # gates — the tree stays red until the why is written down.
        assert rule_ids(report) == [META_MISSING]
        assert len(report.suppressed) == 1

    def test_empty_justification_is_a_finding(self, tmp_path):
        report = self.make(
            tmp_path, "  # graftlint: disable=sharded-sampling --"
        )
        assert rule_ids(report) == [META_MISSING]

    def test_stale_pragma_is_a_cleanup_finding(self, tmp_path):
        report = lint(
            tmp_path, "ggrmcp_tpu/ops/clean.py", """
            def add(a, b):
                return a + b  # graftlint: disable=sharded-sampling -- nothing fires here any more
            """,
        )
        assert rule_ids(report) == [META_STALE]
        assert "cleanup candidate" in report.findings[0].message

    def test_unknown_rule_is_a_finding(self, tmp_path):
        report = lint(
            tmp_path, "ggrmcp_tpu/ops/clean.py", """
            def add(a, b):
                return a + b  # graftlint: disable=no-such-rule -- typo'd id must not silently no-op
            """,
        )
        assert rule_ids(report) == [META_UNKNOWN]

    def test_standalone_pragma_covers_next_line(self, tmp_path):
        report = lint(
            tmp_path, "ggrmcp_tpu/ops/sampling.py", """
            import jax

            def sample(logits, key):
                # graftlint: disable=sharded-sampling -- fixture: standalone-line form
                return jax.random.categorical(key, logits)
            """,
        )
        assert report.clean
        assert len(report.suppressed) == 1

    def test_pragma_only_suppresses_named_rule(self, tmp_path):
        report = self.make(
            tmp_path,
            "  # graftlint: disable=alloc-in-jit -- wrong rule named",
        )
        # sharded-sampling still fires; the alloc-in-jit pragma is stale.
        assert sorted(rule_ids(report)) == [META_STALE, "sharded-sampling"]


# ---------------------------------------------------------------------
# 3. Self-enforcement + CLI + security-scan smoke
# ---------------------------------------------------------------------


class TestSelfEnforcement:
    def test_repo_tree_has_zero_unsuppressed_findings(self):
        """THE gate: the serving plane's own tree must stay clean. A
        red here means a new finding landed without a fix or a
        justified pragma — see docs/static_analysis.md before adding
        either."""
        report = run(REPO)
        assert report.clean, "\n" + report.render()
        # Every suppression in the tree carries its written-down why.
        for _finding, pragma in report.suppressed:
            assert pragma.justification, (
                f"{pragma.path}:{pragma.line} pragma lacks justification"
            )

    def test_cli_exit_codes_and_catalog(self, tmp_path):
        # `make graftlint` contract: rc 0 on the clean repo tree...
        clean = subprocess.run(
            [sys.executable, "-m", "ggrmcp_tpu.analysis"],
            cwd=REPO, capture_output=True, text=True, check=False,
        )
        assert clean.returncode == 0, clean.stdout + clean.stderr
        assert "0 unsuppressed" in clean.stdout
        # ...rc 1 on a dirty tree...
        bad = tmp_path / "ggrmcp_tpu" / "ops"
        bad.mkdir(parents=True)
        (bad / "bad.py").write_text(
            "import jax\n\n"
            "def f(key, logits):\n"
            "    return jax.random.categorical(key, logits)\n"
        )
        dirty = subprocess.run(
            [sys.executable, "-m", "ggrmcp_tpu.analysis",
             "--root", str(tmp_path)],
            cwd=REPO, capture_output=True, text=True, check=False,
        )
        assert dirty.returncode == 1
        assert "sharded-sampling" in dirty.stdout
        assert "precedent:" in dirty.stdout  # findings cite their bug
        # ...and the catalog lists every family with its precedent.
        catalog = subprocess.run(
            [sys.executable, "-m", "ggrmcp_tpu.analysis", "--list-rules"],
            cwd=REPO, capture_output=True, text=True, check=False,
        )
        assert catalog.returncode == 0
        for rid in (
            "sharded-sampling", "unsharded-transfer", "alloc-in-jit",
            "async-hygiene", "proto-drift",
        ):
            assert rid in catalog.stdout


class TestSecurityScanSmoke:
    """scripts/security_scan.py must keep tripping — run the real
    scanner over a fixture tree with one planted HIGH finding and
    assert the gate goes red (and green without it), so the scanner
    itself can't silently rot out of the CI lineup."""

    def run_scan(self, root: pathlib.Path):
        return subprocess.run(
            [sys.executable, str(REPO / "scripts" / "security_scan.py"),
             "--root", str(root)],
            capture_output=True, text=True, check=False,
        )

    def test_planted_high_finding_trips_the_gate(self, tmp_path):
        pkg = tmp_path / "ggrmcp_tpu"
        pkg.mkdir()
        (pkg / "bad.py").write_text(
            "import os\n\n\ndef run(cmd):\n    os.system(cmd)\n"
        )
        proc = self.run_scan(tmp_path)
        assert proc.returncode != 0, proc.stdout
        assert "os-system" in proc.stdout
        assert "FAIL" in proc.stdout

    def test_clean_fixture_passes(self, tmp_path):
        pkg = tmp_path / "ggrmcp_tpu"
        pkg.mkdir()
        (pkg / "ok.py").write_text("def add(a, b):\n    return a + b\n")
        proc = self.run_scan(tmp_path)
        assert proc.returncode == 0, proc.stdout
        assert "PASS" in proc.stdout
