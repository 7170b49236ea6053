"""Continuous batching for the generation engine.

The throughput layer (SURVEY.md §7 stage 6): a fixed pool of decode
slots shares one KV cache; requests are admitted into free slots via a
single-sequence prefill whose cache rows are scattered into the shared
cache, and every loop tick runs ONE batched decode step for all active
slots — new requests join between ticks without stalling running ones.
Per-slot sampling params and seeds ride as device arrays through the
dynamic sampling path (ops/sampling.py::sample_dynamic).

No reference analogue: the Go gateway proxied one RPC per call. This is
the component that turns 64 concurrent MCP sessions into full TPU
batches (the north-star saturation target).
"""

from __future__ import annotations

import asyncio
import dataclasses
import logging
import threading
import time
from collections import deque
from typing import AsyncIterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ggrmcp_tpu.core.config import (
    BatchingConfig,
    GrammarConfig,
    resolve_decode_steps,
    short_tick_steps,
)
from ggrmcp_tpu.grammar.compiler import CompiledGrammar
from ggrmcp_tpu.grammar.runtime import GrammarArena, GrammarHandle
from ggrmcp_tpu.models import llama as llama_mod
from ggrmcp_tpu.ops import quant
from ggrmcp_tpu.ops.sampling import (
    SamplingConfig,
    forced_run_lookup,
    masked_sample_dynamic,
    sample_dynamic,
)
from ggrmcp_tpu.serving.adapter_arena import AdapterExhaustedError
from ggrmcp_tpu.serving.engine import bucket_len, fit_request
from ggrmcp_tpu.serving import tensors
from ggrmcp_tpu.serving.flight_recorder import (
    PHASE_NAMES,
    FlightRecorder,
    PhaseTimer,
)
from ggrmcp_tpu.serving.pages import (
    PageAllocator,
    PageExhaustedError,
    WindowPages,
    window_pages_per_slot,
)
from ggrmcp_tpu.serving.scheduler import (
    Scheduler,
    SchedulerQueue,
    retry_after_for,
)
from ggrmcp_tpu.serving.slo import SloAccount, TenantTable
from ggrmcp_tpu.utils import failpoints, tracing

logger = logging.getLogger("ggrmcp.serving.batching")


class KVTransferError(RuntimeError):
    """A KV page export/import that cannot proceed (paging off, no
    indexed pages, geometry/dtype mismatch). Typed so the TransferKV
    plane degrades loudly: the sidecar maps it to a non-OK status and
    the gateway retries the request on a mixed replica — never a
    silent recompute dressed up as a successful transfer."""


class OverloadedError(RuntimeError):
    """submit() refused a request because the admission queue is at its
    configured cap (batching.max_pending / max_queue_tokens). The
    sidecar maps this to gRPC RESOURCE_EXHAUSTED and the gateway to
    HTTP 429 with Retry-After — shedding at the front door is the
    overload contract; the queue never grows without bound."""

    def __init__(self, message: str, reason: str, retry_after_s: float = 1.0):
        super().__init__(message)
        self.reason = reason  # "requests" | "tokens"
        self.retry_after_s = retry_after_s


class _PendingQueue:
    """Admission queue with request- and token-depth accounting.

    asyncio.Queue can neither report queued prompt tokens (the
    max_queue_tokens cap and the queued_tokens gauge), sweep expired
    entries, nor requeue a tick-failure victim at the FRONT — so the
    pending queue is a deque owned by this class. Single async
    consumer (the batcher loop); every method is event-loop-thread
    only, like the rest of the batcher's host state."""

    def __init__(self) -> None:
        self._items: deque = deque()
        self._tokens = 0
        self._event = asyncio.Event()

    def put_nowait(self, request: "_Request") -> None:
        self._items.append(request)
        self._tokens += len(request.prompt)
        self._event.set()

    def requeue_front(self, request: "_Request") -> None:
        """Head-of-queue insert for replayed requests: they were
        already admitted once and must not wait behind the backlog
        (or shed — replays bypass the caps by design)."""
        self._items.appendleft(request)
        self._tokens += len(request.prompt)
        self._event.set()

    def _pop(self) -> "_Request":
        request = self._items.popleft()
        self._tokens -= len(request.prompt)
        return request

    def get_nowait(self) -> "_Request":
        if not self._items:
            raise asyncio.QueueEmpty
        return self._pop()

    async def get(self) -> "_Request":
        # Single-consumer wait: no await between the emptiness check
        # and clear(), so a concurrent put's set() cannot be lost.
        while not self._items:
            self._event.clear()
            await self._event.wait()
        return self._pop()

    def qsize(self) -> int:
        return len(self._items)

    def empty(self) -> bool:
        return not self._items

    @property
    def token_count(self) -> int:
        return self._tokens


def _merge_row(cache, mini, slot, length):
    """Merge a single prefilled row's [1, S] K/V block into the shared
    [B, S_max] cache at `slot` and set that row's length. The one
    cache-merge definition shared by fused and chunked admission.
    kv_map keeps it working for int8 KV (values + scales merge
    identically; both index leading axes only)."""

    def merge(c, m):
        return jax.lax.dynamic_update_slice(
            c, m.astype(c.dtype), (0, slot, 0, 0, 0)
        )

    return llama_mod.map_planes(
        merge, cache, mini, length=cache.length.at[slot].set(length))


@dataclasses.dataclass
class _Slot:
    active: bool = False
    request: Optional["_Request"] = None
    generated: int = 0
    max_new: int = 0
    done: bool = False
    # Held by an interleaved (chunk-at-a-time) admission in progress:
    # not yet decoding, but not free either — _free_slots skips it
    # until the final chunk lands and _activate_slot flips it active.
    reserved: bool = False


@dataclasses.dataclass
class _IlvRow:
    """One admitting row of the interleave mini cache: host-side
    progress for a long prompt advancing one [1, C] chunk per fused
    tick+chunk call (prefill_interleave=on)."""

    request: "_Request"
    slot: int
    n: int  # prompt length
    progress: int = 0  # tokens already written into the mini row


@dataclasses.dataclass
class _Request:
    prompt: list[int]
    max_new: int
    sampling: SamplingConfig
    seed: int
    out: asyncio.Queue = dataclasses.field(default_factory=asyncio.Queue)
    cancelled: bool = False
    # Unary consumers want ONE terminal chunk: per-tick emission costs
    # a cross-thread call_soon_threadsafe + queue put + consumer wakeup
    # per slot per tick — at batch 16 that is 16x the loop events the
    # result needs. Tokens accumulate in `acc` (executor-thread-only
    # until the terminal emit) and post once on finish. `acc` holds
    # EVERY emitted token for streaming consumers too: it is the
    # replay prefix after a tick failure (the re-admission prefills
    # prompt + acc, so the consumer never sees a duplicate token).
    unary: bool = False
    acc: list[int] = dataclasses.field(default_factory=list)
    # Tick-failure replay bookkeeping: retries burned against
    # batching.tick_retry_limit, and how many acc tokens have already
    # been folded into `prompt` by previous replays (a second failure
    # must only absorb the tokens emitted since the first).
    retries: int = 0
    absorbed: int = 0
    # LoRA adapter row id (0 = base model; ops/lora.py).
    adapter: int = 0
    # STABLE adapter identity for KV keying (the adapter NAME, "" =
    # base): arena rows are reused after eviction, so page hash-chain
    # domains key on this, never on the row id (serving/pages.py).
    adapter_key: str = ""
    # Arena residency pin (serving/adapter_arena.py AdapterLease, None
    # = static mode or base row): held until the terminal chunk —
    # _record_terminal releases it on every terminal path, exactly
    # like the grammar handle — so churn eviction can never rewrite a
    # row an in-flight request is decoding under.
    adapter_lease: object = None
    # Latency accounting (perf_counter seconds): submit → activation
    # is queue time, split at t_pop — the pop that put the request into
    # its admission batch (the LAST one, when a prefill budget, a
    # replay or a preemption requeued it): submit → pop is time spent
    # pending, pop → activation the executor hand-off plus the
    # admission program (flight_recorder pending_ms / prefill_ms).
    t_submit: float = 0.0
    t_pop: float = 0.0
    t_admit: float = 0.0
    # Flight-recorder lifecycle (serving/flight_recorder.py): the
    # gateway trace id this request decodes under (join key into the
    # span and tick rings), the first-token stamp TTFT derives from,
    # the original (pre-replay-fold) prompt length, and the first tick
    # seq this request decoded in (-1 = never admitted).
    trace_id: str = ""
    t_first: float = 0.0
    n_prompt: int = 0
    first_tick: int = -1
    # Schema-constrained decoding (ggrmcp_tpu/grammar): the live arena
    # residency (None = unconstrained), the row's current ABSOLUTE DFA
    # state for host-side sink detection (advanced per emitted token in
    # _emit_chunk), and whether the arena reference was already
    # released (terminal paths can be re-entered under races).
    grammar: Optional[GrammarHandle] = None
    gcur: int = 0
    g_released: bool = False
    # Jump-ahead degrade flag (docs/structured_output.md "Jump-ahead"):
    # set when the collect-side validator refused one of this request's
    # forced runs (grammar_jump_fail chaos / corrupted tables). The
    # replayed request re-admits with jump_ok False and finishes under
    # plain one-token constrained decoding — typed, counted, never
    # silent.
    jump_degraded: bool = False
    # Tenant & SLO identity (serving/slo.py): who this request belongs
    # to and which QoS class judges it at the terminal chunk. With the
    # scheduler off this stays pure accounting; scheduler on, it also
    # keys the priority lane and fair-share order (serving/scheduler).
    tenant: str = ""
    qos_class: str = ""
    # Preemption bookkeeping (serving/scheduler.py): how many times
    # this request was demoted-and-parked (routes the re-put into the
    # resume lane; preemption does NOT burn a tick retry — the fold is
    # the same, the cause is policy, not failure), and how many resume
    # attempts died on adapter-arena pressure (bounded by
    # scheduler.resume_retry_limit before a typed shed).
    preempts: int = 0
    sched_retries: int = 0
    # True while demoted-and-parked (set at park, cleared at the
    # resuming activation): pairs every `sched_resumes` increment with
    # exactly one preemption even when a tick-failure replay re-admits
    # the same request in between.
    parked: bool = False


class ContinuousBatcher:
    """Slot-based continuous batching over a shared KV cache."""

    # counter_stats() keys that aggregate by MAX across tiers, not sum
    # (serving/tiered.py::TieredBatcher.stats). The mesh identity keys
    # are engine-level facts every tier shares — max of identical
    # values (strings included: mesh_shape) reports them once instead
    # of summing a constant per tier.
    MAX_STAT_KEYS = (
        "tp_chips", "mesh_devices", "mesh_shape",
        "mesh_spec_downgrades",
        # Engine-level memory-ledger components: every tier reads the
        # same process-wide weight/LoRA arrays — max of identical
        # values reports them once instead of summing a constant per
        # tier (the per-tier components below them sum as usual).
        "memory_weights_bytes", "memory_lora_bytes",
        # Adapter-arena counters are ENGINE-level (one arena per
        # process, every tier resolves against it): max of identical
        # snapshots, never a per-tier sum of the same counter.
        "lora_adapters_registered", "lora_adapters_resident",
        "lora_rows_total", "lora_loads", "lora_evictions", "lora_hits",
        "lora_load_ms", "lora_shed",
    )

    def __init__(
        self,
        engine,  # GenerationEngine
        cfg: Optional[BatchingConfig] = None,
        eos_id: int = 2,
        ledger_scope: str = "",
    ):
        self.engine = engine
        self.cfg = cfg or BatchingConfig()
        self.eos_id = eos_id
        self.slots = [_Slot() for _ in range(self.cfg.max_batch_size)]
        # Bounded admission queue (batching.max_pending /
        # max_queue_tokens caps enforced in submit()).
        self.pending = _PendingQueue()
        # True while a call that donates the SHARED cache is in flight
        # (set just before, cleared after self.cache is reassigned);
        # admission-failure handling rebuilds the cache only when set.
        self._cache_at_risk = False
        self._task: Optional[asyncio.Task] = None
        self._wake = asyncio.Event()
        self._stopping = False
        # Held by whichever executor call of the loop is running
        # (_in_executor); stop() takes it once to wait that call out.
        self._exec_lock = threading.Lock()

        b = self.cfg.max_batch_size
        platform = engine.mesh.devices.flat[0].platform
        self._steps_per_tick = resolve_decode_steps(self.cfg, platform)
        # Pipelined ticks: tick N+1 is dispatched (device-resident token
        # feedback) before tick N's tokens are pulled to the host, so the
        # host round-trip overlaps its compute. A slot can then overshoot
        # its budget by up to one EXTRA tick before the host notices
        # EOS/max_new: the reserve doubles. "auto": only on an accelerator.
        mode = getattr(self.cfg, "pipeline_ticks", "off")
        self._pipeline = mode == "on" or (mode == "auto" and platform == "tpu")
        # The short tick (_tick_steps) is the pipelined loop's alone.
        short = short_tick_steps(self._steps_per_tick)
        self._short_steps = short if self._pipeline else self._steps_per_tick
        # Jump-ahead constrained decoding (serving.grammar.jump_max,
        # docs/structured_output.md "Jump-ahead"): when a slot's DFA
        # state forces a token run, the tick emits up to jump_max
        # forced tokens plus one sampled token in ONE multi-position
        # forward. The per-tick advance bound widens to 1 + jump_max
        # for grammar-carrying requests, so THEIR overshoot reserve
        # (fit_request and the whole-lifetime paged admission extent)
        # re-derives from it — forced-run KV writes land in positions
        # the slot already owns. Unconstrained requests keep the plain
        # steps_per_tick reserve: they can never jump, and widening
        # pool-wide would tax every workload's cache capacity for a
        # window only constrained rows use (their surplus positions in
        # a jump tick are junk that the write path's sentinel/OOB drop
        # semantics discard — see models/llama.py paged scatter).
        # Ring mode is out: its clobber bound was sized for the prefill
        # chunk, not a decode-side window.
        gcfg = getattr(engine.serving, "grammar", None) or GrammarConfig()
        jump_max = (
            max(0, int(getattr(gcfg, "jump_max", 0))) if gcfg.enabled else 0
        )
        if jump_max and engine.ring_capacity is not None:
            logger.warning(
                "grammar.jump_max > 0 does not compose with kv_ring; "
                "falling back to one-token constrained decoding"
            )
            jump_max = 0
        if jump_max and getattr(engine, "fam", llama_mod) is not llama_mod:
            # MoE routing is batch-global: junk window positions past a
            # row's run would compete for expert capacity and perturb
            # live rows.
            logger.warning(
                "grammar.jump_max > 0 is dense-family only; falling "
                "back to one-token constrained decoding"
            )
            jump_max = 0
        self._jump_max = jump_max
        advance = self._steps_per_tick
        jump_advance = max(advance, 1 + self._jump_max)
        self._reserve = (
            2 * advance - 1 if self._pipeline else advance - 1
        )
        # Per-request widened twin of _reserve (== _reserve when jump
        # is off): _reserve_for picks between them by grammar presence
        # at every fit/clamp/admission site.
        self._jump_reserve = (
            2 * jump_advance - 1 if self._pipeline else jump_advance - 1
        )
        # In-flight dispatched-not-yet-collected ticks, oldest first:
        # (tokens [B, steps] device array, per-slot owner snapshot).
        self._inflight: deque = deque()
        # Serialized host-op queue (run_host_op): (fn, future) pairs the
        # loop drains between ticks in its ONE executor stream — the
        # entry point for work that must not interleave with admissions
        # or ticks (KV page export/import for the TransferKV plane,
        # docs/paged_kv.md). Futures resolve on the loop.
        self._host_ops: deque = deque()
        # Ring-buffer serving (engine.ring_capacity, sliding-window
        # models): the cache holds window + prefill_chunk - 1 positions
        # and request length is bounded by the RoPE range, not the
        # cache. Short prompts keep the fused admission (a fresh mini
        # never wraps, so its contiguous layout IS the ring layout);
        # prompts past prefill_chunk take the chunked path as usual.
        self._ring = engine.ring_capacity is not None
        if self._ring:
            engine_chunk = engine.serving.batching.prefill_chunk
            if self.cfg.prefill_chunk > engine_chunk:
                # The capacity was sized for the ENGINE config's chunk;
                # a wider batcher chunk would violate the trace-time
                # clobber bound mid-admission. Fail fast and clearly.
                raise ValueError(
                    f"batcher prefill_chunk ({self.cfg.prefill_chunk}) "
                    f"exceeds the ring engine's ({engine_chunk}); the "
                    f"ring capacity was sized for the engine's chunk"
                )
            s_max = engine.ring_capacity
            self._fit_limit = engine.cfg.max_seq_len
        else:
            s_max = min(self.cfg.kv_cache_max_seq, engine.cfg.max_seq_len)
            self._fit_limit = s_max
        self.max_seq = s_max
        # Paged KV plane (batching.paged_kv=on, docs/paged_kv.md): the
        # shared cache becomes one page ARENA + per-slot block tables
        # (models/llama.py::PagedKVCache) and a host-side refcounted
        # allocator (serving/pages.py) gives token-level, page-aligned
        # prefix sharing with copy-on-write at the divergent page — the
        # one prefix-reuse mechanism. The contiguous path stays the
        # off-mode so bit-identity is provable (tests/test_paged_kv.py).
        self._paged = getattr(self.cfg, "paged_kv", "off") == "on"
        # Keys the window layers' decode steps read, and keys their
        # rows hold in context (_window_release; 0 without such layers).
        self.window_keys = {"read": 0, "context": 0}
        # ROW_STATE (the family's module says so, as for the flags
        # below): every row keeps a state that no position addresses,
        # in a pool that rides the cache (`cache.state`: the slots'
        # entries, then the snapshots'). Admission programs restore,
        # carry and capture it (`_state_io`); serving/pages.py owns
        # which snapshot hangs where (docs/paged_kv.md "State beside
        # pages").
        self._row_state = bool(
            getattr(getattr(engine, "fam", None), "ROW_STATE", False))
        # A pool entry no pool has: a read of it clips, a write drops.
        self._no_entry = llama_mod.STATE_ENTRIES_PER_SLOT * b
        if self._paged:
            # config.validate mirrors these; batchers built directly in
            # tests must hit the same walls.
            if self._ring:
                raise ValueError("paged_kv does not compose with kv_ring")
            page = max(1, int(getattr(self.cfg, "paged_kv_page_size", 16)))
            if s_max % page:
                raise ValueError(
                    f"paged_kv_page_size ({page}) must divide the cache "
                    f"max_seq ({s_max})"
                )
            self._page_size = page
            self._table_width = s_max // page
            self._n_pages = (
                int(getattr(self.cfg, "paged_kv_pages", 0) or 0)
                or b * self._table_width
            )
            # A second kind of caching layer (`cfg.cache_kinds`: layers
            # that attend a window) keeps its pages in an arena of its
            # own, sized here from slots, window, chunk and page (no
            # option: `paged_kv_pages` stays the arena that keeps
            # everything), and serving/pages.py lets go of a row's
            # pages behind its window as it decodes (docs/paged_kv.md
            # "Two kinds of page"). The host maps a row's next pages
            # `lookahead` positions ahead of what it has seen the row
            # emit: the ticks in flight and the one being dispatched.
            kinds = getattr(engine.cfg, "cache_kinds", ((0, None),))
            self._window = kinds[1][1] if len(kinds) > 1 else None
            self._window_layers = kinds[1][0] if self._window else 0
            self._n_pages_w, window_pages = 0, None
            if self._window:
                lookahead = self._reserve + 1 + self._steps_per_tick
                per_slot = window_pages_per_slot(
                    self._window, self.cfg.prefill_chunk, page, lookahead)
                self._n_pages_w = b * per_slot
                window_pages = WindowPages(
                    self._n_pages_w, page, slots=b,
                    table_width=self._table_width, window=self._window,
                    per_slot=per_slot, lookahead=lookahead,
                    retain=self.cfg.prefill_chunk)
            self._window_dirty = False
            self.pages = PageAllocator(
                self._n_pages, page, slots=b,
                table_width=self._table_width,
                state_entries=(
                    (llama_mod.STATE_ENTRIES_PER_SLOT - 1) * b
                    if self._row_state else 0),
                window=window_pages,
            )
            self._tables_dirty = False
            self.cache = self._make_shared_cache()
            # Host-tier page pool (batching.paged_kv_host_bytes > 0,
            # docs/paged_kv.md "Host tier"): arena eviction demotes
            # page contents D2H into this byte-budgeted host pool and
            # admission restores demoted prefixes H2D instead of
            # recomputing them. The allocator owns placement; the two
            # hooks below are its device halves (gather+pack /
            # unpack+write), both running inside this batcher's
            # serialized executor stream.
            host_bytes = int(
                getattr(self.cfg, "paged_kv_host_bytes", 0) or 0
            )
            if host_bytes > 0:
                from ggrmcp_tpu.serving.host_pool import HostPagePool

                self.host_pool = HostPagePool(
                    host_bytes,
                    geometry=self._kv_page_geometry(),
                    file_path=(
                        getattr(self.cfg, "paged_kv_host_path", "") or ""
                    ),
                    file_budget_bytes=int(
                        getattr(self.cfg, "paged_kv_host_file_bytes", 0)
                        or 0
                    ),
                )
                self.pages.attach_host(
                    self.host_pool, self._demote_fetch,
                    self._restore_write,
                )
            else:
                self.host_pool = None
        else:
            self.pages = None
            self.host_pool = None
            self._window = None
            self.cache = engine.make_cache(b, s_max)
        if self._row_state:
            self._say_row_states()
        # Host-mirrored per-slot state, pushed to device each tick.
        # cur_tokens additionally keeps a DEVICE-resident twin
        # (_cur_dev): the tick feeds on the previous tick's last-step
        # tokens without a host round-trip; admission patches single
        # entries with eager .at[].set (async-dispatched, no sync). The
        # host mirror trails by a tick and only seeds rebuilds.
        self.cur_tokens = np.zeros((b,), np.int32)
        self._cur_dev = None  # lazily jnp.asarray(cur_tokens)
        # Grammar-constrained decoding (ggrmcp_tpu/grammar): per-slot
        # ABSOLUTE DFA state (0 = the arena's universal accept-all
        # state — unconstrained rows), with the same host-mirror /
        # device-twin split as cur_tokens: the tick feeds the previous
        # tick's output states back on device, admission patches single
        # entries eagerly, the mirror only seeds rebuilds. The arena's
        # [arena_states, V] allow/transition tables ride every sampling
        # call as FIXED-shape arguments, so a new schema never
        # recompiles the tick — it only re-uploads table contents
        # (_grammar_tables).
        self.gstates = np.zeros((b,), np.int32)
        self._gstate_dev = None
        self.arena = GrammarArena(
            gcfg.arena_states if gcfg.enabled else 2,
            engine.cfg.vocab_size,
            jump_max=self._jump_max,
        )
        self._g_allow_dev = None
        self._g_trans_dev = None
        self._g_jlen_dev = None
        self._g_jtok_dev = None
        self._g_jstate_dev = None
        self._g_dev_version = -1
        # Tokens emitted under an active grammar mask (the
        # grammar_masked_tokens ServingStats field).
        self.grammar_tokens = 0
        # Ticks the sampler's gates (ops/sampling.py) could not shorten,
        # counted at dispatch from what the tick is given: a live row
        # sampling under top-k/top-p (the sort runs), a live constrained
        # row (the grammar tables are read). Read against `ticks`.
        self.sampler_order_ticks = 0
        self.sampler_mask_ticks = 0
        # Jump-ahead accounting (grammar_jump_* ServingStats fields):
        # forced tokens emitted by multi-token advances, jump ticks
        # that advanced at least one run, and runs the collect-side
        # validator refused (grammar_jump_fail chaos / corrupted
        # tables) — each fallback degrades that request typed to plain
        # one-token constrained decoding, never silently.
        self.grammar_jump_tokens = 0
        self.grammar_jump_runs = 0
        self.grammar_jump_fallbacks = 0
        # Per-slot jump enable, stamped at activation like temps:
        # True only while the slot serves a constrained request that
        # has not been jump-degraded. Host array, shipped with each
        # jump dispatch — parked rows read False, so stale device
        # grammar states can never jump a dead slot's length pointer.
        self.jump_ok = np.zeros((b,), bool)
        self.temps = np.zeros((b,), np.float32)
        self.top_ks = np.zeros((b,), np.int32)
        self.top_ps = np.ones((b,), np.float32)
        self.seeds = np.zeros((b,), np.uint32)
        self.adapter_ids = np.zeros((b,), np.int32)  # per-slot LoRA row
        self.step_counter = 0

        # Model family (dense llama or sparse MoE) — same forward
        # contract; MoE additionally takes a validity mask so padding
        # and parked slots never compete for expert capacity.
        self.fam = getattr(engine, "fam", llama_mod)
        self._is_moe = self.fam is not llama_mod
        # What a family's module says its forward can do (the batcher
        # asks the module, never which family it is). HEAD_AT_INDEX:
        # the head at one position a row on request (`logit_idx`; a
        # [16, 512, V] logits block is 4 GB at a 128k vocabulary).
        # ROUTING_STATS: the names of the counts a step returns
        # (`with_stats`), which ride the tick's token array as one
        # extra row each (_decode_scan) and are summed at collect time
        # into `model_counts` under those names (the family's module
        # says what each one counts). DEEP_GRID_CHUNKS: a cold prompt of
        # more chunks than this is admitted alone, in arrival order
        # (_route_admission). None: every cold long prompt of a round
        # shares its group's call.
        self._head_at_index = getattr(self.fam, "HEAD_AT_INDEX", False)
        self._routing_stats = tuple(getattr(self.fam, "ROUTING_STATS", ()))
        self._deep_grid = getattr(self.fam, "DEEP_GRID_CHUNKS", None)
        # ARENA_BY_LAYER: the admission programs' scatter into and
        # gather from the whole arena (_paged_put, paged_view_layers)
        # go a layer at a time.
        self._arena_by_layer = getattr(self.fam, "ARENA_BY_LAYER", False)
        # admission_rows(cfg): the most rows one admission call over a
        # full-width mini cache (chunked, paged prefix reuse) may take
        # for this configuration; a family without it, or None, takes
        # the whole pool. Larger groups go in consecutive calls.
        rows_of = getattr(self.fam, "admission_rows", None)
        self._mini_rows = min(b, (rows_of(engine.cfg) if rows_of else None) or b)
        # Depth of every cold chunked admission's token grid: the
        # chunks of the longest prompt a request may bring. A constant,
        # so a prompt's depth shapes no program (_admit_chunked_group).
        self._grid_chunks = -(-self._fit_limit // min(
            self.cfg.prefill_chunk, self.max_seq))
        self.model_counts = dict.fromkeys(
            self._routing_stats + ("layer_steps",), 0)
        # Prompt tokens admission programs computed, and prompt tokens
        # they took from pages or a prefix entry instead; chunk_run:
        # the token positions of the chunk rows those programs ran
        # (_admission_program), computed / chunk_run their fill. All
        # three are stamped at a round's end (_prefill_into_slots).
        self.prefill_tokens = {"computed": 0, "reused": 0, "chunk_run": 0}

        # Admissions that reused shared pages, and those that could not
        # (the paged path stamps both).
        self.prefix_hits = 0
        self.prefix_misses = 0

        # Tick / collect / admission-round counts, the rounds settled
        # after a dispatch among them. All TIMES come from PhaseTimers.
        self.timing = dict.fromkeys((
            "ticks", "short_ticks", "collects", "admit_rounds", "admit_rounds_deferred"), 0)
        # Decode-stall histogram: wall-clock gaps (ms) between
        # consecutive token emissions to a slot while its request is
        # live — the per-slot observable the prefill-interleave mode
        # exists to bound (serialized long-prompt admission shows up
        # here as one full-prefill-sized gap on every active slot).
        self._stall_records: deque = deque(maxlen=4096)
        self._slot_last_emit: list = [None] * b
        # EMA of per-row admission cost, feeding the p50_budget_ms
        # admission cap (start pessimistic so a cold first round under
        # an SLO config stays small until measured).
        self._admit_ema_ms = 50.0
        self.timed_out = 0
        # Overload / replay accounting: requests refused at submit()
        # (OverloadedError), requests requeued with a replay prefix
        # after a failed tick, and requests that exhausted the
        # tick_retry_limit budget and surfaced "error".
        self.shed = 0
        self.replayed = 0
        self.replay_exhausted = 0
        # Flight recorder: per-tick + per-request rings and the
        # ttft/e2e/queue/tick-duration histograms
        # (serving.observability; the tiered facade stamps each tier's
        # recorder with a source label after construction).
        self.recorder = FlightRecorder(
            getattr(getattr(engine, "serving", None), "observability", None)
        )
        # Tenant & SLO accounting plane (serving/slo.py): per-class
        # goodput/burn + per-tenant VTC token attribution, fed from the
        # same terminal-chunk hook as the recorder's request ring. One
        # account per batcher (tiers own theirs; the tiered facade
        # merges exactly, like the latency histograms). Obs-off wins:
        # with the recorder disabled this plane stores and computes
        # nothing either.
        _slo_cfg = getattr(getattr(engine, "serving", None), "slo", None)
        self.slo = SloAccount(
            _slo_cfg,
            obs_enabled=self.recorder.enabled,
            bounds=self.recorder._bounds,
        )
        self.tenants = TenantTable(_slo_cfg, enabled=self.slo.enabled)
        # Preemptive SLO-aware scheduler (serving/scheduler.py): when
        # enabled, the FCFS pending queue is REPLACED by the priority +
        # fair-share SchedulerQueue (same interface — the admission
        # loop's control flow is untouched) and the policy object
        # decides demote-don't-kill preemption once per loop cycle.
        # Off (default): None, zero new work on any hot path.
        self.sched_cfg = getattr(
            getattr(engine, "serving", None), "scheduler", None
        )
        self.sched: Optional[Scheduler] = None
        if self.sched_cfg is not None and getattr(
            self.sched_cfg, "enabled", False
        ):
            self.sched = Scheduler(
                self.sched_cfg, slo=self.slo, tenants=self.tenants
            )
            self.pending = SchedulerQueue(
                self.sched_cfg, tenants=self.tenants
            )
        # Tick-phase attribution (flight_recorder.PhaseTimer):
        # cumulative per-phase ms over collected ticks (the ServingStats
        # tick_phase_*_ms scalars; summable across tiers), and two things
        # kept since the last dispatch: the executor admission time
        # (seeded into the NEXT tick's record as its admit phase) and the
        # chunk tokens the admission programs ran (_tick_steps reads it).
        self.phase_ms = dict.fromkeys(PHASE_NAMES, 0.0)
        self._admit_phase_ms, self._admit_run = 0.0, 0
        # The loop's turn, partitioned with nothing left over
        # (_in_executor): cumulative ms of the four contiguous parts of
        # every executor call — exec_wait (submitted → started on the
        # thread), work (started → ended; what the tick phases
        # divide), lag (ended → this coroutine running again) and host
        # (resumed → the next submission: loop-side python) — and the
        # number of calls. `_loop_resumed` is the open host interval's
        # start, None while the loop is parked with nothing to do, so
        # idle time belongs to no part.
        self.loop_ms = dict.fromkeys(
            ("exec_wait", "work", "lag", "host"), 0.0
        )
        self.loop_calls = 0
        self._loop_resumed: Optional[float] = None
        # What the admission round in progress ran (program families in
        # dispatch order, prompt tokens served from reused KV): filled
        # by the admission paths, read into its AdmissionRecord.
        self._adm_families: list[str] = []
        self._adm_reused = 0
        self._adm_chunk_run = 0
        # The admission round in progress (`_round`: its clock, its
        # spans' tags, the programs it queued; _prefill_into_slots opens
        # it) and the round whose rows are seated, first tokens still on
        # the device (`_seated`; _settle_round). Each a _SeatedRound.
        self._round = self._seated = None
        # slot -> (restore source entry, captures) of the rows of the
        # admission round in progress (_state_plan).
        self._state_rows: dict = {}

        # jitted: one decode tick for the whole slot pool (params ride
        # as an argument — a closed-over weight tree would be lowered
        # into the module as constants, bloating compiles and defeating
        # the persistent compile cache; see DecoderEngine.__init__)
        self._tick = jax.jit(self._tick_impl, donate_argnums=(2,), static_argnames="steps")
        # jitted admission — fused prefill + first-token sample + cache
        # merge, ONE device call per admission round. Exactly two row
        # shapes compile per sequence bucket (predictable cold-start):
        # a single-row program for steady-state trickle admissions and
        # a full-pool program for concurrent bursts.
        self._admit_single = jax.jit(
            self._admit_single_impl, donate_argnums=(3,)
        )
        self._admit_full = jax.jit(self._admit_full_impl, donate_argnums=(3,))
        # Fused chunked admission: the WHOLE multi-chunk prefill of an
        # admission group — mini-cache creation, lax.scan over [T, C]
        # chunk steps, per-row final-logit select, shared-cache merge,
        # first-token sample — in ONE device call instead of
        # ~(4 + chunks)·rows of them.
        self._admit_chunked = jax.jit(
            self._admit_chunked_impl, donate_argnums=(3,)
        )
        # Paged prefix-reuse admission: gather the shared-page view
        # into a fresh mini through a host-built gather table, run the
        # suffix grid from the (possibly CoW-advanced) scan start, and
        # merge only the exclusive-page positions back — ONE device
        # call admits a whole same-prefix wave without re-prefilling a
        # single shared page.
        if self._paged:
            self._admit_paged_pfx = jax.jit(
                self._admit_paged_pfx_impl, donate_argnums=(3,)
            )
        # Stall-free prefill/decode interleaving (prefill_interleave=
        # "on"): long prompts arriving mid-decode become per-tick chunk
        # work items instead of one serialized [T, C] grid call. Each
        # fused tick+chunk call runs the decode scan AND extends at
        # most one [K, C] chunk of the carried [K, S_max] mini cache
        # (per-row write offsets stamped host-side each call); the
        # final chunk's row scatters into the shared cache via
        # _ilv_finish (the _merge_row machinery) and activates the
        # slot. K = prefill_interleave_rows; further long prompts
        # queue in _ilv_pending holding a reserved slot.
        self._ilv_k = (
            max(1, int(getattr(self.cfg, "prefill_interleave_rows", 4)))
            if getattr(self.cfg, "prefill_interleave", "off") == "on"
            else 0
        )
        self._ilv_rows: list = [None] * self._ilv_k
        self._ilv_pending: deque = deque()
        self._ilv_mini = None  # lazily _make_mini(K, max_seq)
        self.interleaved_chunks = 0
        self.interleaved_admissions = 0
        self._tick_chunk = jax.jit(
            self._tick_chunk_impl, donate_argnums=(2, 11)
        )
        self._ilv_finish = jax.jit(
            self._ilv_finish_impl, donate_argnums=(0,)
        )
        # Jump-ahead tick programs (grammar.jump_max > 0,
        # docs/structured_output.md "Jump-ahead"): one decode forward
        # over a static [B, 1 + jump_max] window emits each row's
        # forced token run plus one sampled token — shape-invariant
        # across any schema mix (the window width is `jump_max`, a
        # constructor constant, never a data-dependent run length).
        # The chunk variant fuses one interleaved-admission prefill
        # chunk exactly like _tick_chunk does.
        if self._jump_max:
            self._tick_jump = jax.jit(
                self._tick_jump_impl, donate_argnums=(2,)
            )
            self._tick_jump_chunk = jax.jit(
                self._tick_jump_chunk_impl, donate_argnums=(2, 11)
            )
        # Device-memory ledger (serving/memory_ledger.py,
        # docs/observability.md): every persistent device allocation
        # this batcher owns registers a named component on the ENGINE's
        # ledger, scoped per tier, with suppliers reading the live
        # attributes — tick-failure rebuilds reassign self.cache etc.
        # and the next read sees the new arrays. The graftlint rule
        # `ledger-unregistered` holds future allocations to this.
        self._ledger_scope = ledger_scope
        engine.ledger.register(
            "kv_arena",
            lambda: (
                *llama_mod.cache_planes(self.cache), self.cache.length,
                *self.cache.state,
                *(getattr(self.cache, "window", None) or ())),
            scope=ledger_scope,
        )
        engine.ledger.register(
            "block_tables",
            lambda: getattr(self.cache, "table", None),
            scope=ledger_scope,
        )
        engine.ledger.register(
            "ilv_mini", lambda: self._ilv_mini, scope=ledger_scope
        )
        engine.ledger.register(
            "grammar_arena",
            lambda: (
                self._g_allow_dev, self._g_trans_dev,
                self._g_jlen_dev, self._g_jtok_dev, self._g_jstate_dev,
            ),
            scope=ledger_scope,
        )
        engine.ledger.register(
            "tick_state",
            lambda: (self._cur_dev, self._gstate_dev),
            scope=ledger_scope,
        )
        # Host-tier bytes are HOST memory — outside jax.live_arrays(),
        # so they ride the ledger's host-supplier side instead of the
        # device closure: /debug/memory renders them as the `host`
        # section beside the reconciliation.
        engine.ledger.register_host(
            "host_pool",
            lambda: (
                self.host_pool.memory_info()
                if self.host_pool is not None else None
            ),
            scope=ledger_scope,
        )

    def _make_mini(self, rows: int, length: int):
        """Admission mini cache matching the engine's KV storage."""
        return llama_mod.KVCache.create(
            self.engine.cfg, rows, length, self.engine.kv_dtype
        )

    def _make_shared_cache(self):
        """Fresh shared cache of this batcher's configured shape — the
        initial build and every tick-failure rebuild go through here so
        the paged and contiguous planes can't drift."""
        if self._paged:
            return self.engine.make_paged_cache(
                len(self.slots), self.max_seq, self._n_pages,
                self._page_size, window_pages=self._n_pages_w,
            )
        return self.engine.make_cache(len(self.slots), self.max_seq)

    # -- paged KV host/device glue (batching.paged_kv=on) -------------------

    def _sync_tables(self) -> None:
        """Upload the host block tables when they changed since the
        last device call. The tables are HOST state (serving/pages.py
        owns them); the device only ever sees snapshots — admissions
        map pages, finishes unmap them, and the next dispatch carries
        the new mapping. Replay after a tick failure re-MAPS this way
        too: the allocator state is rebuilt host-side and re-uploaded,
        never re-derived from device buffers.

        The snapshot is device_put REPLICATED onto the engine's mesh
        (tables are tiny int32; every chip gathers/scatters the
        head-sharded page arena through its own copy) — a bare
        jnp.asarray would land the table on device 0 only, forcing a
        resharding transfer inside every tick and breaking cache-leaf
        donation under tensor-parallel serving
        (docs/tensor_parallel_serving.md)."""
        if not self._paged:
            return
        from jax.sharding import NamedSharding, PartitionSpec

        everywhere = NamedSharding(self.engine.mesh, PartitionSpec())
        if self._window:
            # The window pages each live row's next steps write, mapped
            # before the device call that may write them. This table
            # moves most ticks (a row passes a page every 16 tokens);
            # the other one only when a row comes or goes.
            for i, slot in enumerate(self.slots):
                if slot.active and slot.request is not None and (
                    self.pages.window.extend(i, self._next_query(slot))
                ):
                    self._window_dirty = True
            if self._window_dirty or self._tables_dirty:
                self.cache = self.cache._replace(
                    window=self.cache.window._replace(table=jax.device_put(
                        self.pages.window.tables, everywhere)))
                self._window_dirty = False
        if self._tables_dirty:
            self.cache = self.cache._replace(
                table=jax.device_put(self.pages.tables, everywhere))
            self._tables_dirty = False

    def _snap_dev(self, x):
        """Host→device snapshot of per-slot tick state (cur/prev
        tokens, grammar states, grammar tables), device_put REPLICATED
        onto the engine's mesh — the same contract as _sync_tables'
        block tables. A bare jnp.asarray commits the snapshot to
        device 0, which forces a resharding transfer inside every tick
        under tensor-parallel serving (graftlint unsharded-transfer,
        the PR 7 block-table bug generalized)."""
        from jax.sharding import NamedSharding, PartitionSpec

        return jax.device_put(
            np.asarray(x), NamedSharding(self.engine.mesh, PartitionSpec())
        )

    def _paged_put(self, cache, mini, slots, true_len, start):
        """Paged counterpart of every row merge (_merge_row, the
        full-pool select, the chunked-finish scatter): write mini rows'
        positions [start_r, true_len_r) through slots' block tables
        into the arena. `start` masks off SHARED prefix pages — those
        are immutable, refcounted storage; only the row's exclusive
        pages are written, and only the positions the row actually
        holds (no more full-row copies). Padding rows (slot index out
        of range) and sentinel table entries drop."""
        b = len(self.slots)
        p = self._page_size
        r = true_len.shape[0]
        mk = mini.k.q if isinstance(mini.k, quant.QuantizedArray) else mini.k
        s = mk.shape[2]
        pos = jnp.arange(s)
        rows = jnp.clip(slots, 0, b - 1)
        off = jnp.broadcast_to(pos % p, (r, s))
        start = jnp.broadcast_to(start, (r,))
        valid = (
            (pos[None, :] >= start[:, None])
            & (pos[None, :] < true_len[:, None])
            & (slots[:, None] >= 0) & (slots[:, None] < b)
        )
        length = cache.length.at[slots].set(true_len, mode="drop")
        if cache.window is not None:
            # Two kinds of page: each kind's layers of the mini go
            # through its own table into its own arena. A window
            # layer's table maps the live tail only, so the positions
            # behind it drop like any unmapped entry's.
            def through(table, n_pages):
                pg = table[rows][:, jnp.minimum(
                    pos // p, self._table_width - 1)]
                pg = jnp.where(valid, pg, n_pages)
                return lambda a, m: a.at[:, pg, off].set(
                    m.astype(a.dtype), mode="drop")

            cfg, win = self.engine.cfg, cache.window
            (fk, wk), (fv, wv) = (
                llama_mod.split_kinds(cfg, m) for m in (mini.k, mini.v))
            full = through(cache.table, self._n_pages)
            tail = through(win.table, self._n_pages_w)
            return cache._replace(
                k=full(cache.k, fk), v=full(cache.v, fv), length=length,
                window=win._replace(k=tail(win.k, wk), v=tail(win.v, wv)))
        rtab = cache.table[rows]  # [R, W]
        page = rtab[:, jnp.minimum(pos // p, self._table_width - 1)]
        page = jnp.where(valid, page, self._n_pages)

        def put(a, m):
            if not self._arena_by_layer:
                return a.at[:, page, off].set(m.astype(a.dtype), mode="drop")

            # A layer at a time, the arena loop-carried and indexed
            # [layer, page, offset] as the tick writes it: in place.
            # Where a page row is one vector (the latent family), one
            # scatter over `a.at[:, page, off]` makes the layer axis
            # part of each update's window, and XLA then re-lays the
            # whole arena out (layer axis next to the lanes) and back:
            # two copies of it alive beside it, 2 x 2.7 GB at a 2 GB
            # latent arena of 5 layers.
            def layer(i, a):
                return a.at[i, page, off].set(
                    m[i].astype(a.dtype), mode="drop")

            return jax.lax.fori_loop(0, a.shape[0], layer, a)

        return llama_mod.map_planes(put, cache, mini, length=length)

    # -- row state beside pages (a ROW_STATE family) -------------------------

    def _state_io(self, placed: list, r: int):
        """What an admission program of `r` rows needs to carry its
        rows' state, as device arrays (`sio`), None for a family
        without one (its programs then have the arguments they always
        had). `placed`: (row index, slot) of the real rows, whose
        restore source and captures `_state_plan` noted (a row it did
        not see starts from zeros and captures nothing). (src [r]: the
        entry each row's state is restored from, -1 = zeros; every
        [r, G]: entry for the state at absolute position (g + 1) x
        prefill_chunk; at [r] + at_dst [r]: one more position, a page
        boundary.) An entry out of range captures nothing."""
        if not self._row_state:
            return None
        every = self.cfg.prefill_chunk
        src = np.full((r,), -1, np.int32)
        grid = np.full((r, self._grid_chunks), self._no_entry, np.int32)
        at = np.full((r,), -1, np.int32)
        at_dst = np.full((r,), self._no_entry, np.int32)
        for j, sl in placed:
            src[j], captures = self._state_rows.get(sl, (-1, ()))
            for pos, entry in captures:
                if pos % every == 0:
                    grid[j, pos // every - 1] = entry
                else:
                    at[j], at_dst[j] = pos, entry
        return tuple(jnp.asarray(x) for x in (src, grid, at, at_dst))

    def _state_enter(self, cache, mini, rows, sio):
        """`mini` with the shared cache's state pool grafted in: its
        batch rows read and write the pool entries `rows` (slot
        indices; out of range: a padding row, dropped), each restored
        first from the snapshot `sio` names, or zeroed."""
        if sio is None:
            return mini
        return mini._replace(
            state=self.fam.restore_rows(cache.state, rows, sio[0]),
            state_rows=rows)

    def _capture_of(self, sio, off, width: int, j=None):
        """forward's `capture` for one [rows, width] step that starts
        at absolute position `off`: the multiple of prefill_chunk the
        step reaches, if it passes one, and the row's page-boundary
        position (forward takes neither where the step does not pass
        it). `j`: one row of `sio` alone."""
        if sio is None:
            return None
        _, grid, at, at_dst = sio
        if j is not None:
            grid, at, at_dst = (
                jax.lax.dynamic_slice_in_dim(x, j, 1) for x in (grid, at, at_dst))
        every = self.cfg.prefill_chunk
        off = jnp.broadcast_to(off, at.shape).astype(jnp.int32)
        reach = (off + width) // every * every
        k = jnp.clip(reach // every - 1, 0, grid.shape[1] - 1)
        dst = jnp.take_along_axis(grid, k[:, None], axis=1)[:, 0]
        pos = jnp.where(reach > off, reach, -1)
        return jnp.stack([pos, at], 1), jnp.stack([dst, at_dst], 1)

    @staticmethod
    def _state_leave(cache, mini, sio):
        """The shared cache holding the pool the admission's forward
        passes left in `mini`."""
        return cache if sio is None else cache._replace(state=mini.state)

    # -- KV page export/import (sidecar→sidecar TransferKV plane) -----------

    def _reserve_for(self, constrained: bool) -> int:
        """The tick-overshoot reserve a request's cache extent must
        cover: grammar-carrying requests reserve the jump window
        (1 + jump_max positions may be written in one jump tick),
        unconstrained requests only the plain per-tick advance. Both
        values are identical when jump is off."""
        return self._jump_reserve if constrained else self._reserve

    def clamp_prompt(
        self, prompt: list[int], max_new: int, constrained: bool = False
    ) -> list[int]:
        """The prompt exactly as an admission for (prompt, max_new)
        will see it (fit_request keeps the TAIL, sized by max_new and
        the tick-overshoot reserve). The disaggregated prefill leg must
        admit and export THIS prompt — with the request's real max_new,
        not its own 1-token one — or a near-limit prompt would register
        a different chain than the decode replica's identically clamped
        admission looks up. `constrained` must mirror whether the
        request carries a grammar: the jump window widens a constrained
        request's reserve, so both disagg legs have to agree on it."""
        clamped, _ = fit_request(
            prompt, max_new, self._fit_limit - self._reserve_for(constrained)
        )
        return clamped

    def export_prompt_kv(
        self, prompt: list[int], adapter: str = ""
    ) -> dict:
        """Gather the indexed full-page KV of `prompt` from the device
        arena to host (the prefill-role half of disaggregated serving;
        run via run_host_op — the serialized executor stream is what
        makes the lookup + gather atomic against eviction). Returns
        {pages, page_size, k, v[, k_scale, v_scale]} with [L, n, P,
        KVH, Dh] host arrays (int8 KV ships values + scales — half the
        bytes). Raises KVTransferError when paging is off or the index
        holds no pages for this prompt (evicted, or never admitted):
        the caller degrades typed, never ships a lie. `adapter`: the
        stable adapter key the chain was registered under ("" = base)
        — adapter'd prompts export their own key domain's pages."""
        if not self._paged:
            raise KVTransferError(
                "kv export requires batching.paged_kv=on"
            )
        pages = self.pages.chain_pages(prompt, adapter=adapter)
        if not pages:
            raise KVTransferError(
                "no indexed pages for this prompt (evicted before "
                "export, or the prompt is shorter than one page)"
            )
        idx = np.asarray(pages, np.int32)
        out: dict = {"pages": len(pages), "page_size": self._page_size}
        for name, leaf in (("k", self.cache.k), ("v", self.cache.v)):
            if isinstance(leaf, quant.QuantizedArray):
                out[name] = np.asarray(leaf.q[:, idx])
                out[name + "_scale"] = np.asarray(leaf.scale[:, idx])
            else:
                out[name] = np.asarray(leaf[:, idx])
        return out

    def import_prompt_kv(
        self,
        prompt: list[int],
        start_page: int,
        k: np.ndarray,
        v: np.ndarray,
        k_scale: "Optional[np.ndarray]" = None,
        v_scale: "Optional[np.ndarray]" = None,
        adapter: str = "",
    ) -> tuple[int, int]:
        """Land one TransferKV chunk in this batcher's arena (the
        decode-role half; run via run_host_op): allocate + index the
        chunk's pages host-side (pages.import_chain — refcount 0,
        LRU-stamped, evictable) and write their contents into the
        device arena. Returns (pages_imported, pages_already_present).
        The device write dispatches INSIDE the serialized stream, so
        any later admission's gather reads it by device ordering — the
        same soundness argument as eager same-round registration.
        Raises KVTransferError on geometry/dtype mismatch and
        PageExhaustedError when the arena can't host the chunk."""
        if not self._paged:
            raise KVTransferError(
                "kv import requires batching.paged_kv=on"
            )
        arena_k = self.cache.k
        quantized = isinstance(arena_k, quant.QuantizedArray)
        if quantized != (k_scale is not None):
            raise KVTransferError(
                "kv dtype mismatch: sender and receiver must both use "
                "int8 KV or neither (serving.kv_cache_dtype)"
            )
        ref = arena_k.q if quantized else arena_k
        want = (ref.shape[0],) + ref.shape[2:]  # [L, P, KVH, Dh]
        got = (k.shape[0],) + k.shape[2:]
        if got != want or v.shape != k.shape:
            raise KVTransferError(
                f"kv page geometry mismatch: got {got}, arena wants "
                f"{want} (layers, page_size, kv_heads, head_dim)"
            )
        placed = self.pages.import_chain(
            prompt, start_page, int(k.shape[1]), adapter=adapter
        )
        present = int(k.shape[1]) - len(placed)
        if not placed:
            return 0, present
        dst = np.asarray([p for _, p in placed], np.int32)
        src = np.asarray([j - start_page for j, _ in placed], np.int32)
        self._write_arena_pages(
            dst, k[:, src], v[:, src],
            k_scale[:, src] if quantized else None,
            v_scale[:, src] if quantized else None,
        )
        return len(placed), present

    def _write_arena_pages(
        self,
        dst: np.ndarray,
        k: np.ndarray,
        v: np.ndarray,
        k_scale: "Optional[np.ndarray]" = None,
        v_scale: "Optional[np.ndarray]" = None,
    ) -> None:
        """H2D write of [L, n, P, KVH, Dh] page contents into arena
        pages `dst` — the ONE device-write shared by the TransferKV
        import and the host-tier restore, so the two paths cannot
        drift. Geometry/dtype are re-validated here (cheap, and the
        restore path has no other gate). Dispatches inside the
        caller's serialized stream: any later admission's gather reads
        the new contents by device ordering."""
        arena_k = self.cache.k
        quantized = isinstance(arena_k, quant.QuantizedArray)
        if quantized != (k_scale is not None):
            raise KVTransferError(
                "kv dtype mismatch: page payload and arena must both "
                "use int8 KV or neither (serving.kv_cache_dtype)"
            )
        ref = arena_k.q if quantized else arena_k
        want = (ref.shape[0],) + ref.shape[2:]  # [L, P, KVH, Dh]
        got = (k.shape[0],) + k.shape[2:]
        if got != want or v.shape != k.shape:
            raise KVTransferError(
                f"kv page geometry mismatch: got {got}, arena wants "
                f"{want} (layers, page_size, kv_heads, head_dim)"
            )

        def put(a, m):
            return a.at[:, dst].set(self._snap_dev(m).astype(a.dtype))

        if quantized:
            new_k = quant.QuantizedArray(
                q=put(arena_k.q, k),
                scale=put(arena_k.scale, k_scale),
            )
            new_v = quant.QuantizedArray(
                q=put(self.cache.v.q, v),
                scale=put(self.cache.v.scale, v_scale),
            )
        else:
            new_k = put(arena_k, k)
            new_v = put(self.cache.v, v)
        self.cache = self.cache._replace(k=new_k, v=new_v)

    # -- host-tier hooks (serving/host_pool.py via pages.attach_host) -------

    def _kv_page_geometry(self) -> str:
        """Page-shape/dtype signature guarding the host pool's file
        tier: a restarted replica with a different arena geometry must
        start fresh, never restore wrong-shaped KV."""
        leaf = self.cache.k
        quantized = isinstance(leaf, quant.QuantizedArray)
        ref = leaf.q if quantized else leaf
        shape = (ref.shape[0],) + ref.shape[2:]
        return "x".join(str(d) for d in shape) + f":{ref.dtype}" + (
            ":int8" if quantized else ""
        )

    def _demote_fetch(self, pages: list[int]) -> list[bytes]:
        """D2H gather + pack of arena pages about to be evicted (the
        allocator's demotion half): ONE device gather for the whole
        victim set, one packed KVPagePayload per page — the exact
        codec TransferKV ships pages with (serving/tensors.py)."""
        idx = np.asarray(pages, np.int32)
        gathered: dict = {}
        for name, leaf in (("k", self.cache.k), ("v", self.cache.v)):
            if isinstance(leaf, quant.QuantizedArray):
                gathered[name] = np.asarray(leaf.q[:, idx])
                gathered[name + "_scale"] = np.asarray(leaf.scale[:, idx])
            else:
                gathered[name] = np.asarray(leaf[:, idx])
        quantized = "k_scale" in gathered
        return [
            tensors.pack_kv_pages(
                gathered["k"][:, i:i + 1], gathered["v"][:, i:i + 1],
                gathered["k_scale"][:, i:i + 1] if quantized else None,
                gathered["v_scale"][:, i:i + 1] if quantized else None,
            )
            for i in range(len(pages))
        ]

    def _restore_write(self, pages: list[int], blobs: list[bytes]) -> None:
        """Unpack + H2D write of restored host-tier pages (the
        allocator's restore half). Raises on the host_restore_fail
        chaos hook or any unpack/geometry error — the allocator
        degrades the admission TYPED to recompute, never a silent
        half-restore (all pages ride one batched write)."""
        failpoints.evaluate("host_restore_fail")
        ks, vs, kss, vss = [], [], [], []
        for blob in blobs:
            k, v, k_s, v_s = tensors.unpack_kv_pages(blob)
            ks.append(k)
            vs.append(v)
            if k_s is not None:
                kss.append(k_s)
                vss.append(v_s)
        if kss and len(kss) != len(ks):
            raise KVTransferError(
                "mixed int8/unquantized payloads in one restore set"
            )
        self._write_arena_pages(
            np.asarray(pages, np.int32),
            np.concatenate(ks, axis=1),
            np.concatenate(vs, axis=1),
            np.concatenate(kss, axis=1) if kss else None,
            np.concatenate(vss, axis=1) if kss else None,
        )

    # -- grammar host side (serving/batching owns residency + states) -------

    def _grammar_tables(self):
        """Device copies of the arena's allow/transition tables,
        re-uploaded only when a grammar was inserted or evicted since
        the last call (arena.version). FIXED [arena_states, V] shape:
        table-content churn never recompiles a device program."""
        if (
            self._g_allow_dev is None
            or self._g_dev_version != self.arena.version
        ):
            (allow, trans, jlen, jtok, jstate,
             version) = self.arena.snapshot()
            self._g_allow_dev = self._snap_dev(allow)
            self._g_trans_dev = self._snap_dev(trans)
            # Forced-run twins ride the same version gate: a jump tick
            # dispatched after any acquire sees relocated run tables
            # consistent with the allow/trans pair it masks under.
            self._g_jlen_dev = self._snap_dev(jlen)
            self._g_jtok_dev = self._snap_dev(jtok)
            self._g_jstate_dev = self._snap_dev(jstate)
            self._g_dev_version = version
        return self._g_allow_dev, self._g_trans_dev

    def _g0(self, request: _Request) -> int:
        """The ABSOLUTE grammar state a (re-)admission samples its
        first token under. Fresh requests start at the grammar's start
        state; tick-failure replays re-derive it by replaying the
        absorbed emitted tokens through the transition table — which is
        what keeps constrained greedy output bit-identical under the
        chaos suite (the re-admitted prefill of prompt+acc continues
        from exactly the state the consumer last observed)."""
        if request.grammar is None:
            return 0
        state = request.grammar.start
        for token in request.acc[:request.absorbed]:
            state = self.arena.step(state, int(token))
        return state

    def _grammar_release(self, request: _Request) -> None:
        """Return a terminal request's arena reference (idempotent —
        several terminal paths can observe the same request)."""
        if request.grammar is not None and not request.g_released:
            request.g_released = True
            self.arena.release(request.grammar)

    # -- jitted bodies ------------------------------------------------------

    def _prefill_sample(
        self, params, tokens, true_len, seeds, temps, ks, ps, adapters,
        g0, g_allow, g_trans, cache=None, rows=None, sio=None,
    ):
        """Shared admission core: prefill the right-padded prompts
        [R, S] against a fresh mini cache, sample each row's first
        token (grammar-masked under each row's admission state `g0`;
        0 = unconstrained). Returns (first [R], mini cache). `sio`
        (a ROW_STATE family): the rows' state lives in `cache`'s pool
        at entries `rows` and leaves in the mini (_state_leave)."""
        r, s = tokens.shape
        mini = self._state_enter(cache, self._make_mini(r, s), rows, sio)
        # Fresh prefill → engine.prefill_forward (handles MoE validity
        # and the sequence-parallel long-chunk path).
        valid = jnp.arange(s)[None, :] < true_len[:, None]
        last = jnp.maximum(true_len - 1, 0)
        logits, mini = self.engine.prefill_forward(
            params, tokens, mini, valid=valid, lora_idx=adapters,
            logit_idx=last if self._head_at_index else None,
            capture=self._capture_of(sio, 0, s),
        )
        if self._head_at_index:  # logits [R, 1, V]: each row's last
            last = jnp.zeros_like(last)
        first = self._first_token_impl(
            logits, last, seeds, temps, ks, ps, g0, g_allow, g_trans,
        )
        return first, mini

    def _admit_single_impl(
        self, params, tokens, true_len, cache, slot, seeds, temps, ks, ps,
        adapters, g0, g_allow, g_trans, sio=None,
    ):
        """Admit ONE request (row shapes [1, S]) into slot `slot`."""
        first, mini = self._prefill_sample(
            params, tokens, true_len, seeds, temps, ks, ps, adapters,
            g0, g_allow, g_trans, cache, jnp.reshape(slot, (1,)), sio,
        )
        if self._paged:
            cache = self._paged_put(
                cache, mini, jnp.reshape(slot, (1,)), true_len,
                jnp.int32(0),
            )
        else:
            cache = _merge_row(cache, mini, slot, true_len[0])
        return first, self._state_leave(cache, mini, sio)

    def _admit_full_impl(
        self, params, tokens, true_len, cache, valid, seeds, temps, ks, ps,
        adapters, g0, g_allow, g_trans, sio=None,
    ):
        """Admit a burst in one call: `tokens` is a full [B, S] batch
        with admitted prompts placed at their slots' rows and
        `valid[B]` marking them; other rows keep their cache state (a
        row-select, not a scatter, so no duplicate-index hazards)."""
        s = tokens.shape[1]
        # A row that admits nothing keeps its state too: its entry is
        # out of range (reads clip, writes drop).
        rows = jnp.where(
            valid, jnp.arange(len(self.slots)), self._no_entry)
        first, mini = self._prefill_sample(
            params, tokens, true_len, seeds, temps, ks, ps, adapters,
            g0, g_allow, g_trans, cache, rows, sio,
        )
        if self._paged:
            slots = jnp.where(
                valid, jnp.arange(len(self.slots)), len(self.slots)
            )
            return first, self._state_leave(self._paged_put(
                cache, mini, slots, true_len, jnp.int32(0)
            ), mini, sio)
        sel = valid[None, :, None, None, None]

        def select(c, m):
            return c.at[:, :, :s].set(
                jnp.where(sel, m.astype(c.dtype), c[:, :, :s])
            )

        lengths = jnp.where(valid, true_len, cache.length)
        return first, self._state_leave(llama_mod.map_planes(
            select, cache, mini, length=lengths), mini, sio)

    def _chunk_step(
        self, params, chunk, off, true_len, last, mini, fl, adapters,
        capture=None,
    ):
        """Extend `mini` by one [B, C] chunk whose first token sits at
        absolute position `off`, and keep in `fl` [B, V] the logits at
        each row's final prompt position `last` (true_len - 1, taken
        once by the caller) if this chunk holds it. The one chunk body
        of every chunked admission (_chunked_scan's grid,
        _chunked_rows' per-row walk)."""
        c = chunk.shape[1]
        if self._is_moe:
            valid = (off + jnp.arange(c))[None, :] < true_len[:, None]
        else:
            valid = None
        idx = jnp.clip(last - off, 0, c - 1)
        logits, mini = self.engine.decode_forward(
            params, chunk, mini, valid=valid, ring=self._ring,
            lora_idx=adapters,
            logit_idx=idx if self._head_at_index else None,
            capture=capture,
        )
        if self._head_at_index:  # logits [B, 1, V], at idx already
            idx = jnp.zeros_like(idx)
        sel = jnp.take_along_axis(
            logits, idx[:, None, None], axis=1
        )[:, 0]
        take = (last >= off) & (last < off + c)
        fl = jnp.where(take[:, None], sel.astype(fl.dtype), fl)
        return mini, fl

    def _chunked_scan(
        self, params, tokens, true_len, mini, adapters, start, sio=None
    ):
        """lax.scan over a [B, T, C] chunk grid: each step extends
        `mini` (which must already hold `start` positions per row) by
        one [B, C] chunk and captures the logits at each row's final
        prompt position as it passes (_chunk_step). Every row of a
        paged prefix-reuse group has the grid's depth (the group's key
        holds it), so no step is a row's padding.
        Returns (final_logits [B, V] f32, mini)."""
        b, t_steps, c = tokens.shape
        carry0 = jnp.zeros((b, self.engine.cfg.vocab_size), jnp.float32)
        last = true_len - 1  # absolute index of each row's final token

        def body(carry, xs):
            mini, fl = carry
            chunk, off = xs
            return self._chunk_step(
                params, chunk, off, true_len, last, mini, fl, adapters,
                self._capture_of(sio, off, c),
            ), None

        offs = start + jnp.arange(t_steps, dtype=jnp.int32) * c
        (mini, fl), _ = jax.lax.scan(
            body, (mini, carry0), (jnp.moveaxis(tokens, 1, 0), offs)
        )
        return fl, mini

    def _chunked_rows(
        self, params, tokens, true_len, adapters, cache=None, slots=None,
        sio=None,
    ):
        """A cold [R, T_max, C] admission, a row at a time in the order
        given: row r runs its own ceil(true_len[r] / C) chunks, one
        [1, C] step each against a one-row mini (_chunk_step), and
        nothing past them — the grid's depth T_max is the batcher's
        constant and shapes no work, a padding row of the bucket
        (true_len 0) runs no chunk. A chunk of one row is already
        compute-bound, so the weights' extra passes hide under its
        products. Returns (final_logits [R, V] f32, mini [R, max_seq]).
        `sio` (a ROW_STATE family): the rows' state is carried from
        chunk to chunk in `cache`'s pool at entries `slots`, which the
        group's mini holds on return (_state_leave)."""
        r, _, c = tokens.shape
        vocab = self.engine.cfg.vocab_size

        def row(j, mini1, state):
            """Row j's chunks into the fresh one-row `mini1`."""
            n = jax.lax.dynamic_slice_in_dim(true_len, j, 1)
            lora = jax.lax.dynamic_slice_in_dim(adapters, j, 1)
            chunks = jax.lax.dynamic_index_in_dim(
                tokens, j, keepdims=False)  # [T_max, C]
            if sio is not None:
                mini1 = mini1._replace(
                    state=state,
                    state_rows=jax.lax.dynamic_slice_in_dim(slots, j, 1))

            def step(t, carry):
                chunk = jax.lax.dynamic_slice_in_dim(chunks, t, 1)
                return self._chunk_step(
                    params, chunk, t * c, n, n - 1, *carry, lora,
                    self._capture_of(sio, t * c, c, j))

            return jax.lax.fori_loop(
                0, (n[0] + c - 1) // c, step,
                (mini1, jnp.zeros((1, vocab), jnp.float32)))

        if r == 1:  # the row's mini is the group's
            mini1 = self._state_enter(
                cache, self._make_mini(1, self.max_seq), slots, sio)
            mini, fl = row(jnp.int32(0), mini1, mini1.state)
            return fl, mini

        def body(j, carry):
            mini, fl = carry
            mini1, fl1 = row(j, self._make_mini(1, self.max_seq), mini.state)
            mini = llama_mod.map_planes(
                lambda m, m1: jax.lax.dynamic_update_slice_in_dim(
                    m, m1, j, axis=1), mini, mini1, state=mini1.state)
            return mini, jax.lax.dynamic_update_slice_in_dim(
                fl, fl1, j, axis=0)

        group = self._state_enter(
            cache, self._make_mini(r, self.max_seq), self._pool_rows(slots), sio)
        mini, fl = jax.lax.fori_loop(
            0, r, body, (group, jnp.zeros((r, vocab), jnp.float32)))
        return fl, mini

    def _chunked_finish(
        self, cache, mini, slots, true_len, fl, seeds, temps, ks, ps,
        g0, g_allow, g_trans, start=None, sio=None,
    ):
        """Scatter the [R, S_max] admission mini into the shared cache
        at `slots` (padding rows carry an out-of-range slot index and
        are DROPPED by the scatter — real slots are distinct, so no
        duplicate-index hazards) and sample each row's first token.
        Paged mode routes through _paged_put instead, writing only
        [start, true_len) of each row (start > 0 = the paged-pfx
        admission's shared-page boundary)."""
        first, _ = masked_sample_dynamic(
            fl, seeds, jnp.int32(0), temps, ks, ps, g0, g_allow, g_trans
        )
        if self._paged:
            return first, self._state_leave(self._paged_put(
                cache, mini, slots, true_len,
                jnp.int32(0) if start is None else start,
            ), mini, sio)

        def put(c_, m):
            return c_.at[:, slots].set(m.astype(c_.dtype), mode="drop")

        lengths = cache.length.at[slots].set(true_len, mode="drop")
        return first, self._state_leave(
            llama_mod.map_planes(put, cache, mini, length=lengths), mini, sio)

    def _admit_chunked_impl(
        self, params, tokens, true_len, cache, slots, seeds, temps, ks,
        ps, adapters, g0, g_allow, g_trans, sio=None,
    ):
        """Fused chunked admission (nothing reused): every row's own
        chunks of the [R, T_max, C] grid (_chunked_rows) + merge +
        first-token sample, ONE device call, and one program a row
        bucket whatever the prompts' depths. R is the caller's bucketed
        group size — per-row work here is the heavy case (long
        prompts), so a trickle admission must not pay the full slot
        pool's compute."""
        fl, mini = self._chunked_rows(
            params, tokens, true_len, adapters, cache, slots, sio)
        return self._chunked_finish(
            cache, mini, slots, true_len, fl, seeds, temps, ks, ps,
            g0, g_allow, g_trans, sio=sio,
        )

    def _admit_paged_pfx_impl(
        self, params, tokens, true_len, cache, slots, gtables,
        scan_start, merge_start, seeds, temps, ks, ps, adapters,
        g0, g_allow, g_trans, sio=None,
    ):
        """Fused paged prefix-reuse admission: gather each row's shared
        prefix into a full-width mini VIEW through the host-built
        gather tables (`gtables` = the slot's block-table row, with the
        first divergent entry swapped for the copy-on-write source page
        when one matched), run the [R, T, C] suffix grid from
        `scan_start`, and merge positions [merge_start, n) back into
        the rows' OWN exclusive pages. Shared pages are read, never
        written; scan_start > merge_start is the CoW case — the overlap
        tokens' KV rides the gather and the merge copies it into the
        slot's fresh divergent page instead of recomputing it. One
        device call admits a whole same-preamble wave."""
        r = tokens.shape[0]
        if cache.window is not None:
            # `gtables` [R, 2, W]: a gather row a kind (_gather_tables);
            # the kinds' views, joined in the model's layer order. A
            # page is gathered as its `[page x KVH, Dh]` rows (the
            # shape the paged-decode kernel reads it in): gathered as
            # `[page, KVH, Dh]` with 4 KV heads, the compiler wanted the
            # view KVH-major and first copied each of the four arenas
            # whole into that order, 3.3 ms each, a third of a
            # re-admission program on the chip (PERF.md section 6, PR
            # 53; the compile for a described v5e shows the copies).
            def view(a, table):
                flat = llama_mod.paged_view_layers(
                    a.reshape(*a.shape[:2], -1, a.shape[-1]), table)
                return flat.reshape(*flat.shape[:2], -1, *a.shape[3:])

            views = [llama_mod.join_kinds(self.engine.cfg, [
                view(a, gtables[:, kind]) for kind, a in enumerate(pair)])
                for pair in ((cache.k, cache.window.k),
                             (cache.v, cache.window.v))]
        else:
            views = [
                llama_mod.paged_view_layers(
                    plane, gtables, self._arena_by_layer)
                for plane in llama_mod.cache_planes(cache)]
        mini = llama_mod.with_planes(
            llama_mod.KVCache(
                None, None, jnp.broadcast_to(scan_start, (r,)).astype(jnp.int32)),
            views)
        mini = self._state_enter(cache, mini, self._pool_rows(slots), sio)
        fl, mini = self._chunked_scan(
            params, tokens, true_len, mini, adapters, scan_start, sio
        )
        return self._chunked_finish(
            cache, mini, slots, true_len, fl, seeds, temps, ks, ps,
            g0, g_allow, g_trans, start=merge_start, sio=sio,
        )

    def _decode_scan(
        self, params, tokens, cache, seeds, step, temps, ks, ps, active,
        adapters, gstate, g_allow, g_trans, steps=None,
    ):
        """`steps` (the full tick's when None) fused decode steps (scan) — the
        shared core of the plain tick and the fused tick+chunk program,
        so interleaved admission cannot perturb decode numerics by
        construction. Each step samples through the grammar mask and
        advances the per-row DFA state via a table gather — the
        constrained step never leaves the device (rows at state 0, the
        accept-all state, are numerically untouched). Returns
        (toks [B, steps], cache, gstate_out [B]); where the family
        names ROUTING_STATS, toks has one more row for each, a step's
        counts (models/mla_moe.py::forward `with_stats`)."""

        def body(carry, i):
            cur, gs, cache = carry
            logits, cache, *counts = self.engine.decode_forward(
                params, cur[:, None], cache,
                valid=active[:, None] if self._is_moe else None,
                ring=self._ring,
                lora_idx=adapters,
                with_stats=bool(self._routing_stats),
            )
            nxt, gs = masked_sample_dynamic(
                logits[:, -1], seeds, step + i, temps, ks, ps,
                gs, g_allow, g_trans, live=active,
            )
            out = jnp.concatenate([nxt, *counts]) if counts else nxt
            return (nxt, gs, cache), out

        (_, gstate, cache), toks = jax.lax.scan(
            body, (tokens, gstate, cache), jnp.arange(steps or self._steps_per_tick)
        )
        return toks.T, cache, gstate  # [B (+ counts), steps], .., [B]

    def _tick_impl(
        self, params, tokens, cache, seeds, step, temps, ks, ps, active,
        adapters, gstate, g_allow, g_trans, steps=None,
    ):
        """One device call = `steps` fused decode steps (lax.scan; a
        static argument: the full and the short tick are two instances
        of this one program). Tokens sampled after a slot's EOS/max_new
        are dropped host-side in `_emit_chunk` (the cache rows they
        touched are masked by `length` on slot reuse)."""
        return self._decode_scan(
            params, tokens, cache, seeds, step, temps, ks, ps, active,
            adapters, gstate, g_allow, g_trans, steps,
        )

    def _tick_chunk_impl(
        self, params, tokens, cache, seeds, step, temps, ks, ps, active,
        adapters, chunk, mini, offs, c_true_len, c_valid, c_adapters,
        gstate, g_allow, g_trans,
    ):
        """Fused tick+chunk (prefill_interleave=on): the decode scan for
        every slot AND at most one [K, C] prefill chunk for admitting
        rows, in ONE device call — an active slot's emission gaps by
        one chunk's compute, never a whole prompt's prefill.

        The chunk part extends the carried [K, S_max] mini cache at the
        host-stamped per-row offsets `offs` (authoritative each call,
        so idle rows — c_valid False — can run junk chunks without
        drifting state: their next occupant re-stamps offset 0 and
        overwrites). Returns each row's logits at its final prompt
        position within THIS chunk (`sel`); the host uses sel[r] only
        for rows whose last chunk this was. Numerics match the
        serialized chunked grid: same chunk widths, same offsets, same
        final-position gather — only the batch row count differs, which
        is row-independent math."""
        toks, cache, gstate = self._decode_scan(
            params, tokens, cache, seeds, step, temps, ks, ps, active,
            adapters, gstate, g_allow, g_trans,
        )
        mini, sel = self._chunk_extend(
            params, chunk, mini, offs, c_true_len, c_valid, c_adapters
        )
        return toks, cache, mini, sel, gstate

    def _jump_core(
        self, params, tokens, cache, seeds, step, temps, ks, ps, active,
        adapters, gstate, g_allow, g_trans, j_len, j_tok, j_state,
        jump_ok,
    ):
        """The jump-ahead advance (docs/structured_output.md
        "Jump-ahead"): ONE decode forward over a static
        [B, 1 + jump_max] window = each row's pending token plus its
        forced run, then one grammar-masked sample under the run's
        landing state. Shape-invariant across any schema mix — the
        window width is the constructor's jump_max, never a
        data-dependent run length; rows without a forced run (state 0,
        jump_ok False, parked slots) read run_len 0 and collapse to the
        plain one-token constrained step, their surplus window
        positions junk that dies under the causal length mask (only the
        length POINTER advances by 1 + run_len; the forward wrote all
        1 + jump_max). Forced tokens get real KV writes from the same
        forward that samples the landing token — "emit without a
        forward pass" means no per-token forward, not no KV.

        Returns (emit [B, 1+jump_max], count [B], cache, cur' [B],
        gstate' [B]); the host emits emit[i, :count[i]] per owned row,
        count = run_len + 1 in [1, 1 + jump_max].
        """
        tlen0 = cache.length
        run_len, run_tokens, landing = forced_run_lookup(
            gstate, j_len, j_tok, j_state, jump_ok
        )
        window = jnp.concatenate([tokens[:, None], run_tokens], axis=1)
        # Dense families only (the constructor gates jump off for MoE:
        # batch-global expert routing would see the junk window
        # positions) — no validity mask needed.
        logits, cache = self.engine.decode_forward(
            params, window, cache, ring=self._ring, lora_idx=adapters,
        )
        # logits[:, i] predicts the token AFTER window[:, :i+1] — the
        # post-run sample reads position run_len (0 when no run: the
        # plain tick's gather).
        sel = jnp.take_along_axis(
            logits, run_len[:, None, None], axis=1
        )[:, 0]
        nxt, gstate2 = masked_sample_dynamic(
            sel, seeds, step, temps, ks, ps, landing, g_allow, g_trans,
            live=active,
        )
        idx = jnp.arange(window.shape[1])[None, :]
        emit = jnp.where(
            idx < run_len[:, None],
            jnp.pad(run_tokens, ((0, 0), (0, 1))),
            jnp.where(idx == run_len[:, None], nxt[:, None], 0),
        )
        count = run_len + 1
        # Commit cur + the forced run; the sampled token is the next
        # tick's pending feed (its KV unwritten, the plain-tick
        # invariant).
        cache = cache._replace(length=tlen0 + count)
        return emit, count, cache, nxt, gstate2

    def _tick_jump_impl(
        self, params, tokens, cache, seeds, step, temps, ks, ps, active,
        adapters, gstate, g_allow, g_trans, j_len, j_tok, j_state,
        jump_ok,
    ):
        """One jump-ahead device call for the whole slot pool — the
        multi-token twin of _tick_impl, dispatched instead of it while
        any live slot can jump (_tick_step)."""
        return self._jump_core(
            params, tokens, cache, seeds, step, temps, ks, ps, active,
            adapters, gstate, g_allow, g_trans, j_len, j_tok, j_state,
            jump_ok,
        )

    def _tick_jump_chunk_impl(
        self, params, tokens, cache, seeds, step, temps, ks, ps, active,
        adapters, chunk, mini, offs, c_true_len, c_valid, c_adapters,
        gstate, g_allow, g_trans, j_len, j_tok, j_state, jump_ok,
    ):
        """_tick_jump_impl fused with one [K, C] interleaved-admission
        prefill chunk — the jump path rides the existing chunked-
        prefill machinery the same way _tick_chunk_impl does, so a
        forced run never serializes against a long prompt's
        admission."""
        emit, count, cache, cur2, gstate2 = self._jump_core(
            params, tokens, cache, seeds, step, temps, ks, ps, active,
            adapters, gstate, g_allow, g_trans, j_len, j_tok, j_state,
            jump_ok,
        )
        mini, sel = self._chunk_extend(
            params, chunk, mini, offs, c_true_len, c_valid, c_adapters
        )
        return emit, count, cache, cur2, gstate2, mini, sel

    def _chunk_extend(
        self, params, chunk, mini, offs, c_true_len, c_valid, c_adapters
    ):
        """The chunk half of a fused tick+chunk call (shared by the
        plain and jump variants): extend the carried [K, S_max]
        mini cache by one [K, C] chunk at the host-stamped offsets and
        gather each row's final-prompt-position logits."""
        mini = mini._replace(length=offs)
        c = chunk.shape[1]
        if self._is_moe:
            valid = c_valid[:, None] & (
                (offs[:, None] + jnp.arange(c)[None, :])
                < c_true_len[:, None]
            )
        else:
            valid = None
        logits, mini = self.engine.decode_forward(
            params, chunk, mini, valid=valid, ring=self._ring,
            lora_idx=c_adapters,
        )
        last = c_true_len - 1
        idx = jnp.clip(last - offs, 0, c - 1)
        sel = jnp.take_along_axis(logits, idx[:, None, None], axis=1)[:, 0]
        return mini, sel.astype(jnp.float32)

    def _ilv_finish_impl(
        self, cache, mini, row, slot, n, sel, seeds, temps, ks, ps,
        g0, g_allow, g_trans,
    ):
        """Final-chunk completion for one interleaved admission: copy
        mini row `row` into the shared cache at `slot` with true length
        `n` (the _merge_row machinery) and sample the first token from
        that row's final-position logits `sel` (step 0, matching
        _chunked_finish)."""

        def pick(m):
            return jax.lax.dynamic_slice_in_dim(m, row, 1, axis=1)

        picked = llama_mod.map_planes(
            pick, mini, length=jnp.full((1,), n, jnp.int32))
        if self._paged:
            cache = self._paged_put(
                cache, picked, jnp.reshape(slot, (1,)),
                jnp.reshape(n, (1,)), jnp.int32(0),
            )
        else:
            cache = _merge_row(cache, picked, slot, n)
        fl = jax.lax.dynamic_slice_in_dim(sel, row, 1, axis=0)
        first, _ = masked_sample_dynamic(
            fl, seeds, jnp.int32(0), temps, ks, ps, g0, g_allow, g_trans
        )
        return first, cache

    def _first_token_impl(
        self, logits, idx, seeds, temps, ks, ps, g0, g_allow, g_trans
    ):
        last = jnp.take_along_axis(logits, idx[:, None, None], axis=1)[:, 0]
        first, _ = masked_sample_dynamic(
            last, seeds, jnp.int32(0), temps, ks, ps, g0, g_allow, g_trans
        )
        return first

    def _seat_slot(self, slot_idx: int, request: _Request) -> None:
        """Seat `request` in its slot while its admission program is
        queued: everything the next tick's dispatch reads of the row
        but its first token, which stays on the device
        (`_patch_seated` scatters it into the tick's token feedback
        from the program's own output). No device result is read
        here; `_settle_slot` does the rest once the token is on the
        host (the two halves of what was one activation: the round
        seats, the tick step settles after its dispatch)."""
        slot = self.slots[slot_idx]
        slot.active = True
        slot.request = request
        slot.generated = 0
        slot.max_new = request.max_new
        slot.done = False
        slot.reserved = False
        if request.parked:
            # Resume completes a preempt cycle (serving/scheduler.py):
            # the parked request is decoding again, its demoted pages
            # restored (or recomputed) by the prefill just queued.
            request.parked = False
            if self.sched is not None:
                self.sched.resumes += 1
        # First decode tick this request can participate in is the NEXT
        # dispatch (ticks is the count of dispatched ticks; records are
        # 1-based on the same counter).
        request.first_tick = self.timing["ticks"] + 1
        self.recorder.note_admit()
        # Grammar state: the row's emit tracker starts at the admission
        # state (the settle's _emit advances it through the first
        # token). The slot's NEXT-tick state is the post-first-token
        # state, which only the host can step to: a round with a
        # grammar row settles before the tick is dispatched
        # (_prefill_into_slots), and `_settle_slot` patches it into the
        # mirror and the device twin. An unconstrained row's is 0.
        request.gcur = self._g0(request)
        self.gstates[slot_idx] = 0
        self.temps[slot_idx] = request.sampling.temperature
        self.top_ks[slot_idx] = request.sampling.top_k
        self.top_ps[slot_idx] = request.sampling.top_p
        self.seeds[slot_idx] = request.seed & 0xFFFFFFFF
        self.adapter_ids[slot_idx] = request.adapter
        # Jump-ahead eligibility: only a live constrained request that
        # has not been jump-degraded may multi-token advance.
        self.jump_ok[slot_idx] = bool(
            self._jump_max
            and request.grammar is not None
            and not request.jump_degraded
        )
        # What is NOT here, and waits for the token (`_settle_slot`,
        # below `cache_bytes`): the host mirror `cur_tokens`, the
        # request's `t_admit` stamp, `pages.register` (the prompt's
        # full pages enter the index once the first token proves the
        # prefill ran) and the first token's `_emit`. A seated row is
        # active and has emitted nothing: a failure before its settle
        # replays it from its prompt (_recover_after_tick_failure).
        #
        # (This function keeps the place and the length `_activate_slot`
        # had. The line numbers of everything above `warmup()`'s last
        # program call are part of the compile cache's key of every
        # program with a kernel in it, so a host-only change that
        # moves none of them starts warm on the chip beside its
        # parent; the new helpers live below `cache_bytes`. See the
        # verify skill's note on keeping a chip call warm: PR 49's,
        # in `.claude/skills/verify/SKILL.md`.)

    # -- public API ---------------------------------------------------------

    def warmup(self) -> None:
        """Compile the decode tick and both admission programs (for the
        smallest prompt bucket) with inert inputs BEFORE serving —
        otherwise the cold compiles land inside the first requests'
        latency (about 13 s per program on a v5e, PERF.md).

        PRE-SERVING ONLY: the _admit_single call overwrites slot 0's
        cache rows (no valid mask on that path) and the tick advances
        every row's length counter — harmless while no slot is active,
        corrupting if ever run under load. Each call donates and
        returns the cache, so reassign it."""
        s = bucket_len(1, maximum=self.max_seq)
        b = len(self.slots)
        zeros1 = np.zeros((1, s), np.int32)
        zlen1 = np.zeros((1,), np.int32)
        zseed1 = np.zeros((1,), np.uint32)
        zf1 = np.zeros((1,), np.float32)
        zi1 = np.zeros((1,), np.int32)
        of1 = np.ones((1,), np.float32)
        # Grammar tables ride every sampling program as fixed-shape
        # args; state 0 (accept-all) keeps warmup numerics inert.
        g_allow, g_trans = self._grammar_tables()
        zgb, warm = np.zeros((b,), np.int32), {}  # warm: rows -> first
        warm[1], self.cache = self._admit_single(
            self.engine.params, jnp.asarray(zeros1), jnp.asarray(zlen1),
            self.cache, jnp.int32(0), jnp.asarray(zseed1),
            jnp.asarray(zf1), jnp.asarray(zi1), jnp.asarray(of1),
            jnp.asarray(zi1), jnp.asarray(zi1), g_allow, g_trans,
            self._state_io([], 1),
        )
        warm[b], self.cache = self._admit_full(
            self.engine.params, jnp.asarray(np.zeros((b, s), np.int32)),
            jnp.asarray(np.zeros((b,), np.int32)), self.cache,
            jnp.asarray(np.zeros((b,), bool)),
            jnp.asarray(np.zeros((b,), np.uint32)),
            jnp.asarray(np.zeros((b,), np.float32)),
            jnp.asarray(np.zeros((b,), np.int32)),
            jnp.asarray(np.ones((b,), np.float32)),
            jnp.asarray(np.zeros((b,), np.int32)),
            jnp.asarray(zgb), g_allow, g_trans, self._state_io([], b),
        )
        # Token/grammar-state feedback rides the tick as the COMMITTED
        # device twin (_snap_dev) at real dispatch: warmup compiles
        # against the same placement, or the FIRST live request pays a
        # post-warmup jit(_tick_impl) (the compile watcher caught it).
        # Both lengths _tick_steps can pick, the full one first.
        for steps in sorted({self._steps_per_tick, self._short_steps}, reverse=True):
            _, self.cache, _ = self._tick(
                self.engine.params, self._snap_dev(self.cur_tokens),
                self.cache,
                jnp.asarray(self.seeds), jnp.int32(0),
                jnp.asarray(self.temps), jnp.asarray(self.top_ks),
                jnp.asarray(self.top_ps),
                jnp.asarray(np.zeros((b,), bool)),
                jnp.asarray(np.zeros((b,), np.int32)),
                self._snap_dev(self.gstates), g_allow, g_trans, steps=steps,
            )
        if self._jump_max:
            # The jump tick alternates with the plain tick at
            # dispatch time (jump only while some slot can jump) —
            # BOTH must be warm or the first constrained request
            # pays a post-warmup compile (compile-watcher contract).
            # All-False jump_ok: every row runs a zero-length run,
            # advancing length pointers by 1 like the plain tick —
            # harmless pre-serving.
            _, _, self.cache, _, _ = self._tick_jump(
                self.engine.params, self._snap_dev(self.cur_tokens),
                self.cache,
                jnp.asarray(self.seeds), jnp.int32(0),
                jnp.asarray(self.temps), jnp.asarray(self.top_ks),
                jnp.asarray(self.top_ps),
                jnp.asarray(np.zeros((b,), bool)),
                jnp.asarray(np.zeros((b,), np.int32)),
                self._snap_dev(self.gstates), g_allow, g_trans,
                self._g_jlen_dev, self._g_jtok_dev,
                self._g_jstate_dev,
                jnp.asarray(np.zeros((b,), bool)),
            )
        # Fused chunked-admission programs: one a row bucket, whatever
        # the prompts' depths (the grid's depth is `_grid_chunks`, and
        # each row's chunk count is read on the device), so these calls
        # compile every program a cold long prompt can run.
        b_rows = len(self.slots)
        zlenb = np.zeros((b_rows,), np.int32)
        # Out-of-range slot indices: the insert scatter drops every
        # warmup row, leaving the cache untouched.
        zslotb = np.full((b_rows,), b_rows, np.int32)
        zseedb = np.zeros((b_rows,), np.uint32)
        zfb = np.zeros((b_rows,), np.float32)
        zib = np.zeros((b_rows,), np.int32)
        ofb = np.ones((b_rows,), np.float32)
        c = min(self.cfg.prefill_chunk, self.max_seq)
        if self.cfg.prefill_chunk < self._fit_limit or self._ring:
            # Every reachable row bucket (R = 1, 2, 4 .. B), all rows
            # empty: no chunk runs.
            r_buckets = []
            r_bucket = 1
            while r_bucket < self._mini_rows:
                r_buckets.append(r_bucket)
                r_bucket *= 2
            # Groups clamp to the pool size (or the family's rows a
            # call), so non-pow2 pools reach R = B itself
            # (_admit_chunked_group's min(b, bucket)).
            r_buckets.append(self._mini_rows)
            for r_bucket in r_buckets:
                warm[r_bucket], self.cache = self._admit_chunked(
                    self.engine.params,
                    jnp.asarray(np.zeros(
                        (r_bucket, self._grid_chunks, c), np.int32)),
                    jnp.asarray(zlenb[:r_bucket]), self.cache,
                    jnp.asarray(zslotb[:r_bucket]),
                    jnp.asarray(zseedb[:r_bucket]),
                    jnp.asarray(zfb[:r_bucket]),
                    jnp.asarray(zib[:r_bucket]),
                    jnp.asarray(ofb[:r_bucket]),
                    jnp.asarray(zib[:r_bucket]),
                    jnp.asarray(zib[:r_bucket]), g_allow, g_trans,
                    self._state_io([], r_bucket),
                )
        if self._ilv_k and (
            self.cfg.prefill_chunk < self._fit_limit or self._ring
        ):
            # Fused tick+chunk + row-finish programs (ONE shape each):
            # a long prompt landing mid-decode must not pay a cold
            # compile inside the very stall interleaving exists to
            # bound. Inert inputs: no valid chunk rows, no active
            # slots, finish into slot 0 with length 0 — pre-serving
            # only, like every other warmup call here.
            if self._ilv_mini is None:
                self._ilv_mini = self._make_mini(self._ilv_k, self.max_seq)
            k_rows = self._ilv_k
            _, self.cache, self._ilv_mini, sel, _ = self._tick_chunk(
                self.engine.params, self._snap_dev(self.cur_tokens),
                self.cache, jnp.asarray(self.seeds), jnp.int32(0),
                jnp.asarray(self.temps), jnp.asarray(self.top_ks),
                jnp.asarray(self.top_ps),
                jnp.asarray(np.zeros((b,), bool)),
                jnp.asarray(np.zeros((b,), np.int32)),
                jnp.asarray(np.zeros((k_rows, c), np.int32)),
                self._ilv_mini,
                jnp.asarray(np.zeros((k_rows,), np.int32)),
                jnp.asarray(np.ones((k_rows,), np.int32)),
                jnp.asarray(np.zeros((k_rows,), bool)),
                jnp.asarray(np.zeros((k_rows,), np.int32)),
                self._snap_dev(self.gstates), g_allow, g_trans,
            )
            if self._jump_max:
                # Jump + interleave composes (same alternating-
                # dispatch reasoning as the plain/jump pair above).
                (
                    _, _, self.cache, _, _, self._ilv_mini, sel
                ) = self._tick_jump_chunk(
                    self.engine.params,
                    self._snap_dev(self.cur_tokens),
                    self.cache, jnp.asarray(self.seeds),
                    jnp.int32(0),
                    jnp.asarray(self.temps),
                    jnp.asarray(self.top_ks),
                    jnp.asarray(self.top_ps),
                    jnp.asarray(np.zeros((b,), bool)),
                    jnp.asarray(np.zeros((b,), np.int32)),
                    jnp.asarray(np.zeros((k_rows, c), np.int32)),
                    self._ilv_mini,
                    jnp.asarray(np.zeros((k_rows,), np.int32)),
                    jnp.asarray(np.ones((k_rows,), np.int32)),
                    jnp.asarray(np.zeros((k_rows,), bool)),
                    jnp.asarray(np.zeros((k_rows,), np.int32)),
                    self._snap_dev(self.gstates), g_allow, g_trans,
                    self._g_jlen_dev, self._g_jtok_dev,
                    self._g_jstate_dev,
                    jnp.asarray(np.zeros((b,), bool)),
                )
            _, self.cache = self._ilv_finish(
                self.cache, self._ilv_mini, jnp.int32(0), jnp.int32(0),
                jnp.int32(0), sel, jnp.asarray(zseed1),
                jnp.asarray(zf1), jnp.asarray(zi1), jnp.asarray(of1),
                jnp.asarray(zi1), g_allow, g_trans,
            )
        if self._paged:
            # Paged prefix-reuse admission ladder: every suffix-width
            # bucket a page hit can pick, trickle (R=1) and wave (R=B)
            # row shapes — no live request pays a cold compile.
            # All-sentinel gather tables and out-of-range slots keep it
            # inert (reads clip to junk that is never merged; merges
            # drop). Deeper [R, T>1, C] suffix grids compile on their
            # first long shared prompt, exactly like the cold chunked
            # grids.
            width = 32
            while width <= bucket_len(c, maximum=self.max_seq):
                for r_rows in sorted({1, self._mini_rows}):
                    gtw = self._gather_tables(r_rows)
                    warm[r_rows], self.cache = self._admit_paged_pfx(
                        self.engine.params,
                        jnp.asarray(np.zeros((r_rows, 1, width), np.int32)),
                        jnp.asarray(zlenb[:r_rows]), self.cache,
                        jnp.asarray(zslotb[:r_rows]), jnp.asarray(gtw),
                        jnp.int32(0), jnp.int32(0),
                        jnp.asarray(zseedb[:r_rows]),
                        jnp.asarray(zfb[:r_rows]),
                        jnp.asarray(zib[:r_rows]),
                        jnp.asarray(ofb[:r_rows]),
                        jnp.asarray(zib[:r_rows]),
                        jnp.asarray(zib[:r_rows]), g_allow, g_trans,
                        self._state_io([], r_rows),
                    )
                width *= 2
        # The seat's scatter of a program's first tokens into the
        # tick's token feedback (_patch_seated), one a row count, from
        # the programs' own outputs (their placement is part of the
        # scatter's signature). Every slot index out of range: nothing
        # is written, and the twins read what the first dispatch would
        # have snapped.
        for r_rows, first in warm.items():
            self._patch_seated(first, np.full((r_rows,), b, np.int32))
        jax.block_until_ready(self.cache.k)

    def start(self) -> None:
        if self._task is None:
            self._stopping = False
            self._loop_ref = asyncio.get_running_loop()
            self._task = self._loop_ref.create_task(self._loop())

    # What an executor call of the loop is, by the function it runs
    # (HandoffRecord.kind); anything else is a queued host op.
    _HANDOFF_KINDS = {
        "_tick_step": "tick", "_prefill_into_slots": "admit",
        "_drain_inflight": "drain", "_preempt_slots": "preempt",
        "_settle_round": "settle",
    }

    async def _in_executor(self, loop, fn, *args):
        """Run one of the loop's device-bound calls in the executor.
        Cancelling the loop task abandons the await, not the thread:
        the lock is how stop() waits for a call already running, and
        the _stopping check keeps one that had not started yet from
        running after stop() returned.

        Four stamps per call partition the loop's turn: submitted
        (here), started (on the thread, lock held), ended (on the
        thread), resumed (this coroutine runs again). They feed
        loop_ms and one HandoffRecord; a cancelled call records
        nothing."""
        stamps = [0.0, 0.0]

        def call():
            with self._exec_lock:
                stamps[0] = stamps[1] = time.perf_counter()
                try:
                    if not self._stopping:
                        return fn(*args)
                finally:
                    stamps[1] = time.perf_counter()

        tick_seq = self.timing["ticks"] + 1
        submitted = time.perf_counter()
        host_s = (
            submitted - self._loop_resumed
            if self._loop_resumed is not None else 0.0
        )
        try:
            return await loop.run_in_executor(None, call)
        finally:
            resumed = time.perf_counter()
            if stamps[0]:  # the call ran (not cancelled in the queue)
                started, ended = stamps
                self.loop_ms["host"] += host_s * 1000.0
                self.loop_ms["exec_wait"] += (started - submitted) * 1000.0
                self.loop_ms["work"] += (ended - started) * 1000.0
                self.loop_ms["lag"] += (resumed - ended) * 1000.0
                self.loop_calls += 1
                self.recorder.note_handoff(
                    self.loop_calls,
                    self._HANDOFF_KINDS.get(
                        getattr(fn, "__name__", ""), "host_op"
                    ),
                    host_s * 1000.0, submitted, started, ended, resumed,
                    tick_seq,
                )
            self._loop_resumed = resumed

    def _loop_park(self) -> None:
        """The loop is about to wait with nothing to do: close the
        open host interval, so idle time belongs to no part."""
        if self._loop_resumed is not None:
            self.loop_ms["host"] += (
                time.perf_counter() - self._loop_resumed
            ) * 1000.0
            self._loop_resumed = None

    async def stop(self) -> None:
        self._stopping = True
        self._wake.set()
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            self._task = None
            # The cancelled task's executor call (a tick, an admission)
            # may still be on its thread, holding device arrays and
            # mutating slot state: wait it out, so that "stopped" means
            # nothing of this batcher runs any more.
            await asyncio.get_running_loop().run_in_executor(
                None, self._exec_barrier
            )
        # Fail queued host ops LOUDLY: a TransferKV handler awaiting an
        # import must get an error, not hang on a future the dead loop
        # will never resolve.
        while self._host_ops:
            _, fut = self._host_ops.popleft()
            if not fut.done():
                fut.set_exception(RuntimeError("batcher stopped"))
        # Release the host pool's file tier (appends are flushed per
        # record, so the warm-restart log is already durable; the pool
        # keeps serving RAM-only if the batcher restarts in-process).
        if self.host_pool is not None:
            self.host_pool.close()

    def _exec_barrier(self) -> None:
        with self._exec_lock:
            pass

    async def acquire_adapter(self, name: str):
        """Resolve an adapter NAME to a pinned arena row (dynamic-
        registry mode, serving/adapter_arena.py) — the load's batched
        H2D factor write runs through the serialized run_host_op
        stream BETWEEN ticks, never racing a dispatch. Returns the
        AdapterLease; pass it (and the name, as adapter_key) to
        submit(), which releases it on every terminal path. Typed
        failures propagate: UnknownAdapterError (caller's error),
        AdapterExhaustedError (overload ladder), AdapterLoadError
        (degrade loudly — never silently serve base weights)."""
        arena = getattr(self.engine, "adapter_arena", None)
        if arena is None:
            raise RuntimeError(
                "no dynamic adapter arena (serving.lora.registry unset); "
                "resolve names via engine.resolve_adapter"
            )
        return await self.run_host_op(lambda: arena.acquire(name))

    def release_adapter(self, lease) -> None:
        """Return an acquired lease that never reached submit() (shed/
        validation failures on the caller's side). Host bookkeeping
        only — safe from the loop thread, idempotent like the
        in-request release."""
        if lease is not None:
            self.engine.adapter_arena.release(lease)

    async def run_host_op(self, fn):
        """Run `fn()` (host + device work) in the batcher's serialized
        executor stream — between ticks and admission rounds, never
        concurrent with them. The entry point for externally triggered
        arena work (KV page export/import); returns fn's result or
        re-raises its exception. The batcher loop must be running."""
        if self._task is None or self._stopping:
            raise RuntimeError("batcher is not running")
        fut = asyncio.get_running_loop().create_future()
        self._host_ops.append((fn, fut))
        self._wake.set()
        return await fut

    async def _drain_host_ops(self, loop) -> None:
        """Execute queued host ops in FIFO order, one executor call
        each (same serialization contract as ticks/admissions). Op
        failures resolve the caller's future and never kill the loop —
        a bad import is the transfer's problem, not the pool's."""
        while self._host_ops:
            fn, fut = self._host_ops.popleft()
            try:
                result = await self._in_executor(loop, fn)
            except asyncio.CancelledError:
                if not fut.done():
                    fut.set_exception(RuntimeError("batcher stopped"))
                raise  # batcher shutdown cancels the loop task
            except Exception as exc:  # noqa: BLE001 — delivered, not dropped
                if not fut.done():
                    fut.set_exception(exc)
            else:
                if not fut.done():
                    fut.set_result(result)

    def submit(
        self,
        prompt: list[int],
        max_new: int,
        sampling: SamplingConfig,
        seed: int = 0,
        unary: bool = False,
        adapter: int = 0,
        trace_id: str = "",
        grammar: Optional[CompiledGrammar] = None,
        adapter_key: str = "",
        adapter_lease=None,
        tenant: str = "",
        qos_class: str = "",
    ) -> AsyncIterator[tuple[list[int], Optional[str]]]:
        """Enqueue a request; yields (token_ids_chunk, finish_reason)
        pairs; finish_reason is set on the final chunk. `unary=True`
        (non-streaming consumers): one terminal chunk with all tokens —
        same iterator contract, a fraction of the cross-thread events
        (see _Request.unary). `adapter`: LoRA adapter row id (0 = base;
        resolve names via engine.resolve_adapter, or acquire_adapter
        under the dynamic arena — which also yields `adapter_lease`,
        the residency pin this request holds until its terminal chunk,
        and `adapter_key`, the stable name the paged-KV hash chains
        key on). `trace_id`: the
        gateway trace this request serves — stamped into the flight
        recorder's request/tick records so one id walks span → request
        record → tick records. `grammar`: a CompiledGrammar
        (ggrmcp_tpu/grammar) every sampled token must satisfy — decode
        is DFA-masked on device, finish_reason "grammar_complete" fires
        when the accepting sink is reached, and GrammarCapacityError is
        raised here, eagerly, when the table arena cannot host another
        distinct schema.

        Validation, the admission-cap check, and the enqueue all run
        HERE, eagerly, not at first iteration of the returned
        generator: a caller that enqueues several requests before
        consuming any sees bad-argument errors AND OverloadedError at
        the call site — and the caps, the queued_tokens gauge, and the
        queue-deadline clock all agree on when a request starts
        occupying bounded queue capacity.

        Raises OverloadedError (load shedding) when batching.max_pending
        or max_queue_tokens would be exceeded."""
        # Range-check the adapter row (names resolve upstream):
        # jnp.take clips out-of-range gathers, which would silently
        # serve the WRONG adapter's factors.
        arena = getattr(self.engine, "adapter_arena", None)
        n_adapters = (
            arena.rows if arena is not None
            else len(getattr(self.engine, "lora_names", {}))
        )
        if not 0 <= adapter <= n_adapters:
            raise ValueError(
                f"adapter id {adapter} out of range (0..{n_adapters})"
            )
        if adapter and not adapter_key:
            if adapter_lease is not None:
                adapter_key = adapter_lease.name
            elif arena is None:
                # Static mode: rows are stable 1:1 with names, so a
                # row-derived key is a valid stable domain for callers
                # that skipped name resolution (direct batcher tests).
                adapter_key = f"row:{adapter}"
            else:
                # Arena rows are REUSED after eviction — a row-derived
                # key would alias one tenant's KV to another's. Name
                # your adapter (acquire_adapter returns the lease).
                raise ValueError(
                    "dynamic adapter arena: submit needs adapter_key "
                    "(or the AdapterLease from acquire_adapter) — row "
                    "ids are not stable KV-keying identities"
                )
        # Reserve cache positions for tick overshoot: a tick may run
        # past a slot's max_new by up to steps_per_tick-1 positions
        # before the host masks the extra tokens — one further full
        # tick under pipelining (emission lags the dispatch by a tick),
        # and up to jump_max further positions when this request's
        # grammar lets a jump tick write a forced run (_reserve_for).
        prompt, max_new = fit_request(
            prompt, max_new,
            self._fit_limit - self._reserve_for(grammar is not None),
        )
        cap = self.cfg.max_pending
        if cap > 0 and self.pending.qsize() >= cap:
            self.shed += 1
            # Submit-time shed raises before the request object exists:
            # the SLO/tenant ledgers must still see it — typed into the
            # unevaluated partition, never dropped from the total.
            self.slo.record_shed(qos_class)
            self.tenants.record_shed(tenant)
            raise OverloadedError(
                f"admission queue full ({cap} requests pending)",
                reason="requests",
                retry_after_s=retry_after_for(self.sched_cfg, qos_class),
            )
        tcap = self.cfg.max_queue_tokens
        if (
            tcap > 0 and not self.pending.empty()
            and self.pending.token_count + len(prompt) > tcap
        ):
            # The non-empty guard keeps a single prompt longer than
            # the whole cap admissible on an idle queue: a
            # misconfigured cap must degrade to FIFO, not to a
            # permanent 429 for every large request.
            self.shed += 1
            self.slo.record_shed(qos_class)
            self.tenants.record_shed(tenant)
            raise OverloadedError(
                f"admission queue token budget full ({tcap} tokens)",
                reason="tokens",
                retry_after_s=retry_after_for(self.sched_cfg, qos_class),
            )
        # Arena residency is taken HERE (host-side bookkeeping only —
        # the device upload happens lazily in the executor), after the
        # overload caps: a shed request must not hold table rows.
        handle = self.arena.acquire(grammar) if grammar is not None else None
        request = _Request(
            prompt=prompt, max_new=max_new, sampling=sampling, seed=seed,
            unary=unary, adapter=adapter, trace_id=trace_id,
            n_prompt=len(prompt), grammar=handle,
            adapter_key=adapter_key, adapter_lease=adapter_lease,
            tenant=tenant, qos_class=qos_class,
        )
        request.t_submit = time.perf_counter()
        self.pending.put_nowait(request)
        self._wake.set()
        return self._consume(request)

    async def _consume(
        self, request: _Request
    ) -> AsyncIterator[tuple[list[int], Optional[str]]]:
        try:
            while True:
                ids, reason = await request.out.get()
                yield ids, reason
                if reason is not None:
                    return
        finally:
            request.cancelled = True

    def _say_row_states(self) -> None:
        """What the pool really holds, leaf by leaf off the device
        arrays, once at start-up: the benchmark's check holds the bytes
        an entry to the precisions its configuration states."""
        state = tuple(self.cache.state)
        entries = state[0].shape[1]
        logger.info(
            "row states: %d entries x %d B an entry (%s)", entries,
            sum(leaf.nbytes for leaf in state) // entries,
            ", ".join(f"{leaf.dtype} {list(leaf.shape)}" for leaf in state))

    def _pool_rows(self, slots):
        """The state pool's entries of an admission group's rows (traced
        inside the program): a real row's is its slot's; a bucket's
        padding row carries slot index B, out of range for the slots
        but the FIRST SNAPSHOT'S entry of the pool, which its zeroed
        restore would overwrite: such a row gets an entry no pool has
        (reads clip, writes drop). A lone row is never padding."""
        if slots.shape[0] == 1:
            return slots
        return jnp.where(slots < len(self.slots), slots, self._no_entry)

    def cache_bytes(self) -> int:
        """KV-cache HBM: the shared slot pool (or paged arena + block
        tables), the rows' state pool where the family has one, and the
        interleave mini cache (K admission rows) once allocated."""
        def planes(cache):
            return sum(p.nbytes for p in llama_mod.cache_planes(cache))

        total = planes(self.cache) + sum(p.nbytes for p in self.cache.state)
        if self._paged:
            total += self.cache.table.nbytes + sum(
                a.nbytes for a in self.cache.window or ())
        if self._ilv_mini is not None:
            total += planes(self._ilv_mini)
        return total

    def _gather_tables(self, r: int) -> np.ndarray:
        """An admission group's gather tables, every entry unmapped:
        [r, W], or [r, 2, W] where the cache has two kinds of page (a
        row of each kind's table; reads of an unmapped entry clip)."""
        if self._window:
            return np.full(
                (r, 2, self._table_width),
                max(self._n_pages, self._n_pages_w), np.int32)
        return np.full((r, self._table_width), self._n_pages, np.int32)

    @staticmethod
    def _next_query(slot) -> int:
        """The position of a live row's next query as far as the host
        has seen it emit: its newest token's (whose K/V that step
        writes). The device may be ahead by the ticks in flight, never
        behind."""
        return len(slot.request.prompt) + max(slot.generated, 1) - 1

    def _window_release(self, steps: int) -> None:
        """A tick's collect, for a cache with window pages: every live
        row lets go of the pages now wholly behind the window of its
        next query (serving/pages.py WindowPages.release; a
        `ggrmcp.pages.window_release` span while a capture runs), and
        the keys its window layers read in the tick's `steps` steps
        are counted beside the keys its context holds."""
        with tracing.annotation("ggrmcp.pages.window_release"):
            for i, slot in enumerate(self.slots):
                if not slot.active or slot.request is None:
                    continue
                pos = self._next_query(slot)
                if self.pages.window.release(i, pos):
                    self._window_dirty = True
                per = self._window_layers * steps
                self.window_keys["read"] += per * min(pos + 1, self._window)
                self.window_keys["context"] += per * (pos + 1)

    def _tick_steps(self) -> int:
        """The length of the plain tick about to be dispatched, from
        what the batcher sees at that moment. Under the pipeline a tick
        is dispatched before the one in flight is collected, so an
        admission waits out the tick in flight and is launched behind
        the next: two tick lengths between a client's answer and its
        first token (PERF.md section 5). While a request waits for a
        slot, or a slot is free for the next one to arrive, the tick is
        short (the same program at a smaller static step count, warmed
        beside the full one); with every slot live and nobody waiting
        there is nothing to admit at the next collect, and the full
        length costs the fewest host turns a token. Without the
        pipeline the collect follows its own dispatch and an admission
        waits behind no tick: `_short_steps` is the full length there.
        The tick after a LONG admission is full as well. An admission
        round's programs run behind the tick in flight, to their end,
        before the tick dispatched here runs (it is queued behind
        them), so between two rounds the decoding rows advance by one
        tick, this one. A pass over a
        chunk of `prefill_chunk` tokens takes a decode step's time at
        least (the same weights, far more arithmetic), so once the
        programs since the last dispatch ran more chunk tokens than a
        full tick's steps times `prefill_chunk`, the rows have stood
        still for longer than a full tick: they get one. A count the
        batcher keeps, not a clock: the same arrivals give the same
        ticks. (Where cold documents of 6-12k tokens follow one another,
        the kanana cell, short ticks there halved what a decoding row
        advanced a round and moved the median call up: PERF.md section
        6, PR 50.) The reserves stay derived from the full length, the
        upper bound. `pending` is the loop thread's; this reads its
        emptiness alone (one deque truth test) from the executor thread
        that owns the slots."""
        if self._admit_run > self._steps_per_tick * self.cfg.prefill_chunk:
            return self._steps_per_tick
        if not self.pending.empty() or self._free_slots():
            return self._short_steps
        return self._steps_per_tick

    def stall_snapshot(self) -> list[float]:
        """Snapshot of recent decode-stall samples (ms between
        consecutive emissions to a live slot) — the in-process view the
        interleave tests read; concatenated across tiers by the tiered
        facade."""
        return list(self._stall_records)

    def stats(self) -> dict:
        """Live counters + flight-recorder histograms for the
        ServingStats RPC / diagnostics."""
        return {
            **self.counter_stats(),
            **self.recorder.histogram_stats(),
            # Structured (repeated-message) SLO/tenant fragments ride
            # OUTSIDE counter_stats: the tiered facade's sum-by-key
            # aggregation only handles scalars — it merges these via
            # SloAccount/TenantTable.merged_stats instead, like the
            # histograms. Empty dicts when the plane is disabled.
            **self.slo.stats(),
            **self.tenants.stats(),
        }

    def flight_snapshot(
        self,
        max_ticks: int = 128,
        max_requests: int = 128,
        trace_id: str = "",
        tenant: str = "",
    ) -> tuple[list, list]:
        """(tick records, request records), oldest first, optionally
        filtered to the records a trace id participated in — the
        DebugService.GetFlightRecord body (sidecar) and the bench's
        TTFT source. `tenant` narrows the REQUEST records to one
        tenant's (ticks are shared across tenants and stay unfiltered,
        matching the FlightRecordRequest.tenant contract). The
        admission and hand-off rings ride beside it: loop_snapshot."""
        ticks = self.recorder.tick_snapshot()
        requests = self.recorder.request_snapshot()
        if trace_id:
            ticks = [t for t in ticks if trace_id in t.trace_ids]
            requests = [r for r in requests if r.trace_id == trace_id]
        if tenant:
            requests = [r for r in requests if r.tenant == tenant]
        return ticks[-max(1, max_ticks):], requests[-max(1, max_requests):]

    def loop_snapshot(
        self, max_records: int = 128, trace_id: str = ""
    ) -> tuple[list, list]:
        """(admission records, hand-off records), oldest first, each
        bounded like the ticks. A trace id narrows the admissions to
        the rounds that admitted it and leaves the hand-offs out (they
        carry none)."""
        admissions = self.recorder.admission_snapshot()
        handoffs = self.recorder.handoff_snapshot()
        if trace_id:
            admissions = [a for a in admissions if trace_id in a.trace_ids]
            handoffs = []
        n = max(1, max_records)
        return admissions[-n:], handoffs[-n:]

    def request_record(self, trace_id: str):
        """Latest flight-recorder request record for a trace id (the
        sidecar's span-attribution lookup)."""
        return self.recorder.request_record(trace_id)

    # The ledger components this batcher reports as ServingStats
    # memory_*_bytes scalars: engine-level (scope "", MAX-aggregated
    # across tiers) then per-tier (summed). Mirrors the proto field
    # set; the gateway renders them as ONE
    # gateway_backend_memory_bytes{target, component} family.
    _LEDGER_ENGINE_COMPONENTS = ("weights", "lora")
    _LEDGER_BATCHER_COMPONENTS = (
        "kv_arena", "block_tables", "ilv_mini", "grammar_arena",
        "tick_state",
    )

    def _memory_stats(self) -> dict:
        """ServingStats memory_*_bytes fields from the engine ledger
        (all zero when the ledger is off — the obs-off contract)."""
        comp = self.engine.ledger.component_bytes(max_age_s=1.0)
        out = {
            f"memory_{name}_bytes": comp.get(("", name), 0)
            for name in self._LEDGER_ENGINE_COMPONENTS
        }
        out.update({
            f"memory_{name}_bytes": comp.get((self._ledger_scope, name), 0)
            for name in self._LEDGER_BATCHER_COMPONENTS
        })
        return out

    def _ledger_tick_snapshot(self) -> dict:
        """component -> bytes for THIS tick's record (the timeline's
        counter tracks). TTL-cached in the ledger: device shapes only
        change on rebuild events, so the per-tick cost is a dict copy."""
        comp = self.engine.ledger.component_bytes(max_age_s=1.0)
        return {
            name: b
            for (scope, name), b in comp.items()
            if scope in ("", self._ledger_scope) and b
        }

    def counter_stats(self) -> dict:
        """Summable counters only (no percentiles) — what the tiered
        facade aggregates across tiers before computing percentiles
        ONCE over the concatenated records. Reads are loop-side
        snapshots of host state the executor mutates — monotonic
        counters and slot flags, safe to read stale."""
        t = self.timing
        counts = self.model_counts
        return {
            # Device-memory ledger components (serving/memory_ledger.py
            # — "phase attribution for bytes"): weights/lora are
            # engine-level (MAX_STAT_KEYS), the rest are this batcher's
            # own allocations and sum across tiers.
            **self._memory_stats(),
            # Mesh identity (docs/tensor_parallel_serving.md): the
            # tensor-axis size, total devices, human-readable shape,
            # and how many sharding specs compatible_spec downgraded to
            # replication — 0 downgrades is what makes "TP serving" a
            # verified claim instead of a config setting.
            **self.engine.mesh_stats(),
            # Multi-LoRA serving (ops/lora.py + serving/adapter_arena
            # .py; all zeros when LoRA is off): registry size, rows
            # resident/total, dynamic loads/evictions/hits, cumulative
            # load wall time, and acquisitions shed typed when every
            # row was pinned. hits/(hits+loads) is the arena hit rate
            # the churn bench holds (docs/multi_lora.md).
            **self.engine.lora_stats(),
            "active_slots": self._active_count(),
            "total_slots": len(self.slots),
            "queued_requests": self.pending.qsize(),
            "kv_cache_bytes": self.cache_bytes(),
            "prefix_cache_hits": self.prefix_hits,
            "prefix_cache_misses": self.prefix_misses,
            "decode_steps": self.step_counter,
            "timed_out": self.timed_out,
            # Pending-depth gauges + overload/replay counters: queue
            # depth in prompt tokens (queued_requests above is the
            # depth in requests), submits shed with OverloadedError,
            # tick-failure replays, and replays that exhausted
            # tick_retry_limit and surfaced "error".
            "queued_tokens": self.pending.token_count,
            "shed_requests": self.shed,
            "replayed_requests": self.replayed,
            "replay_exhausted": self.replay_exhausted,
            # Preemptive scheduler plane (serving/scheduler.py; all 0
            # when serving.scheduler is off): demote-don't-kill
            # preemptions, completed resumes, typed preempt failures,
            # the currently-parked gauge (resume-lane depth — every
            # entry holds host-tier KV), and admissions deferred by
            # the Sarathi prefill token budget.
            **(
                self.sched.counter_stats(
                    parked=self.pending.parked_count()
                )
                if self.sched is not None else {
                    "sched_preemptions": 0, "sched_resumes": 0,
                    "sched_preempt_failures": 0, "sched_parked": 0,
                    "sched_budget_deferrals": 0,
                }
            ),
            # Paged KV plane (batching.paged_kv=on; all 0 when off):
            # arena occupancy gauges plus the sharing counters — pages
            # resident (live + reuse cache), pages referenced by 2+
            # slots right now, admissions that reused shared pages or a
            # CoW source, and divergent-page copy-on-writes.
            "window_keys_read": self.window_keys["read"],
            "window_keys_context": self.window_keys["context"],
            **(self.pages.stats() if self._paged else {
                "kv_pages_total": 0, "kv_pages_in_use": 0,
                "kv_pages_shared": 0, "paged_prefix_hits": 0,
                "paged_cow_copies": 0, "paged_pages_reused": 0,
                "paged_pages_admitted": 0,
                # Host tier (paged_kv_host_bytes; all 0 when paging or
                # the tier is off — the allocator's stats() carries
                # the live values when on).
                "kv_host_entries": 0, "kv_host_bytes_used": 0,
                "kv_host_budget_bytes": 0, "kv_host_file_entries": 0,
                "kv_host_file_bytes": 0, "kv_host_demotions": 0,
                "kv_host_restores": 0, "kv_host_bytes_demoted": 0,
                "kv_host_bytes_restored": 0,
                "kv_host_restore_failures": 0,
            }),
            # Interleaved (tick-fused) admission activity: chunks
            # piggybacked onto decode ticks / requests admitted that way.
            "interleaved_chunks": self.interleaved_chunks,
            "interleaved_admissions": self.interleaved_admissions,
            # Grammar-constrained decoding: tokens emitted under an
            # active DFA mask, and arena table rows currently resident
            # (state 0 + every cached grammar's states). The sidecar
            # adds the compile/cache-hit counters from its GrammarCache.
            "grammar_masked_tokens": self.grammar_tokens,
            "grammar_states_in_use": self.arena.states_in_use(),
            # Of `ticks`, those the sampler's gates could not shorten.
            "sampler_order_ticks": self.sampler_order_ticks,
            "sampler_mask_ticks": self.sampler_mask_ticks,
            # Jump-ahead constrained decoding (grammar.jump_max > 0):
            # forced tokens emitted by multi-token advances, runs
            # advanced, and runs the collect-side validator refused
            # (each one a typed degrade to one-token decoding).
            # grammar_jump_tokens / grammar_masked_tokens is the
            # forced-token fraction (docs/observability.md).
            "grammar_jump_tokens": self.grammar_jump_tokens,
            "grammar_jump_runs": self.grammar_jump_runs,
            "grammar_jump_fallbacks": self.grammar_jump_fallbacks,
            # Ticks dispatched, ticks collected, admission rounds: the
            # divisors of the phase sums below.
            "ticks": t["ticks"],
            # Of `ticks`, those dispatched at the short length because
            # a request waited or a slot was free (_tick_steps).
            "short_ticks": t["short_ticks"],
            "tick_collects": t["collects"],
            "admit_rounds": t["admit_rounds"],
            # Of `admit_rounds`, those whose following tick was
            # dispatched before their first tokens were read
            # (_tick_step settles them after its dispatch).
            "admit_rounds_deferred": t["admit_rounds_deferred"],
            # Tick-phase attribution (flight recorder PhaseTimer;
            # cumulative ms over collected ticks, divide by
            # tick_collects for per-tick means): admit = queue drain +
            # admission prefill preceding the tick, sync = host-state
            # snapshots, dispatch = jitted launch, wait = device wait +
            # transfer (in-flight), host = emission/finish bookkeeping.
            # The five sum to the cumulative tick duration_ms — no
            # unattributed time (docs/observability.md). Zeros when
            # serving.observability is disabled, like the histograms.
            **{
                f"tick_phase_{p}_ms": round(self.phase_ms[p], 2)
                for p in PHASE_NAMES
            },
            # The loop's turn (_in_executor): the four contiguous
            # parts of every executor call, summed while the batcher
            # had work, and busy = their sum by construction. work is
            # what the tick phases divide; the other three are the
            # hand-offs between event loop and executor, where the
            # device can idle and no phase looks.
            "loop_exec_wait_ms_sum": self.loop_ms["exec_wait"],
            "loop_exec_wait_ms_count": self.loop_calls,
            "loop_work_ms_sum": self.loop_ms["work"],
            "loop_lag_ms_sum": self.loop_ms["lag"],
            "loop_lag_ms_count": self.loop_calls,
            "loop_host_ms_sum": self.loop_ms["host"],
            "loop_busy_ms_sum": sum(self.loop_ms.values()),
            # Expert routing of the decode ticks (the latent-attention
            # family; 0 elsewhere), each summed over expert layers and
            # decode steps: distinct experts a valid token reached,
            # the largest load of one expert, routed pairs computed
            # here and routed pairs whose expert this chip does not
            # hold, and the (layer, step) count that divides them.
            "moe_experts_hit": counts.get("experts_hit", 0),
            "moe_load_max_sum": counts.get("load_max", 0),
            "moe_routed_pairs": counts.get("pairs", 0),
            "moe_pairs_absent": counts.get("pairs_absent", 0),
            "moe_layer_steps": counts.get("layer_steps", 0),
            # Sparse attention of the decode ticks (a model with an
            # indexer; 0 elsewhere), summed over layers and steps: keys
            # the selected queries attended, keys they could see, and
            # the (row, layer) instances in which a selection ran.
            "sparse_keys_selected": counts.get("sparse_selected", 0),
            "sparse_keys_visible": counts.get("sparse_visible", 0),
            "sparse_layer_steps": counts.get("sparse_layer_steps", 0),
            # Prompt tokens the admission programs computed, against
            # prompt tokens taken from shared pages or a prefix entry.
            "prefill_tokens_computed": self.prefill_tokens["computed"],
            "prefill_tokens_reused": self.prefill_tokens["reused"],
            # Token positions of the chunk rows those programs ran:
            # what prefill_tokens_computed fills.
            "prefill_chunk_tokens_run": self.prefill_tokens["chunk_run"],
        }

    # -- the loop -----------------------------------------------------------

    def _free_slots(self) -> list[int]:
        return [
            i for i, s in enumerate(self.slots)
            if not s.active and not s.reserved
        ]

    def _active_count(self) -> int:
        return sum(s.active for s in self.slots)

    def _ilv_busy(self) -> bool:
        """Interleaved admissions in flight (rows chunking or queued
        for a row) — the loop must keep ticking for them even with no
        active decode slot."""
        return any(r is not None for r in self._ilv_rows) or bool(
            self._ilv_pending
        )

    async def _loop(self) -> None:
        loop = asyncio.get_running_loop()
        while not self._stopping:
            await self._drain_host_ops(loop)
            if self.sched is not None:
                await self._maybe_preempt(loop)
            admitted = await self._admit()
            if self._active_count() == 0 and not self._ilv_busy():
                if self._inflight:
                    # The last live requests finished while a pipelined
                    # tick was already dispatched: drain it (its rows'
                    # owners are gone, so this emits nothing) before
                    # sleeping, or a terminal tick would sit in flight
                    # across an idle period.
                    try:
                        await self._in_executor(loop, self._drain_inflight)
                    except asyncio.CancelledError:
                        raise  # batcher shutdown cancels the loop task
                    except Exception:
                        logger.exception("in-flight tick drain failed")
                        self._recover_after_tick_failure()
                    continue
                # Clear BEFORE checking pending: a submit() landing after
                # the check still leaves its set() visible to wait(),
                # avoiding the lost-wakeup race.
                self._wake.clear()
                if not self.pending.empty() or self._host_ops:
                    continue
                self._loop_park()
                await self._wake.wait()
                self._loop_resumed = time.perf_counter()
                continue
            # One batched decode tick (device-bound → executor).
            try:
                await self._in_executor(loop, self._tick_step)
            except asyncio.CancelledError:
                raise  # batcher shutdown cancels the loop task
            except Exception:
                # Replay every victim with budget left rather than
                # failing the whole pool for one transient fault; the
                # loop stays alive for future submissions either way.
                logger.exception("decode tick failed; replaying active slots")
                self._recover_after_tick_failure()
            await asyncio.sleep(0)  # noqa: ASYNC115 — deliberate yield so handlers drain queues (asyncio has no checkpoint())

    def _drain_inflight(self) -> None:
        while self._inflight:
            self._tick_collect_one()

    def _tick_collect_one(self) -> None:
        """Collect the oldest in-flight tick (a `ggrmcp.tick.collect`
        span in the profiler's trace while a capture runs)."""
        rec = self._inflight[0][3]
        with tracing.annotation(
            "ggrmcp.tick.collect", seq=rec.seq if rec is not None else 0
        ):
            self._collect_tick()

    def _record_terminal(self, request: _Request, reason: str) -> None:
        """Flight-record a request's terminal outcome — called on EVERY
        path that queues a terminal chunk (emission finish, queue
        timeout, replay exhaustion, cancellation, admission failure),
        so the request ring accounts for failures, not only successes.
        Doubles as the one place a terminal request returns its grammar
        arena reference AND its adapter-arena lease (same every-path
        property — a leaked pin would exempt a row from eviction
        forever)."""
        self._grammar_release(request)
        if request.adapter_lease is not None:
            self.engine.adapter_arena.release(request.adapter_lease)
        if not self.recorder.enabled:
            return
        if request.first_tick >= 0:
            last_tick = max(request.first_tick, self.timing["ticks"])
        else:
            last_tick = -1
        # Tenant & SLO ledgers (serving/slo.py), same stamps and the
        # same skip discipline as the recorder below: a never-admitted
        # death has no latency to judge (unevaluated), TPOT needs a
        # decode interval (>= 2 tokens). slo.enabled is False whenever
        # the recorder is disabled, so obs-off computes none of this.
        outcome = ""
        if self.slo.enabled:
            now = time.perf_counter()
            tokens = len(request.acc)
            admitted = bool(request.t_admit)
            ttft_ms = (
                max(0.0, (request.t_first - request.t_submit) * 1000.0)
                if request.t_first else None
            )
            tpot_ms = (
                (now - request.t_first) * 1000.0 / (tokens - 1)
                if request.t_first and tokens > 1 else None
            )
            outcome = self.slo.record_terminal(
                request.qos_class, reason,
                admitted=admitted,
                ttft_ms=ttft_ms,
                tpot_ms=tpot_ms,
                e2e_ms=max(0.0, (now - request.t_submit) * 1000.0),
            )
            self.tenants.record_terminal(
                request.tenant,
                admitted=admitted,
                prompt_tokens=request.n_prompt,
                decode_tokens=tokens,
                queue_ms=(
                    max(0.0, (request.t_admit - request.t_submit) * 1000.0)
                    if request.t_admit else 0.0
                ),
            )
        self.recorder.record_request(
            request.trace_id, request.t_submit, request.t_admit,
            request.t_first, request.n_prompt, len(request.acc),
            reason, request.first_tick, last_tick,
            constrained=request.grammar is not None,
            tenant=request.tenant,
            qos_class=request.qos_class,
            slo_violated=outcome == "violated",
            t_pop=request.t_pop,
        )

    def _replay_or_fail(self, request: _Request) -> None:
        """One victim of a failed device call. With retry budget left,
        requeue it at the head of the admission queue with its emitted
        tokens folded into the prompt — the re-admission prefill
        resumes EXACTLY where the consumer last saw a token (no
        duplicates, and a greedy continuation of prompt + emitted is
        bit-identical to the uninterrupted run, which is what the
        chaos suite asserts). Only budget exhaustion — a fault that
        recurs tick_retry_limit+1 times, i.e. likely deterministic —
        surfaces finish_reason "error"."""
        if request.cancelled:
            # The consumer is gone; freeing the slot is the recovery.
            self._record_terminal(request, "cancelled")
            self._loop_ref.call_soon_threadsafe(
                request.out.put_nowait, ([], "cancelled")
            )
            return
        if request.retries >= self.cfg.tick_retry_limit:
            self.replay_exhausted += 1
            self._record_terminal(request, "error")
            self._loop_ref.call_soon_threadsafe(
                request.out.put_nowait, ([], "error")
            )
            return
        request.retries += 1
        self.replayed += 1
        # Fold only the tokens emitted SINCE the last replay into the
        # prompt (request.absorbed tracks the fold point) and return
        # their budget: prompt' + max_new' keeps the same total, so
        # the original fit_request bound still holds.
        fresh = request.acc[request.absorbed:]
        if fresh:
            request.prompt = list(request.prompt) + [int(t) for t in fresh]
            request.max_new -= len(fresh)
            request.absorbed = len(request.acc)
        # Fresh queue clock: a replay must not inherit the original
        # wait and get swept by queue_deadline_ms after the system
        # already streamed it tokens.
        request.t_submit = time.perf_counter()
        self.pending.requeue_front(request)
        self._wake.set()

    # -- preemption (serving/scheduler.py) ----------------------------------

    async def _maybe_preempt(self, loop) -> None:
        """One scheduling decision per loop cycle: if the
        highest-priority waiter is at risk (head-of-line wait or burn
        rate, Scheduler.should_preempt) and no free slot exists, demote
        the policy's victims. Decision here on the loop thread (queue +
        slot metadata only); the preempt op itself — drain the
        pipelined tick, fold, demote KV, release the lease, park — runs
        in the serialized executor stream like every other device-state
        mutation."""
        if self._free_slots():
            return
        head = self.pending.head_waiter()
        if head is None:
            return
        waiter_class, wait_s = head
        if not self.sched.should_preempt(waiter_class, wait_s):
            return
        active = [
            (i, s.request.qos_class, s.request.tenant)
            for i, s in enumerate(self.slots)
            if s.active and s.request is not None
        ]
        victims = self.sched.victims(waiter_class, active)
        if not victims:
            return
        try:
            await self._in_executor(loop, self._preempt_slots, victims)
        except asyncio.CancelledError:
            raise  # batcher shutdown cancels the loop task
        except Exception:
            # _preempt_slots degrades per-slot and should never raise;
            # if it somehow does, the slots are in an unknown state —
            # the tick-failure recovery (replay everyone) is the
            # correct big hammer.
            logger.exception("preemption failed; recovering")
            self._recover_after_tick_failure()

    def _preempt_slots(self, victims: list[int]) -> None:
        """Demote-don't-kill (executor thread): for each victim slot,
        drain the pipelined tick, fold the emitted tokens into the
        prompt (the _replay_or_fail fold WITHOUT burning a tick retry —
        preemption is policy, not failure), park the valid KV pages as
        evictable cache + host-tier copies (pages.demote_for_preempt),
        release the adapter-arena pin, and park the request in its
        class's resume lane. The grammar handle is KEPT — the resuming
        activation re-derives the DFA state from the replay prefix
        (_g0), exactly like a tick-failure replay, which is why greedy
        output through a preempt cycle is bit-identical to the
        uninterrupted run (the invariant the sched chaos suite
        asserts). A `sched_preempt_fail` failpoint (or any unexpected
        error) degrades TYPED: the victim keeps decoding unharmed and
        sched_preempt_failures counts it — a failed preemption must
        never hurt the request it tried to evict."""
        # Collect in-flight pipelined ticks first: a dispatched tick
        # still writes the victim's KV row and emits its tokens — the
        # fold below must see the final acc, and no device write may
        # land on a parked slot.
        self._drain_inflight()
        for sl in victims:
            slot = self.slots[sl]
            request = slot.request
            if not slot.active or request is None or request.cancelled:
                # Finished (or its consumer left) while the decision
                # was in flight — nothing to demote; the normal
                # terminal path owns the cleanup.
                continue
            try:
                failpoints.evaluate("sched_preempt_fail")
                fresh = request.acc[request.absorbed:]
                if fresh:
                    request.prompt = (
                        list(request.prompt) + [int(t) for t in fresh]
                    )
                    request.max_new -= len(fresh)
                    request.absorbed = len(request.acc)
                if self._paged:
                    self.pages.demote_for_preempt(
                        sl, request.prompt, adapter=request.adapter_key
                    )
                    self._tables_dirty = True
            except failpoints.FailpointError:
                self.sched.preempt_failures += 1
                logger.warning(
                    "preemption failed for slot %d (injected); victim "
                    "keeps decoding", sl,
                )
                continue
            except Exception:
                # Past the failpoint the sequence is host bookkeeping
                # only (numpy index/refcount walks; the D2H inside
                # demote_for_preempt is best-effort internally), so
                # this is unexpected — degrade like the failpoint, but
                # free the slot's pages defensively (free_slot is a
                # no-op on an already-cleared row) and replay the
                # request through the failure path, which burns a
                # retry: the slot's page state is not trustworthy
                # enough to keep decoding on.
                logger.exception("preemption failed for slot %d", sl)
                self.sched.preempt_failures += 1
                if self._paged:
                    self.pages.free_slot(sl)
                    self._tables_dirty = True
                slot.active = False
                slot.request = None
                slot.done = False
                self.jump_ok[sl] = False
                self.temps[sl] = 0.0
                self.adapter_ids[sl] = 0
                self.gstates[sl] = 0
                self._slot_last_emit[sl] = None
                self._loop_ref.call_soon_threadsafe(
                    self._replay_or_fail, request
                )
                continue
            # Release the arena pin so the row is evictable while the
            # request is parked (resume reacquires — possibly a
            # DIFFERENT row; the stable adapter_key keeps the KV
            # domain). Static mode / base rows have no lease.
            if request.adapter_lease is not None:
                self.engine.adapter_arena.release(request.adapter_lease)
                request.adapter_lease = None
            # Park the slot exactly like _jump_degrade.
            slot.active = False
            slot.request = None
            slot.done = False
            self.jump_ok[sl] = False
            self.temps[sl] = 0.0
            self.adapter_ids[sl] = 0
            self.gstates[sl] = 0
            self._slot_last_emit[sl] = None
            request.preempts += 1
            request.parked = True
            # Fresh queue clock: park time is scheduler-imposed wait,
            # not the caller's original queue time — and the sweep's
            # queue_deadline_ms must not expire a request the system
            # already invested a prefill in because it parked too long.
            request.t_submit = time.perf_counter()
            self.sched.preemptions += 1
            self._loop_ref.call_soon_threadsafe(
                self._park_preempted, request
            )

    def _park_preempted(self, request: _Request) -> None:
        """Loop-thread tail of a preemption: the parked request enters
        its class's resume lane (head — its host-tier pages are the
        hottest)."""
        if request.cancelled:
            self._record_terminal(request, "cancelled")
            request.out.put_nowait(([], "cancelled"))
            return
        self.pending.park_preempted(request)
        self._wake.set()

    def _resume_reacquire(
        self, slots_idx: list[int], batch: list[_Request]
    ) -> None:
        """Executor-side pre-pass of _prefill_into_slots (scheduler
        on): a resuming request whose adapter pin was released at
        preemption reacquires a row HERE, inside the serialized stream
        where the arena's H2D factor write is safe — before the paged
        pre-pass builds any block table. Rows that cannot reacquire
        are FILTERED from the batch in place (slots_idx/batch are the
        admission's own lists, so _admit's failure handling never sees
        the dropped rows): arena pressure re-parks the request for the
        next cycle, bounded by scheduler.resume_retry_limit attempts
        before a typed "overloaded" shed — parking is a bounded
        promise, not a black hole. Unknown/unloadable adapters (the
        registry changed while parked) die typed as "error"."""
        arena = getattr(self.engine, "adapter_arena", None)
        keep_slots: list[int] = []
        keep_batch: list[_Request] = []
        for sl, request in zip(slots_idx, batch):
            if (
                request.preempts > 0
                and request.adapter_key
                and request.adapter_lease is None
                and arena is not None
            ):
                try:
                    lease = arena.acquire(request.adapter_key)
                except AdapterExhaustedError:
                    request.sched_retries += 1
                    if request.sched_retries > int(
                        self.sched_cfg.resume_retry_limit
                    ):
                        self.shed += 1
                        self._record_terminal(request, "overloaded")
                        self._loop_ref.call_soon_threadsafe(
                            request.out.put_nowait, ([], "overloaded")
                        )
                    else:
                        self._loop_ref.call_soon_threadsafe(
                            self._repark, request
                        )
                    continue
                except Exception:
                    logger.exception(
                        "resume: adapter %r reacquire failed",
                        request.adapter_key,
                    )
                    self._record_terminal(request, "error")
                    self._loop_ref.call_soon_threadsafe(
                        request.out.put_nowait, ([], "error")
                    )
                    continue
                request.adapter_lease = lease
                # The row may DIFFER from the pre-preemption one —
                # adapter_key (not the row id) keys the KV chains, so
                # the parked pages are still this adapter's pages.
                request.adapter = lease.row
            keep_slots.append(sl)
            keep_batch.append(request)
        slots_idx[:] = keep_slots
        batch[:] = keep_batch

    def _repark(self, request: _Request) -> None:
        """Loop-thread re-park after a failed resume attempt: BACK of
        the class's resume lane (put_nowait routes on `parked`), so
        sibling parked requests get their attempt before this one
        retries."""
        if request.cancelled:
            self._record_terminal(request, "cancelled")
            request.out.put_nowait(([], "cancelled"))
            return
        self.pending.put_nowait(request)
        self._wake.set()

    def _recover_after_tick_failure(self) -> None:
        """Tick-failure recovery. The failed call donated the shared
        cache (and any interleave mini), so device state is gone — but
        the host still knows every victim's prompt and emitted tokens:
        instead of erroring the whole pool, each victim re-enters the
        queue through _replay_or_fail with its replay prefix. A
        transient device fault then costs one re-prefill per victim,
        not every in-flight request."""
        for slot in self.slots:
            if slot.active and slot.request is not None:
                self._replay_or_fail(slot.request)
            slot.active = False
            slot.request = None
            slot.done = False
            slot.reserved = False
        # In-flight interleaved admissions die with the tick (the fused
        # call donated their mini cache alongside the shared one); they
        # have emitted nothing yet, so their replay prefix is the plain
        # prompt — but the requeue still burns a retry, or a prompt
        # that poisons the fused call would requeue forever.
        for st in list(self._ilv_rows) + list(self._ilv_pending):
            if st is not None:
                self._replay_or_fail(st.request)
        self._ilv_rows = [None] * self._ilv_k
        self._ilv_pending.clear()
        self._ilv_mini = None
        self._slot_last_emit = [None] * len(self.slots)
        # The tick donated the shared cache, so its buffers are dead
        # after an error — rebuild, or every future admission scatter
        # would fail and no request could ever succeed. The in-flight
        # queue and device token feedback are poisoned with it. Grammar
        # state resets with the slots: every victim re-derives its DFA
        # state from its replay prefix at re-admission (_g0).
        self._inflight.clear()
        self._cur_dev = None
        self.adapter_ids[:] = 0
        self.gstates[:] = 0
        self.jump_ok[:] = False
        self._gstate_dev = None
        # A round seated and not settled dies with the cache its
        # programs donated: its rows were active, with nothing emitted,
        # and were replayed from their prompts above.
        self._seated = None
        self._cache_at_risk = False
        if self._paged:
            # The donated arena died with the tick: every page and
            # every index entry is device-dead. Reset the HOST
            # allocator wholesale — victims replay through admission,
            # which re-maps fresh pages and re-registers prefixes (a
            # shared preamble re-shares from its first replayed
            # sighting; hit rate dips for one wave, correctness never).
            self.pages.reset()
            self._tables_dirty = True
        self.cache = self._make_shared_cache()

    def _sweep_expired_pending(self) -> None:
        """Deadline-aware sweep: drop already-expired (and abandoned)
        queued requests BEFORE admission. Runs every loop turn, free
        slot or not — under a saturated pool the backlog expires in
        the queue instead of each entry burning an admission slot and
        a prefill only to die at its consumer's long-gone deadline."""
        ddl = self.cfg.queue_deadline_ms
        if ddl <= 0 or self.pending.empty():
            return
        now = time.perf_counter()
        keep: list[_Request] = []
        while True:
            try:
                request = self.pending.get_nowait()
            except asyncio.QueueEmpty:
                break
            if request.cancelled:
                continue  # consumer gone; just release the queue slot
            if (now - request.t_submit) * 1000.0 > ddl:
                self.timed_out += 1
                self._record_terminal(request, "timeout")
                request.out.put_nowait(([], "timeout"))
            else:
                keep.append(request)
        for request in keep:  # full drain + re-put preserves FIFO order
            self.pending.put_nowait(request)

    async def _admit(self) -> int:
        """Admit pending requests into free slots. Pending requests are
        drained into one batch per round (capped at the free slots);
        a burst costs ONE device call (fused prefill+sample+merge via
        the full-pool program), a trickle of ≤2 uses the cheaper
        single-row program."""
        self._sweep_expired_pending()
        admitted = 0
        deadline = time.monotonic() + self.cfg.max_queue_delay_ms / 1000.0
        loop = asyncio.get_running_loop()
        capped = False
        # Sarathi-style tick-time control knob (scheduler on): cap the
        # prefill tokens one _admit call may pull in while decodes are
        # live, so a wave of long prompts never stalls in-flight
        # interactive TPOT for more than one budgeted round.
        prefill_budget = (
            int(self.sched_cfg.prefill_budget_tokens)
            if self.sched is not None else 0
        )
        tok_sum = 0
        while self._free_slots() and not capped:
            batch: list[_Request] = []
            budget = len(self._free_slots())
            if self.cfg.p50_budget_ms > 0 and self._active_count() > 0:
                # Latency SLO: while slots are decoding, one admission
                # round may stall them by at most p50_budget_ms/4 —
                # cap the batch at what the measured per-row prefill
                # cost (EMA) predicts fits. One capped batch per call;
                # the rest of the queue waits a tick (decode progress
                # between admissions is the whole point of the cap).
                stall_ms = self.cfg.p50_budget_ms / 4.0
                cap = max(
                    1, int(stall_ms / max(self._admit_ema_ms, 1e-3))
                )
                if cap < budget:
                    budget = cap
                    capped = True
            while len(batch) < budget:
                try:
                    timeout = deadline - time.monotonic()
                    if timeout <= 0 or admitted + len(batch) >= len(self.slots):
                        break
                    if (
                        self._active_count() > 0 or admitted > 0 or batch
                        or self._ilv_busy()
                    ):
                        # Don't stall running decodes (or in-flight
                        # interleaved chunk work) for stragglers.
                        request = self.pending.get_nowait()
                    else:
                        # Idle pool, nothing batched yet: waiting here
                        # for a first arrival is parking, not work.
                        self._loop_park()
                        try:
                            request = await asyncio.wait_for(
                                self.pending.get(), timeout=timeout
                            )
                        finally:
                            self._loop_resumed = time.perf_counter()
                except (asyncio.TimeoutError, asyncio.QueueEmpty):
                    break
                if request.cancelled:
                    continue
                ddl = self.cfg.queue_deadline_ms
                if ddl > 0 and (
                    time.perf_counter() - request.t_submit
                ) * 1000.0 > ddl:
                    # Expired in queue: fail fast instead of spending
                    # prefill on a call the client has abandoned.
                    self.timed_out += 1
                    self._record_terminal(request, "timeout")
                    request.out.put_nowait(([], "timeout"))
                    continue
                if (
                    prefill_budget > 0
                    and self._active_count() > 0
                    and (batch or admitted)
                    and tok_sum + len(request.prompt) > prefill_budget
                ):
                    # Over budget for this round: head-of-queue defer
                    # (it pops first next cycle, against a fresh
                    # budget). The (batch or admitted) guard admits at
                    # least one request per call — a single prompt
                    # larger than the whole budget must degrade to
                    # one-at-a-time admission, never starve.
                    self.pending.requeue_front(request)
                    self.sched.budget_deferrals += 1
                    capped = True
                    break
                request.t_pop = time.perf_counter()
                batch.append(request)
                tok_sum += len(request.prompt)
            if not batch:
                break
            slots_idx = self._free_slots()[: len(batch)]
            if self._seated is not None:
                # A second round of this call: the round before it is
                # settled first (its first tokens read, its pages
                # indexed, its first tokens emitted), as it was before
                # the settle moved behind the tick's dispatch, so this
                # round's pages.admit sees the index the synchronous
                # order shows it. A call of its own: a device failure
                # of those programs is theirs, not this batch's.
                try:
                    await self._in_executor(loop, self._settle_round)
                except asyncio.CancelledError:
                    raise  # batcher shutdown cancels the loop task
                except Exception:
                    logger.exception(
                        "admission programs failed; replaying active slots"
                    )
                    self._recover_after_tick_failure()
                    slots_idx = self._free_slots()[: len(batch)]
            try:
                await self._in_executor(
                    loop, self._prefill_into_slots, slots_idx, batch
                )
            except asyncio.CancelledError:
                raise  # batcher shutdown cancels the loop task
            except Exception:
                # Fail the batch, but scale the blast radius to what
                # actually broke. Requests from this batch that already
                # activated (a program of the round seated them: its
                # settle emits their first tokens, or a dead cache
                # replays them below) get no terminal chunk here.
                # The shared cache is rebuilt ONLY if the failing call
                # was one that donates it (_cache_at_risk); a failure
                # before that dispatch killed nothing shared, and
                # nuking every active slot for it would turn one
                # poisoned prompt into a full-pool outage.
                logger.exception(
                    "batched prefill failed for slots %s", slots_idx
                )
                cache_dead = self._cache_at_risk
                activated = {
                    id(s.request) for s in self.slots
                    if s.active and s.request is not None
                }
                for request in batch:
                    if id(request) not in activated:
                        self._record_terminal(request, "error")
                        self._loop_ref.call_soon_threadsafe(
                            request.out.put_nowait, ([], "error")
                        )
                if self._paged and not cache_dead:
                    # The arena survived (the failing call didn't
                    # donate it), but the failed rows' block tables
                    # must not leak their pages — and their eagerly
                    # indexed, never-prefilled pages must leave the
                    # index rather than cache garbage.
                    for sl, request in zip(slots_idx, batch):
                        if id(request) not in activated:
                            self.pages.free_slot(sl, discard_index=True)
                            self._tables_dirty = True
                if cache_dead:
                    # The donated buffers are dead: every active slot's
                    # KV rows go with them (anything less would stream
                    # garbage from a zeroed cache). The failing batch
                    # itself got "error" above — it may be the poison —
                    # but the bystanders it killed are innocent:
                    # replay them with their emitted prefix instead of
                    # turning one bad admission into a full-pool outage.
                    for slot in self.slots:
                        if slot.active and slot.request is not None:
                            self._replay_or_fail(slot.request)
                        slot.active = False
                        slot.request = None
                        slot.done = False
                    self._slot_last_emit = [None] * len(self.slots)
                    if self._paged:
                        self.pages.reset()
                        self._tables_dirty = True
                    self.cache = self._make_shared_cache()
                    self._cache_at_risk = False
                    # The seated rows were among the replayed, and the
                    # token feedback their seat patched is as dead as
                    # the cache (a failed program's output poisons it).
                    self._seated = None
                    self._cur_dev = self._gstate_dev = None
                continue
            admitted += len(batch)
        return admitted

    def _prefill_into_slots(
        self, slots_idx: list[int], batch: list[_Request]
    ) -> None:
        """One admission round (an executor work item): route the
        batch (_route_admission), which QUEUES the round's programs and
        seats their rows without reading a device result, and account
        for the round on ONE clock, its PhaseTimer, marked from inside
        by every admission program call (_admission_program: build /
        launch, and tick_wait / device where a second program of the
        round waits for the one before it) and seat (activate).

        On a pipelined loop the round ends there, its rows seated
        (`_seated`): the loop dispatches the next tick behind the
        round's programs and `_tick_step` settles the round after that
        dispatch (_settle_round: first tokens read, pages indexed,
        first tokens emitted), so the device never waits for a host
        turn at a round's end. Two rounds keep the order they always
        had and settle here, before the round returns, because the code
        can see from their input that the next dispatch needs the
        host's view of the first token: a loop that is not pipelined
        (`_pipeline` false: the collect follows its own dispatch, the
        tests' synchronous reference), and a round that holds a row
        with a grammar (the next tick's `gstates[slot]` is
        `arena.step(g0, first)`, stepped on the host). Observed, not
        configured.

        The round's time in THIS call goes to the next tick's admit
        phase; a deferred settle runs inside that tick's wait phase.
        The round's AdmissionRecord (family that ran, rows, tokens,
        trace ids, the tick it precedes; host / tick_wait / device /
        dispatch, which sum to its duration) and the per-row cost EMA
        are written when the round is settled (_round_done). While a
        profile capture runs the round is a `ggrmcp.admit` span in the
        profiler's own trace, with a `ggrmcp.admit.program` /
        `.activate` child for each launch and seat; its settle is a
        `ggrmcp.admit.settle` span with `.device` / `.activate`
        children."""
        timer = PhaseTimer()
        seq = self.timing["admit_rounds"] + 1
        tick_seq = self.timing["ticks"] + 1
        rnd = self._round = _SeatedRound(
            timer, {"seq": seq, "tick": tick_seq},
            # The tick dispatched before this round, still on the
            # device (the loop keeps at most one; the newest is last).
            self._inflight[-1][0] if self._inflight else None,
        )
        with tracing.annotation("ggrmcp.admit", **rnd.span):
            # Chaos hooks: admission latency (admit_slow, arm with ms=)
            # and admission failure (admit_fail) — the latter exercises
            # _admit's blast-radius-scaled batch-failure handling.
            failpoints.evaluate("admit_slow")
            failpoints.evaluate("admit_fail")
            if self.sched is not None:
                # Resume pre-pass: reacquire released adapter pins (and
                # filter rows that cannot) BEFORE any block table or
                # cache row is touched for them.
                self._resume_reacquire(slots_idx, batch)
                if not batch:
                    return
            self._adm_families, self._adm_reused = [], 0
            self._adm_chunk_run = 0
            try:
                queued, shed_rows = self._route_admission(slots_idx, batch)
            finally:
                # Pins keep a LATER pages.admit of this round from
                # evicting a pool entry a queued program restores from;
                # past the round, device order does: a program that
                # captures into the entry is queued behind the one
                # that reads it.
                if self._row_state and self._paged:
                    self.pages.release_snapshot_pins()
                self._state_rows.clear()
            # What is left after the last seat is the way out; a round
            # that launched nothing (every row queued for tick-fused
            # chunks, or shed) was building all along.
            timer.mark("activate" if rnd.programs else "build")
            if rnd.programs and (
                not self._pipeline
                or any(r.grammar is not None for r in batch)
            ):
                self._settle_round()
        dt = (timer.last - timer.t0) * 1000.0
        self.timing["admit_rounds"] = seq
        # Phase attribution: this round's executor time seeds the NEXT
        # tick record's admit phase (queue drain + admission prefill
        # belong to the tick window they precede).
        self._admit_phase_ms += dt
        prompt_tokens = sum(len(r.prompt) for r in batch)
        self.prefill_tokens["reused"] += self._adm_reused
        self.prefill_tokens["computed"] += prompt_tokens - self._adm_reused
        self.prefill_tokens["chunk_run"] += self._adm_chunk_run
        rnd.note = dict(
            family="+".join(self._adm_families),
            batch_trace_ids=[r.trace_id for r in batch if r.trace_id],
            rows=len(batch),
            prompt_tokens=prompt_tokens,
            reused_tokens=self._adm_reused,
            tick_seq=tick_seq, seq=seq,
        )
        # Interleave-queued rows ran no prefill here — feeding their
        # ~zero cost into the EMA would let the p50_budget_ms cap admit
        # unbounded short-prompt bursts on the strength of cheap
        # enqueues.
        rnd.prefilled = len(batch) - queued - shed_rows
        if self._seated is None:  # settled above, or nothing launched
            self._round_done(rnd)

    def _settle_round(self) -> None:
        """Settle the seated round: wait the tick that was in flight
        before it off the device (`tick_wait`: what follows is the
        round's programs alone), bring each program's first tokens to
        the host (`device`, once a program: the wait for it, not the
        copy of one already waited for) and settle its rows
        (`activate`: _settle_slot). Called by `_tick_step` after its
        dispatch (the deferred order: the gap since the round returned,
        the hop to the loop and that dispatch, is marked `dispatch`),
        by `_admit` before a second round of one call, and by the round
        itself where the order is synchronous. A device failure of a
        donating program surfaces here, with `_cache_at_risk` still
        set: the caller's handler replays every active slot, the seated
        rows among them."""
        rnd, self._seated = self._seated, None
        timer = rnd.timer
        returned = rnd.note is not None
        if returned:
            # What lies between the round's return and here is the
            # tick's dispatch where one was made, else the loop's hop.
            timer.mark("dispatch" if rnd.deferred else "activate")
        settle_from = timer.last
        with tracing.annotation("ggrmcp.admit.settle", **rnd.span):
            for program in rnd.programs:
                self._await_program(rnd, program)
            # Cleared only now: under async dispatch a device failure
            # in a donating call surfaces in the waits above, and the
            # handler must still see the cache as possibly dead.
            self._cache_at_risk = False
            with tracing.annotation("ggrmcp.admit.activate", **rnd.span):
                for program in rnd.programs:
                    first = np.asarray(program.first)
                    # A settled round holds nothing on the device.
                    program.first = None
                    for sl, req, j in program.rows:
                        self._settle_slot(sl, req, int(first[j]))
            rnd.tick_ahead = None
            timer.mark("activate")
        if returned:
            if not rnd.deferred:
                self._admit_phase_ms += (timer.last - settle_from) * 1000.0
            self._round_done(rnd)

    def _round_done(self, rnd: "_SeatedRound") -> None:
        """The round's record and the per-row cost EMA, once its marks
        are all made."""
        timer = rnd.timer
        self.recorder.note_admission(timer, deferred=rnd.deferred, **rnd.note)
        if rnd.prefilled:
            dt = (timer.last - timer.t0) * 1000.0 - timer.acc.get(
                "dispatch", 0.0)
            self._admit_ema_ms = (
                0.7 * self._admit_ema_ms + 0.3 * dt / rnd.prefilled
            )

    def _state_plan(self, sl: int, req: _Request, adm) -> None:
        """A ROW_STATE family's row, its block table just built: note
        which snapshot its admission program restores from and reserve
        the pool entries for the states it will capture; `_state_io`
        reads both when the row's program is built. The `no snapshot
        state` fault (failpoint state_restore_zero, the benchmark's
        control) leaves the slot's state zero where one was due."""
        src = adm.state_src
        if src >= 0:
            try:
                failpoints.evaluate("state_restore_zero")
            except failpoints.FailpointError:
                src = -1
        self._state_rows[sl] = (src, self.pages.plan_snapshots(
            sl, req.prompt, adm.scan_start, self.cfg.prefill_chunk,
            adapter=req.adapter_key))

    def _admission_ran(self, family: str, reused_tokens: int = 0) -> None:
        """An admission path notes the program family it dispatched
        (consecutive repeats fold) and the prompt tokens it took from
        reused KV, for the round's AdmissionRecord."""
        if not self._adm_families or self._adm_families[-1] != family:
            self._adm_families.append(family)
        self._adm_reused += reused_tokens

    def _admission_program(
        self, launch, family: str, rows: int, chunks: int, tokens: int,
        width: int,
    ):
        """Queue ONE admission program and return its rows' first
        tokens as the DEVICE array the program produced: nothing is
        read here (_settle_round reads, once everything the loop has
        for the device is queued). `launch()` makes the jitted call,
        which donates the shared cache, and returns (first, cache).
        `chunks` is the number of [1, width] chunk rows the program
        runs, a bucket's padding rows and a grid's no-op chunks among
        them where the program computes those: chunks x width is what
        the `tokens` prompt tokens it computes fill
        (prefill_chunk_tokens_run beside prefill_tokens_computed, both
        stamped at the round's end, so a delta of the two covers the
        same rounds). The round's timer is marked where each thing
        happens (flight_recorder.ADMIT_HOST_MARKS): `build` closes here
        (the caller's numpy grids, its argument transfers, the grammar
        tables, the table sync), `launch` when the jitted call returns
        (the enqueue).

        One admission program's temporaries at a time: the runtime
        reserves a program's temporaries when it is QUEUED, so a second
        program of a round queued behind the first would hold two mini
        caches at once where the families that admit a row a program do
        so because one is all that fits (keye: 0.45 GB a row beside
        ~13.5 of 16 GB). A second program is therefore BUILT while the
        one before it runs and launched when that one has left the
        device (_await_program: no copy, no activation; the device
        waits for the enqueue alone). One rule for every family: the
        tick queued behind a round's last program adds its own few MB
        and is safe everywhere. While a capture runs the launch is a
        `ggrmcp.admit.program` span carrying the round's seq and the
        tick it precedes."""
        rnd = self._round
        self._sync_tables()
        run = chunks * width
        self._adm_chunk_run += run
        self._admit_run += run
        rnd.timer.mark("build")
        if rnd.programs:
            self._await_program(rnd, rnd.programs[-1])
        self._cache_at_risk = True
        with tracing.annotation(
            "ggrmcp.admit.program", family=family, rows=rows,
            chunks=chunks, tokens=tokens, chunk_tokens_run=run,
            **rnd.span,
        ):
            first, self.cache = launch()
        rnd.timer.mark("launch")
        return first

    def _await_tick_ahead(self, rnd: "_SeatedRound") -> None:
        """On a pipelined loop the tick dispatched before the round is
        still on the device when the round's first program is queued,
        and the program runs behind it: wait here until that tick has
        left the device and mark the time `tick_wait`, so that what
        follows is the admission programs alone. Nothing is consumed,
        collected or reordered — the thread would block as long in the
        wait for the program, and the tick's collect then finds its
        array ready. Once a round; the tick's own failure is raised by
        its collect, to the handler that owns it (_loop's replay), not
        here."""
        if rnd.tick_ahead is None or "tick_wait" in rnd.timer.acc:
            return
        try:
            jax.block_until_ready(rnd.tick_ahead)
        except Exception:  # noqa: BLE001 — see above
            pass
        rnd.timer.mark("tick_wait")

    def _await_program(self, rnd: "_SeatedRound", program) -> None:
        """Wait until `program` has left the device (its first tokens
        are ready; nothing is copied) and mark the wait `device`, once
        a program: the round's `device` marks are the host's waits for
        its programs, each ending when its program does (a
        `ggrmcp.admit.device` span while a capture runs). The
        program's failure is raised here."""
        self._await_tick_ahead(rnd)
        if program.waited:
            return
        program.waited = True
        with tracing.annotation("ggrmcp.admit.device", **rnd.span):
            jax.block_until_ready(program.first)
        rnd.timer.mark("device")

    def _activate_rows(self, rows: list, first, slots) -> None:
        """Seat the rows of the program just queued: `rows` are (slot,
        request, index into `first`), `first` the program's first
        tokens on the device, `slots` [len(first)] the slot each of
        them belongs to (out of range: a padding row). The round's
        `activate` segment, a `ggrmcp.admit.activate` span while a
        capture runs. The round is `_seated` from its first program
        on."""
        rnd = self._round
        with tracing.annotation("ggrmcp.admit.activate", **rnd.span):
            for sl, req, _ in rows:
                self._seat_slot(sl, req)
            self._patch_seated(first, slots)
        try:
            first.copy_to_host_async()
        except (AttributeError, RuntimeError):
            pass  # the settle's np.asarray transfers instead
        rnd.programs.append(_QueuedProgram(first, rows))
        self._seated = rnd
        rnd.timer.mark("activate")

    def _patch_seated(self, first, slots) -> None:
        """The device half of a seat: scatter a program's first tokens
        (`first`, still on the device) into the tick's token feedback
        at `slots`, one scatter a program, and zero the grammar-state
        twin there (an unconstrained row's state; a constrained row's
        is patched by its settle, which comes before the next
        dispatch). An index out of range is dropped. The twins are
        snapped here where no tick has made them yet."""
        if self._cur_dev is None:
            self._cur_dev = self._snap_dev(self.cur_tokens)
        if self._gstate_dev is None:
            self._gstate_dev = self._snap_dev(self.gstates)
        self._cur_dev = self._cur_dev.at[slots].set(first, mode="drop")
        self._gstate_dev = self._gstate_dev.at[slots].set(0, mode="drop")

    def _settle_slot(
        self, slot_idx: int, request: _Request, first_tok: int
    ) -> None:
        """What a seated row's activation still owes once its first
        token is on the host (_seat_slot did the rest)."""
        request.t_admit = time.perf_counter()
        self.cur_tokens[slot_idx] = first_tok
        if request.grammar is not None:
            # The slot's NEXT-tick state is the post-first-token state,
            # patched into the mirror + device twin like cur_tokens.
            g_next = self.arena.step(request.gcur, first_tok)
            self.gstates[slot_idx] = g_next
            if self._gstate_dev is not None:
                self._gstate_dev = self._gstate_dev.at[slot_idx].set(g_next)
        # Paged KV: the prompt's full pages now hold valid prefix KV
        # (the first token implies the prefill materialized) — index
        # them so later admissions share instead of recomputing.
        # Adapter'd rows index under their own key domain (the chain
        # root folds the stable adapter key — serving/pages.py), so
        # same-adapter sessions share while cross-adapter aliasing
        # stays impossible. Before _emit: a one-token request finishes
        # inside it, and the cache window should survive the request
        # (refcount-0 indexed pages stay resident, LRU-evicted).
        if self._paged:
            self.pages.register(
                slot_idx, request.prompt, adapter=request.adapter_key
            )
        self._emit(slot_idx, first_tok)

    def _activate_slot(
        self, slot_idx: int, request: _Request, first_tok: int
    ) -> None:
        """Seat and settle one row whose first token is on the host
        already (an interleaved admission's row finish, inside the
        tick's dispatch)."""
        self._seat_slot(slot_idx, request)
        if self._cur_dev is not None:
            self._cur_dev = self._cur_dev.at[slot_idx].set(first_tok)
        if self._gstate_dev is not None:
            self._gstate_dev = self._gstate_dev.at[slot_idx].set(0)
        self._settle_slot(slot_idx, request, first_tok)

    def _route_admission(
        self, slots_idx: list[int], batch: list[_Request]
    ) -> tuple[int, int]:
        """Route each admission. Rows that reuse shared pages group by
        suffix geometry (_admit_paged_group); short cold prompts fuse
        into one prefill call (_prefill_fused); long cold prompts group
        wholesale, each group admitted by ONE fused chunked device call
        (_admit_chunked_group).
        Returns (rows queued for interleaved chunks, rows shed)."""
        fused_slots: list[int] = []
        fused_batch: list[_Request] = []
        long_rows: list[tuple[int, _Request]] = []
        queued = 0  # rows diverted to the interleave queue (no prefill)
        # Interleave long prompts only while decode (or earlier chunk
        # work) is in flight: on an idle pool the serialized fused grid
        # is strictly better (one device call vs T round-trips), and
        # there is nothing to stall anyway.
        ilv = self._ilv_k > 0 and (
            self._active_count() > 0 or self._ilv_busy()
        )
        # Paged pre-pass (batching.paged_kv=on): every row gets its
        # block table built FIRST — the longest page-aligned indexed
        # prefix is refcount-shared, a divergent-page CoW source is
        # picked, and exclusive pages cover the rest of the request's
        # lifetime (prompt + max_new + tick overshoot: no allocation
        # ever happens inside jit). Rows with any reuse group by suffix
        # geometry into fused _admit_paged_pfx calls; cold rows fall
        # through to the unchanged fused/chunked/interleaved routing
        # (whose merges write pages via _paged_put).
        paged_groups: dict[tuple, list] = {}
        rows = list(zip(slots_idx, batch))
        shed_rows = 0
        if self._paged:
            c = min(self.cfg.prefill_chunk, self.max_seq)
            cold: list[tuple[int, _Request]] = []
            for sl, req in rows:
                try:
                    # Chaos hook: page_exhausted forces the allocator's
                    # exhaustion path (utils/failpoints.py).
                    failpoints.evaluate("page_exhausted")
                    # Sharing is adapter-DOMAIN-scoped since ISSUE 15:
                    # the chain root folds the stable adapter key, so
                    # same-adapter sessions share prefix pages (and
                    # ride the host tier) while cross-adapter sharing
                    # is impossible by key construction — the old
                    # `share=req.adapter == 0` full-recompute gate is
                    # lifted (serving/pages.py key-domain test).
                    adm = self.pages.admit(
                        sl, req.prompt,
                        len(req.prompt) + req.max_new
                        + self._reserve_for(req.grammar is not None) + 1,
                        adapter=req.adapter_key,
                    )
                except (PageExhaustedError, failpoints.FailpointError):
                    # Typed shed on the PR-2 overload ladder: the
                    # "overloaded" terminal maps to RESOURCE_EXHAUSTED
                    # at the sidecar and HTTP 429 + Retry-After at the
                    # gateway. admit() is all-or-nothing, so resident
                    # block tables are untouched.
                    self.shed += 1
                    shed_rows += 1
                    self._record_terminal(req, "overloaded")
                    self._loop_ref.call_soon_threadsafe(
                        req.out.put_nowait, ([], "overloaded")
                    )
                    continue
                self._tables_dirty = True
                if self._row_state:
                    self._state_plan(sl, req, adm)
                if adm.scan_start > 0:
                    self.prefix_hits += 1
                    suffix = len(req.prompt) - adm.scan_start
                    if suffix <= c:
                        t_steps = 1
                        width = bucket_len(suffix, maximum=self.max_seq)
                    else:
                        t_steps, width = -(-suffix // c), c
                    key = (adm.merge_start, adm.scan_start, t_steps, width)
                    paged_groups.setdefault(key, []).append((sl, req, adm))
                else:
                    self.prefix_misses += 1
                    cold.append((sl, req))
                    # Eager registration: index this cold row's
                    # full pages NOW, so same-round rows
                    # sharing its preamble land in a paged group
                    # instead of recomputing it. Sound because cold
                    # fused/chunked calls dispatch BEFORE the paged
                    # groups below (device order writes the pages
                    # before any gather reads them) — which is why
                    # interleave-bound rows (prefilled across FUTURE
                    # ticks) must not register early, and an admission
                    # failure deregisters (free_slot discard_index).
                    if not (
                        ilv and len(req.prompt) > self.cfg.prefill_chunk
                    ):
                        self.pages.register(
                            sl, req.prompt, adapter=req.adapter_key
                        )
            rows = cold
        for sl, req in rows:
            if len(req.prompt) > self.cfg.prefill_chunk:
                if ilv:
                    # Chunk work item: the slot is held (reserved) but
                    # the prefill rides the decode ticks one chunk at a
                    # time instead of monopolizing this admission round.
                    self.slots[sl].reserved = True
                    self._ilv_pending.append(_IlvRow(req, sl, len(req.prompt)))
                    self.interleaved_admissions += 1
                    self._admission_ran("interleave_queued")
                    queued += 1
                else:
                    long_rows.append((sl, req))
            else:
                fused_slots.append(sl)
                fused_batch.append(req)
        if long_rows:
            # A family with deep grids (`_deep_grid`) admits a prompt
            # past that many chunks one row a call, in arrival order: a
            # group's rows all finish with its deepest, so whether two
            # long prompts that arrived a millisecond apart were popped
            # together or not would move the first one's first token by
            # a whole prefill (and, in a closed loop, every later
            # admission with it). Shallow prompts, and every prompt of
            # a family without deep grids, share one [R, T, C] call.
            c = min(self.cfg.prefill_chunk, self.max_seq)
            deep = [
                self._deep_grid is not None
                and len(req.prompt) > self._deep_grid * c
                for _, req in long_rows
            ]
            shallow = [row for row, d in zip(long_rows, deep) if not d]
            for at in range(0, len(shallow), self._mini_rows):
                self._admit_chunked_group(shallow[at: at + self._mini_rows])
            for row, d in zip(long_rows, deep):
                if d:
                    self._admit_chunked_group([row])
        if fused_batch:
            self._prefill_fused(fused_slots, fused_batch)
        # Paged groups LAST: a group may gather pages a cold call above
        # just wrote (eager same-round registration) — device execution
        # follows dispatch order, so the writes land first.
        for key, group in paged_groups.items():
            for at in range(0, len(group), self._mini_rows):
                self._admit_paged_group(group[at: at + self._mini_rows], *key)
        return queued, shed_rows

    def _admit_chunked_group(
        self, rows: list[tuple[int, _Request]]
    ) -> None:
        """ONE fused device call admitting `rows` (slot, request)
        pairs: each prompt lies from position 0 in its row of an
        [R, T_max, prefill_chunk] grid whose depth is the batcher's
        constant (`_grid_chunks`), and the program runs each row's own
        ceil(len / chunk) chunks (_chunked_rows): the prompts' depths
        shape no program.

        Row-count bucketing: long-prompt groups compile per power-of-2
        R (a trickle long admission must not pay the full slot pool's
        prefill compute). Padding rows carry slot index B (out of range
        → dropped by the insert scatter) and length 0 (no chunk)."""
        b = len(self.slots)
        c = min(self.cfg.prefill_chunk, self.max_seq)
        r = min(b, bucket_len(len(rows), minimum=1))
        tokens = np.zeros((r, self._grid_chunks, c), np.int32)
        true_len = np.zeros((r,), np.int32)
        slots_arr = np.full((r,), b, np.int32)  # pad = out of range
        seeds = np.zeros((r,), np.uint32)
        temps = np.zeros((r,), np.float32)
        ks = np.zeros((r,), np.int32)
        ps = np.ones((r,), np.float32)
        adapters = np.zeros((r,), np.int32)
        g0s = np.zeros((r,), np.int32)
        for j, (sl, req) in enumerate(rows):
            piece = np.asarray(req.prompt, np.int32)
            tokens[j].reshape(-1)[: len(piece)] = piece
            true_len[j] = len(req.prompt)
            slots_arr[j] = sl
            seeds[j] = req.seed & 0xFFFFFFFF
            temps[j] = req.sampling.temperature
            ks[j] = req.sampling.top_k
            ps[j] = req.sampling.top_p
            adapters[j] = req.adapter
            g0s[j] = self._g0(req)
        self._admission_ran("chunked")
        g_allow, g_trans = self._grammar_tables()
        sio = self._state_io([(j, sl) for j, (sl, _) in enumerate(rows)], r)
        # Transferred here, while the program before this one (if the
        # round has one) still runs: the launch is the enqueue alone.
        toks_dev, len_dev, *rest = map(jnp.asarray, (
            tokens, true_len, slots_arr, seeds, temps, ks, ps, adapters, g0s))
        first = self._admission_program(
            lambda: self._admit_chunked(
                self.engine.params, toks_dev, len_dev, self.cache, *rest,
                g_allow, g_trans, sio,
            ),
            "chunked", rows=len(rows),
            chunks=int((-(-true_len // c)).sum()),
            tokens=int(true_len.sum()), width=c,
        )
        self._activate_rows(
            [(sl, req, j) for j, (sl, req) in enumerate(rows)], first, slots_arr
        )

    def _admit_paged_group(
        self,
        rows: list[tuple[int, _Request, object]],
        merge_start: int,
        scan_start: int,
        t_steps: int,
        width: int,
    ) -> None:
        """ONE fused device call admitting a group of paged prefix
        reuses that share suffix geometry (same merge/scan starts and
        [T, C] suffix grid — a same-preamble wave lands in one group,
        the agentic arrival shape). Row-count bucketing mirrors
        _admit_chunked_group; padding rows carry slot index B and an
        all-sentinel gather table (reads clip, writes drop)."""
        b = len(self.slots)
        r = min(b, bucket_len(len(rows), minimum=1))
        tokens = np.zeros((r, t_steps, width), np.int32)
        true_len = np.zeros((r,), np.int32)
        slots_arr = np.full((r,), b, np.int32)
        gtables = self._gather_tables(r)
        seeds = np.zeros((r,), np.uint32)
        temps = np.zeros((r,), np.float32)
        ks = np.zeros((r,), np.int32)
        ps = np.ones((r,), np.float32)
        adapters = np.zeros((r,), np.int32)
        g0s = np.zeros((r,), np.int32)
        for j, (sl, req, adm) in enumerate(rows):
            piece = np.asarray(req.prompt[scan_start:], np.int32)
            tokens[j].reshape(-1)[: len(piece)] = piece
            true_len[j] = len(req.prompt)
            slots_arr[j] = sl
            if self._window:
                gtables[j] = adm.gather_row, self.pages.window.tables[sl]
            else:
                gtables[j] = adm.gather_row
            seeds[j] = req.seed & 0xFFFFFFFF
            temps[j] = req.sampling.temperature
            ks[j] = req.sampling.top_k
            ps[j] = req.sampling.top_p
            adapters[j] = req.adapter
            g0s[j] = self._g0(req)
        self._admission_ran("paged_pfx", scan_start * len(rows))
        g_allow, g_trans = self._grammar_tables()
        sio = self._state_io(
            [(j, sl) for j, (sl, _, _) in enumerate(rows)], r)
        # Transferred here (see _admit_chunked_group).
        toks_dev, len_dev, *rest = map(jnp.asarray, (
            tokens, true_len, slots_arr, gtables, np.int32(scan_start),
            np.int32(merge_start), seeds, temps, ks, ps, adapters, g0s))
        first = self._admission_program(
            lambda: self._admit_paged_pfx(
                self.engine.params, toks_dev, len_dev, self.cache, *rest,
                g_allow, g_trans, sio,
            ),
            "paged_pfx", rows=len(rows), chunks=r * t_steps,
            tokens=int(true_len.sum()) - scan_start * len(rows),
            width=width,
        )
        self._activate_rows(
            [(sl, req, j) for j, (sl, req, _) in enumerate(rows)],
            first, slots_arr,
        )

    def _prefill_fused(
        self, slots_idx: list[int], batch: list[_Request]
    ) -> None:
        """One fused device call admitting `batch` into `slots_idx`:
        the single-row program for one request, the full-pool program
        for a burst (row index == slot index)."""
        s = bucket_len(
            max(len(req.prompt) for req in batch), maximum=self.max_seq
        )
        single = len(batch) == 1
        rows = 1 if single else len(self.slots)
        if not single and len(batch) <= 2:
            # Tiny burst: two serial single-row calls beat one full-pool
            # prefill (compute scales with rows; round-trips are ~equal).
            for slot_idx, req in zip(slots_idx, batch):
                self._prefill_fused([slot_idx], [req])
            return
        row_of = (lambda j: 0) if single else (lambda j: slots_idx[j])
        tokens = np.zeros((rows, s), np.int32)
        true_len = np.zeros((rows,), np.int32)
        seeds = np.zeros((rows,), np.uint32)
        temps = np.zeros((rows,), np.float32)
        ks = np.zeros((rows,), np.int32)
        ps = np.ones((rows,), np.float32)
        valid = np.zeros((rows,), bool)
        adapters = np.zeros((rows,), np.int32)
        g0s = np.zeros((rows,), np.int32)
        for j, req in enumerate(batch):
            row = row_of(j)
            tokens[row, : len(req.prompt)] = req.prompt
            true_len[row] = len(req.prompt)
            seeds[row] = req.seed & 0xFFFFFFFF
            temps[row] = req.sampling.temperature
            ks[row] = req.sampling.top_k
            ps[row] = req.sampling.top_p
            valid[row] = True
            adapters[row] = req.adapter
            g0s[row] = self._g0(req)
        family = "single" if single else "full"
        self._admission_ran(family)
        g_allow, g_trans = self._grammar_tables()
        sio = self._state_io(
            [(row_of(j), sl) for j, sl in enumerate(slots_idx)], rows)
        program = self._admit_single if single else self._admit_full
        # Transferred here (see _admit_chunked_group). The single-row
        # program takes the slot index, the full-pool one a mask of the
        # rows that hold a request.
        toks_dev, len_dev, *rest = map(jnp.asarray, (
            tokens, true_len, np.int32(slots_idx[0]) if single else valid,
            seeds, temps, ks, ps, adapters, g0s))
        first = self._admission_program(
            lambda: program(
                self.engine.params, toks_dev, len_dev, self.cache, *rest,
                g_allow, g_trans, sio,
            ),
            family, rows=len(batch), chunks=rows,
            tokens=int(true_len.sum()), width=s,
        )
        # The slot of each of the program's rows: the one row's, or the
        # row's own index where the row holds a request.
        slots_arr = np.asarray(slots_idx[:1], np.int32) if single else (
            np.where(valid, np.arange(rows, dtype=np.int32), rows))
        self._activate_rows([
            (slot_idx, req, row_of(j))
            for j, (slot_idx, req) in enumerate(zip(slots_idx, batch))
        ], first, slots_arr)

    def _tick_step(self) -> None:
        """One loop turn of decode work: dispatch a tick (fused with at
        most one prefill chunk when interleaved admissions are in
        flight), settle the admission round seated before it, if any
        (_settle_round: the deferred order), then collect down to the
        pipeline depth. Synchronous
        mode (pipeline_ticks off) collects the tick it just dispatched
        — the classic loop; pipelined mode leaves it in flight and
        collects the PREVIOUS one, so the host pull of tick N overlaps
        tick N+1's compute."""
        # Chaos hook: an injected fault here is indistinguishable from
        # a real device failure at tick dispatch — _loop's handler
        # replays the victims (utils/failpoints.py).
        failpoints.evaluate("tick_fail")
        # While a profile capture runs, dispatch and collect are spans
        # in the profiler's own trace, tagged with the tick's seq (the
        # key into the tick ring); otherwise one attribute check each.
        with tracing.annotation(
            "ggrmcp.tick.dispatch", seq=self.timing["ticks"] + 1
        ):
            if self._jump_max and bool(self.jump_ok.any()):
                # Jump-ahead tick only while some live slot can
                # actually jump (a constrained, non-degraded request):
                # unconstrained workloads keep the plain tick's
                # steps_per_tick scan and pay ZERO jump overhead. Both
                # program families are warmed, so alternating
                # dispatchers never recompiles.
                self._tick_dispatch_jump(chunk=self._ilv_busy())
            elif self._ilv_busy():
                self._tick_dispatch_chunk()
            else:
                self._tick_dispatch()
        rnd = self._seated
        if rnd is not None:
            # The round before this tick queued its programs and seated
            # its rows; the tick is queued behind them now, and only
            # here are their first tokens read: the programs and this
            # tick run while the host settles, and the device has not
            # waited for the round's way out, the loop's hop or this
            # dispatch. (A round that failed on its way keeps no record
            # and is not counted: its seated rows are settled all the
            # same.)
            if rnd.note is not None:
                rnd.deferred = True
                self.timing["admit_rounds_deferred"] += 1
            self._settle_round()
        depth = 1 if self._pipeline else 0
        while len(self._inflight) > depth:
            self._tick_collect_one()

    def _tick_record(self, active, steps: int):
        """Open this tick's flight record at dispatch (None when the
        recorder is disabled). seq is 1-based on timing["ticks"], the
        same counter _activate_slot stamps first_tick from; `steps` is
        the decode steps this dispatch advances a row by. The record
        carries the tick's PhaseTimer — the dispatch paths mark "sync"
        and "dispatch", the collect marks "wait", tick_done settles
        "host" — and is seeded with the executor admission time
        accumulated since the previous dispatch (the admit phase)."""
        admit_ms, self._admit_phase_ms = self._admit_phase_ms, 0.0
        self._admit_run = 0
        # The sampler's two counters, here because every dispatch path
        # opens its record with the tick's `active` mask in hand.
        self.sampler_order_ticks += bool(np.any(
            active & (self.temps > 0.0)
            & ((self.top_ks > 0) | (self.top_ps < 1.0))
        ))
        self.sampler_mask_ticks += any(
            s.active and s.request is not None
            and s.request.grammar is not None for s in self.slots
        )
        if not self.recorder.enabled:
            return None
        trace_ids = list(dict.fromkeys(
            s.request.trace_id for s in self.slots
            if s.active and s.request is not None and s.request.trace_id
        ))
        return self.recorder.tick_start(
            seq=self.timing["ticks"] + 1,
            active=int(active.sum()),
            steps=steps,
            interleaved_rows=0,  # chunk dispatchers stamp theirs post-create
            trace_ids=trace_ids,
            shed=self.shed,
            replayed=self.replayed,
            timed_out=self.timed_out,
            kv_pages_in_use=self.pages.in_use() if self._paged else 0,
            admit_ms=admit_ms,
            memory=self._ledger_tick_snapshot(),
        )

    def _tick_dispatch(self) -> None:
        steps = self._tick_steps()
        step0 = self.step_counter
        # By the steps dispatched: the counter tags the sampler's draws
        # (step + i) and is ServingStats decode_steps.
        self.step_counter += steps
        self.timing["short_ticks"] += steps < self._steps_per_tick
        active = np.array([s.active for s in self.slots], bool)
        # Record FIRST so the PhaseTimer's contiguous marks cover the
        # host-state sync below ("sync") and the jitted launch
        # ("dispatch") — the phase sum must close on duration_ms.
        rec = self._tick_record(active, steps)
        self._sync_tables()
        if self._cur_dev is None:
            self._cur_dev = self._snap_dev(self.cur_tokens)
        if self._gstate_dev is None:
            self._gstate_dev = self._snap_dev(self.gstates)
        g_allow, g_trans = self._grammar_tables()
        if rec is not None:
            rec.phases.mark("sync")
        toks, self.cache, gstate_out = self._tick(
            self.engine.params, self._cur_dev, self.cache,
            jnp.asarray(self.seeds), jnp.int32(step0 + 1),
            jnp.asarray(self.temps), jnp.asarray(self.top_ks),
            jnp.asarray(self.top_ps), jnp.asarray(active),
            jnp.asarray(self.adapter_ids),
            self._gstate_dev, g_allow, g_trans, steps=steps,
        )
        # Device-side feedback for the next tick; no host sync. Grammar
        # state rides the same way: the scan's final per-row states
        # feed the next dispatch without materializing.
        self._cur_dev = toks[:len(self.slots), -1]
        self._gstate_dev = gstate_out
        try:
            toks.copy_to_host_async()
        except (AttributeError, RuntimeError):
            pass  # transfer will happen at collect time instead
        # Owner snapshot: emission must credit each row to the request
        # that owned the slot AT DISPATCH — under pipelining a slot can
        # finish (tick N's emission) and be re-admitted before tick
        # N+1's junk row for the old request is collected.
        owners = [s.request if s.active else None for s in self.slots]
        self._inflight.append((toks, None, owners, rec))
        self.timing["ticks"] += 1
        if rec is not None:
            rec.phases.mark("dispatch")

    def _ilv_fill_rows(self) -> None:
        """Claim queued chunk work items into free interleave rows."""
        for r in range(self._ilv_k):
            if self._ilv_rows[r] is None and self._ilv_pending:
                self._ilv_rows[r] = self._ilv_pending.popleft()

    def _ilv_chunk_inputs(self):
        """Host-stamped inputs for the chunk half of a fused tick+chunk
        call (shared by the plain and jump dispatches)."""
        k = self._ilv_k
        c = min(self.cfg.prefill_chunk, self.max_seq)
        chunk = np.zeros((k, c), np.int32)
        offs = np.zeros((k,), np.int32)
        c_tl = np.ones((k,), np.int32)
        c_valid = np.zeros((k,), bool)
        c_adapt = np.zeros((k,), np.int32)
        for r, st in enumerate(self._ilv_rows):
            if st is None:
                continue
            piece = st.request.prompt[st.progress : st.progress + c]
            chunk[r, : len(piece)] = piece
            offs[r] = st.progress
            c_tl[r] = st.n
            c_valid[r] = True
            c_adapt[r] = st.request.adapter
        return chunk, offs, c_tl, c_valid, c_adapt

    def _ilv_advance(self, sel) -> None:
        """Advance every admitting row by the chunk just dispatched and
        finish the rows whose final chunk it was."""
        c = min(self.cfg.prefill_chunk, self.max_seq)
        done: list[int] = []
        for r, st in enumerate(self._ilv_rows):
            if st is None:
                continue
            self.interleaved_chunks += 1
            st.progress += c
            if st.progress >= st.n:
                done.append(r)
        for r in done:
            self._ilv_finish_row(r, sel)

    def _tick_dispatch_chunk(self) -> None:
        """_tick_dispatch's interleaved twin: ONE fused device call =
        the decode scan for every slot PLUS at most one [K, C] prefill
        chunk advancing the admitting rows' mini caches. Rows whose
        final chunk this was finish right after (merge + first-token
        sample + activation — one small device call each, once per
        admission)."""
        step0 = self.step_counter
        self.step_counter += self._steps_per_tick
        active = np.array([s.active for s in self.slots], bool)
        # Record first: the PhaseTimer must cover the host-state sync
        # below (same contract as _tick_dispatch).
        rec = self._tick_record(active, self._steps_per_tick)
        self._ilv_fill_rows()
        self._sync_tables()
        if self._cur_dev is None:
            self._cur_dev = self._snap_dev(self.cur_tokens)
        if self._ilv_mini is None:
            self._ilv_mini = self._make_mini(self._ilv_k, self.max_seq)
        chunk, offs, c_tl, c_valid, c_adapt = self._ilv_chunk_inputs()
        if rec is not None:
            rec.interleaved_rows = int(c_valid.sum())
        if self._gstate_dev is None:
            self._gstate_dev = self._snap_dev(self.gstates)
        g_allow, g_trans = self._grammar_tables()
        if rec is not None:
            rec.phases.mark("sync")
        toks, self.cache, self._ilv_mini, sel, gstate_out = self._tick_chunk(
            self.engine.params, self._cur_dev, self.cache,
            jnp.asarray(self.seeds), jnp.int32(step0 + 1),
            jnp.asarray(self.temps), jnp.asarray(self.top_ks),
            jnp.asarray(self.top_ps), jnp.asarray(active),
            jnp.asarray(self.adapter_ids),
            jnp.asarray(chunk), self._ilv_mini, jnp.asarray(offs),
            jnp.asarray(c_tl), jnp.asarray(c_valid), jnp.asarray(c_adapt),
            self._gstate_dev, g_allow, g_trans,
        )
        self._cur_dev = toks[:len(self.slots), -1]
        self._gstate_dev = gstate_out
        try:
            toks.copy_to_host_async()
        except (AttributeError, RuntimeError):
            pass
        owners = [s.request if s.active else None for s in self.slots]
        self._inflight.append((toks, None, owners, rec))
        self.timing["ticks"] += 1
        self._ilv_advance(sel)
        if rec is not None:
            # After _ilv_advance: a final chunk's row finish (one small
            # device call + activation) is dispatch-side host work.
            rec.phases.mark("dispatch")

    def _tick_dispatch_jump(self, chunk: bool = False) -> None:
        """The jump-ahead twin of _tick_dispatch: one device call =
        each row's forced run plus ONE sampled token (a static
        [B, 1 + jump_max] window — _jump_core), fused with at most one
        [K, C] interleaved prefill chunk when `chunk`. Token/grammar
        feedback stays device-resident exactly like the plain tick;
        the host pulls (emit, count) at collect, validates each run
        against its own arena walk, and advances each slot by its run
        length + 1."""
        step0 = self.step_counter
        # 1 + jump_max positions processed per row; the sample's RNG
        # tag (step0 + 1) stays unique across ticks.
        self.step_counter += 1 + self._jump_max
        active = np.array([s.active for s in self.slots], bool)
        # Record first: the PhaseTimer must cover the host-state sync
        # below (same contract as _tick_dispatch).
        rec = self._tick_record(active, 1 + self._jump_max)
        if chunk:
            self._ilv_fill_rows()
        self._sync_tables()
        if self._cur_dev is None:
            self._cur_dev = self._snap_dev(self.cur_tokens)
        if self._gstate_dev is None:
            self._gstate_dev = self._snap_dev(self.gstates)
        g_allow, g_trans = self._grammar_tables()
        args = (
            self.engine.params, self._cur_dev, self.cache,
            jnp.asarray(self.seeds), jnp.int32(step0 + 1),
            jnp.asarray(self.temps), jnp.asarray(self.top_ks),
            jnp.asarray(self.top_ps), jnp.asarray(active),
            jnp.asarray(self.adapter_ids),
        )
        # jump_ok ships per dispatch (host-stamped, like temps): a
        # parked slot's stale device grammar state can never advance a
        # dead row's length pointer.
        jargs = (
            self._gstate_dev, g_allow, g_trans,
            self._g_jlen_dev, self._g_jtok_dev, self._g_jstate_dev,
            jnp.asarray(self.jump_ok),
        )
        if chunk:
            if self._ilv_mini is None:
                self._ilv_mini = self._make_mini(self._ilv_k, self.max_seq)
            chunk_arr, offs, c_tl, c_valid, c_adapt = (
                self._ilv_chunk_inputs()
            )
            if rec is not None:
                rec.interleaved_rows = int(c_valid.sum())
                rec.phases.mark("sync")
            (
                toks, counts, self.cache, cur_out, gstate_out,
                self._ilv_mini, sel,
            ) = self._tick_jump_chunk(
                *args, jnp.asarray(chunk_arr), self._ilv_mini,
                jnp.asarray(offs), jnp.asarray(c_tl),
                jnp.asarray(c_valid), jnp.asarray(c_adapt), *jargs,
            )
        else:
            if rec is not None:
                rec.phases.mark("sync")
            toks, counts, self.cache, cur_out, gstate_out = (
                self._tick_jump(*args, *jargs)
            )
        self._cur_dev = cur_out
        self._gstate_dev = gstate_out
        try:
            toks.copy_to_host_async()
            counts.copy_to_host_async()
        except (AttributeError, RuntimeError):
            pass
        owners = [s.request if s.active else None for s in self.slots]
        self._inflight.append((toks, counts, owners, rec))
        self.timing["ticks"] += 1
        if chunk:
            self._ilv_advance(sel)
        if rec is not None:
            rec.phases.mark("dispatch")

    def _ilv_finish_row(self, r: int, sel) -> None:
        """Complete interleave row `r`: scatter its mini row into the
        shared cache, sample the first token from `sel[r]`, activate
        the held slot. The int() materialization forces any async
        device failure to surface HERE, inside _tick_step's try, where
        _recover_after_tick_failure owns the cleanup."""
        st = self._ilv_rows[r]
        req = st.request
        g_allow, g_trans = self._grammar_tables()
        self._sync_tables()
        first, self.cache = self._ilv_finish(
            self.cache, self._ilv_mini, jnp.int32(r), jnp.int32(st.slot),
            jnp.int32(st.n), sel,
            jnp.asarray([req.seed & 0xFFFFFFFF], np.uint32),
            jnp.asarray([req.sampling.temperature], np.float32),
            jnp.asarray([req.sampling.top_k], np.int32),
            jnp.asarray([req.sampling.top_p], np.float32),
            jnp.asarray([self._g0(req)], np.int32), g_allow, g_trans,
        )
        first_tok = int(np.asarray(first)[0])
        self._ilv_rows[r] = None
        self._activate_slot(st.slot, req, first_tok)

    def _collect_tick(self) -> None:
        """Pull the oldest in-flight tick's tokens to the host and emit
        them. Rows whose owner no longer holds the slot (finished — and
        possibly re-admitted — since dispatch) are dropped: their
        tokens are the junk a parked slot keeps sampling."""
        toks_dev, counts_dev, owners, rec = self._inflight.popleft()
        toks = np.asarray(toks_dev)  # [B, steps_per_tick | J+1]
        # counts is the jump tick's per-row forced-run length + 1 (None
        # on plain ticks): emission truncates to it.
        counts = None if counts_dev is None else np.asarray(counts_dev)
        if self._routing_stats and counts is None:
            extra = toks[len(self.slots):]  # [counts, steps]
            for name, row in zip(self._routing_stats, extra):
                self.model_counts[name] += int(row.sum())
            self.model_counts["layer_steps"] += (
                extra.shape[1] * self.engine.cfg.num_expert_layers)
            toks = toks[:len(self.slots)]
        if rec is not None:
            # Everything since the dispatch mark was in-flight wait:
            # device compute + transfer, plus the deliberate one-tick
            # lag (and the next tick's host work) under pipelining.
            rec.phases.mark("wait")
        self.timing["collects"] += 1
        finished = 0
        jump_tokens = jump_runs = 0
        for i, request in enumerate(owners):
            if request is None:
                continue
            slot = self.slots[i]
            if slot.request is not request:
                continue
            if counts is None:
                self.cur_tokens[i] = toks[i, -1]
                self._emit_chunk(i, toks[i])
            else:
                c = int(counts[i])
                if c > 1 and not self._jump_validate(i, request, toks, c):
                    # Refused run (grammar_jump_fail chaos or corrupted
                    # tables): nothing from this tick is delivered for
                    # the row — the request replays typed and finishes
                    # under plain one-token constrained decoding.
                    self._jump_degrade(i, request)
                    continue
                if c > 1:
                    jump_tokens += c - 1
                    jump_runs += 1
                self.cur_tokens[i] = toks[i, c - 1]
                self._emit_chunk(i, toks[i, :c])
            if self.slots[i].request is not request:
                finished += 1
        if self._window:
            self._window_release(toks.shape[1])
        self.grammar_jump_tokens += jump_tokens
        self.grammar_jump_runs += jump_runs
        self.recorder.tick_done(
            rec, finished, jump_tokens=jump_tokens, jump_runs=jump_runs,
        )
        if rec is not None:
            # Cumulative per-phase attribution (ServingStats
            # tick_phase_*_ms): settled at tick_done, so the scalars
            # and the per-phase histograms always agree.
            for phase in PHASE_NAMES:
                self.phase_ms[phase] += getattr(rec, f"phase_{phase}_ms")

    def _jump_validate(self, slot_idx: int, request, toks, c: int) -> bool:
        """Collect-side check of a jump tick's forced run for one row:
        re-derive the run from the HOST arena walk at the request's
        current DFA state and require the device's emitted run to match
        it exactly. The host walk is the independent mirror (lock-free;
        live rows are immutable while referenced), so a corrupted
        device table or landing state is caught before a single bad
        token reaches the consumer. The grammar_jump_fail failpoint
        injects exactly that corruption (chaos suite)."""
        try:
            failpoints.evaluate("grammar_jump_fail")
        except failpoints.FailpointError:
            return False
        expected = self.arena.forced_run(request.gcur)
        return [int(t) for t in toks[slot_idx, : c - 1]] == expected

    def _jump_degrade(self, slot_idx: int, request) -> None:
        """A refused forced run degrades the request TYPED to plain
        one-token constrained decoding — counted, logged, never silent.
        The device row is unusable (its length pointer and grammar
        state advanced through the refused run), so the slot parks and
        the request replays through admission with its delivered prefix
        (prompt + acc — the same machinery a tick failure uses), now
        with jump_degraded set: the re-admission stamps jump_ok False
        and the row single-steps to completion, its greedy output still
        schema-valid because the allow-mask path never depended on the
        run tables."""
        self.grammar_jump_fallbacks += 1
        request.jump_degraded = True
        logger.warning(
            "jump-ahead: forced run refused for slot %d; degrading "
            "request to one-token constrained decoding and replaying",
            slot_idx,
        )
        slot = self.slots[slot_idx]
        slot.active = False
        slot.request = None
        self.jump_ok[slot_idx] = False
        self.temps[slot_idx] = 0.0
        self.adapter_ids[slot_idx] = 0
        self.gstates[slot_idx] = 0
        self._slot_last_emit[slot_idx] = None
        if self._paged:
            self.pages.free_slot(slot_idx)
            self._tables_dirty = True
        # This runs on the batcher's executor; the replay requeue
        # touches loop-owned state (pending queue + wake event), so hop
        # through the loop like every other executor→loop edge.
        self._loop_ref.call_soon_threadsafe(self._replay_or_fail, request)

    def _emit_chunk(self, slot_idx: int, tokens) -> None:
        """Deliver a tick's tokens for one slot: truncate at EOS or the
        slot's max_new budget, finish the slot if either was hit."""
        slot = self.slots[slot_idx]
        request = slot.request
        if request is None:
            return
        finished_reason = None
        ids: list[int] = []
        for raw_token in tokens:
            token = int(raw_token)
            if token == self.eos_id:
                # Under a grammar, EOS is only sampleable in accepting
                # DFA states — the output is complete valid JSON.
                finished_reason = "stop"
                break
            ids.append(token)
            slot.generated += 1
            if request.grammar is not None:
                # Advance the host DFA tracker through the emitted
                # token; reaching the accepting SINK (nothing may
                # follow) finishes the request — the schema's terminal
                # brace, not EOS, ends a constrained generation.
                request.gcur = self.arena.step(request.gcur, token)
                self.grammar_tokens += 1
                if self.arena.is_sink(request.gcur):
                    finished_reason = "grammar_complete"
                    break
            if slot.generated >= slot.max_new:
                finished_reason = "length"
                break
        if request.cancelled:
            finished_reason = finished_reason or "cancelled"
            ids = []
        # Decode-stall accounting: the gap since this slot's previous
        # emission (admission-induced stalls land here — the histogram
        # prefill_interleave exists to flatten).
        now = time.perf_counter()
        if request.t_first == 0.0:
            # First token produced (the activation emit): the TTFT
            # stamp — generation time, not consumer-delivery time, so
            # unary and streaming consumers measure identically.
            request.t_first = now
        last = self._slot_last_emit[slot_idx]
        if last is not None:
            self._stall_records.append((now - last) * 1000.0)
        self._slot_last_emit[slot_idx] = (
            None if finished_reason is not None else now
        )
        if finished_reason is not None:
            # Park the slot BEFORE delivering the terminal chunk: the
            # moment the consumer sees it, the request is observably
            # complete — a stats scrape racing this executor thread
            # must not count the slot as still active.
            slot.active = False
            slot.request = None
            # Freeze the row so it stops influencing shared state
            # (cache row stays, masked by length on reuse). The host
            # grammar-state mirror resets too; the device twin keeps
            # its stale value until the slot is re-admitted (the parked
            # row's junk tokens are dropped here regardless).
            self.temps[slot_idx] = 0.0
            self.adapter_ids[slot_idx] = 0
            self.gstates[slot_idx] = 0
            self.jump_ok[slot_idx] = False
            if self._paged:
                # Release the slot's page references (indexed pages
                # stay resident as evictable reuse cache) and unmap the
                # row to the sentinel — an in-flight pipelined tick's
                # junk writes against the stale device table land only
                # in this slot's own former tail pages, which every
                # reuser fully re-prefills before reading.
                self.pages.free_slot(slot_idx)
                self._tables_dirty = True
        # Every delivered token also lands in `acc`: for unary
        # consumers it is the terminal payload; for ALL consumers it
        # is the replay prefix a tick failure resumes from.
        request.acc.extend(ids)
        if finished_reason is not None:
            self._record_terminal(request, finished_reason)
        if request.unary:
            if finished_reason is not None:
                self._loop_ref.call_soon_threadsafe(
                    request.out.put_nowait,
                    (request.acc, finished_reason),
                )
        else:
            # Runs on executor threads; asyncio.Queue is not
            # thread-safe, so hop through the loop.
            self._loop_ref.call_soon_threadsafe(
                request.out.put_nowait, (ids, finished_reason)
            )

    def _emit(self, slot_idx: int, token: int) -> None:
        self._emit_chunk(slot_idx, [token])


# Below the class, not beside _IlvRow: see _seat_slot on the lines
# above the admission programs.
@dataclasses.dataclass
class _QueuedProgram:
    """One admission program of a round, queued: its first tokens on
    the device, its rows as (slot, request, index into `first`), and
    whether the host has waited for it already (_await_program)."""

    first: object
    rows: list
    waited: bool = False


@dataclasses.dataclass
class _SeatedRound:
    """An admission round from its start to its settle: its clock, what
    its profiler spans carry (seq, tick), the token array of the tick
    that was in flight when it began (None: none was), the programs it
    queued, whether a tick was dispatched before its first tokens were
    read, and, once the round has returned, what its AdmissionRecord
    says (`note`) and how many of its rows ran a prefill."""

    timer: PhaseTimer
    span: dict
    tick_ahead: object
    programs: list = dataclasses.field(default_factory=list)
    deferred: bool = False
    note: Optional[dict] = None
    prefilled: int = 0
