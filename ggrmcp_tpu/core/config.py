"""Whole-app configuration tree.

Capability parity with the reference config system (pkg/config/config.go)
with the gaps deliberately fixed (SURVEY.md §5.6): the tree here is
actually *plumbed* — every subsystem takes its config slice — and it
loads from defaults → YAML/JSON file → environment → CLI overrides,
whereas the reference defined the tree but only ever used two fields.

Defaults mirror the reference's canonical values (config.go:211-312):
HTTP 50053, 4 MB gRPC messages, keepalive 10 s/5 s, reconnect 5×5 s,
protocol 2024-11-05, sessions 30 min / 10 k, schema max depth 10 — plus
the TPU sections (mesh/serving/batching) that have no reference analogue.
"""

from __future__ import annotations

import dataclasses
import json
import os
from dataclasses import dataclass, field
from typing import Any, Optional

from ggrmcp_tpu.utils.jaxenv import cpu_requested


# ---------------------------------------------------------------------------
# Server / HTTP
# ---------------------------------------------------------------------------


@dataclass
class SecurityConfig:
    enable_security_headers: bool = True
    hsts: bool = True
    content_security_policy: str = "default-src 'none'"


@dataclass
class CORSConfig:
    enabled: bool = True
    allowed_origins: list[str] = field(default_factory=lambda: ["*"])
    allowed_methods: list[str] = field(
        default_factory=lambda: ["GET", "POST", "OPTIONS"]
    )
    allowed_headers: list[str] = field(
        default_factory=lambda: ["Content-Type", "Mcp-Session-Id", "Authorization"]
    )
    exposed_headers: list[str] = field(default_factory=lambda: ["Mcp-Session-Id"])


@dataclass
class RateLimitConfig:
    enabled: bool = True
    requests_per_second: float = 100.0
    burst: int = 200


@dataclass
class ServerConfig:
    host: str = "0.0.0.0"
    port: int = 50053
    read_timeout_s: float = 15.0
    write_timeout_s: float = 15.0
    idle_timeout_s: float = 60.0
    request_timeout_s: float = 30.0
    max_request_bytes: int = 1 << 20  # 1 MB
    shutdown_grace_s: float = 30.0
    # Worker processes sharing the listen port via SO_REUSEPORT. The Go
    # reference used every core through goroutines; asyncio is
    # single-core, so >1 scales the gateway across cores. Sessions are
    # worker-local (kernel hashing keeps a keep-alive connection on one
    # worker; use 1 worker or a sticky LB if cross-connection session
    # continuity matters). Requires a fixed port.
    workers: int = 1
    # HTTP server implementation: "fastlane" (raw asyncio.Protocol hot
    # path, gateway/fastlane.py — the default; ~framework-free
    # per-request cost) or "aiohttp" (the web.Application stack).
    # Identical served surface and gate semantics either way.
    http_impl: str = "fastlane"
    allowed_content_types: list[str] = field(
        default_factory=lambda: ["application/json"]
    )
    security: SecurityConfig = field(default_factory=SecurityConfig)
    cors: CORSConfig = field(default_factory=CORSConfig)
    rate_limit: RateLimitConfig = field(default_factory=RateLimitConfig)


# ---------------------------------------------------------------------------
# gRPC upstream(s)
# ---------------------------------------------------------------------------


@dataclass
class KeepAliveConfig:
    time_s: float = 10.0
    timeout_s: float = 5.0
    permit_without_stream: bool = True


@dataclass
class ReconnectConfig:
    """Background reconnect policy.

    The reference defined Reconnect (pkg/grpc/discovery.go:187-235) but
    never invoked it at runtime; here a background watchdog actually
    drives it (SURVEY.md §5.3 'deliberately fix').
    """

    enabled: bool = True
    max_attempts: int = 5
    interval_s: float = 5.0
    watchdog_interval_s: float = 10.0


@dataclass
class HeaderForwardingConfig:
    enabled: bool = True
    forward_all: bool = False
    case_insensitive: bool = True
    allowed_headers: list[str] = field(
        default_factory=lambda: [
            "authorization",
            "x-trace-id",
            "x-request-id",
            "x-user-id",
            "x-session-id",
            "x-adapter-id",
            "x-tenant-id",
            "x-qos-class",
            "x-api-key",
            "user-agent",
            "accept-language",
        ]
    )
    blocked_headers: list[str] = field(
        default_factory=lambda: [
            "cookie",
            "set-cookie",
            "host",
            "content-length",
            "content-type",
            "connection",
            "upgrade",
            "proxy-authorization",
            "proxy-authenticate",
            "te",
            "trailer",
            "transfer-encoding",
            "mcp-session-id",
        ]
    )


@dataclass
class DescriptorSetConfig:
    enabled: bool = False
    path: str = ""
    prefer_over_reflection: bool = True
    include_source_info: bool = True


@dataclass
class GRPCConfig:
    host: str = "localhost"
    port: int = 50051
    max_message_bytes: int = 4 << 20  # 4 MB
    connect_timeout_s: float = 5.0
    call_timeout_s: float = 30.0
    use_tls: bool = False
    keepalive: KeepAliveConfig = field(default_factory=KeepAliveConfig)
    reconnect: ReconnectConfig = field(default_factory=ReconnectConfig)
    header_forwarding: HeaderForwardingConfig = field(
        default_factory=HeaderForwardingConfig
    )
    descriptor_set: DescriptorSetConfig = field(default_factory=DescriptorSetConfig)

    @property
    def target(self) -> str:
        return f"{self.host}:{self.port}"


# ---------------------------------------------------------------------------
# MCP protocol
# ---------------------------------------------------------------------------


@dataclass
class ValidationConfig:
    max_method_length: int = 1024
    max_tool_name_length: int = 128
    max_nesting_depth: int = 10
    max_request_bytes: int = 1 << 20


@dataclass
class MCPConfig:
    protocol_version: str = "2024-11-05"
    server_name: str = "ggrmcp-tpu"
    # Default comes from the package metadata (ggrmcp_tpu.__version__)
    # so `initialize` reports the real installed version — reference
    # parity with handler.go:160-179 ("ggRMCP/1.0.0"), minus its
    # hardcoding. field(default_factory=...) defers the import.
    server_version: str = field(
        default_factory=lambda: __import__("ggrmcp_tpu").__version__
    )
    validation: ValidationConfig = field(default_factory=ValidationConfig)


# ---------------------------------------------------------------------------
# Sessions
# ---------------------------------------------------------------------------


@dataclass
class SessionRateLimitConfig:
    """Per-session fixed-window limit — and unlike the reference
    (pkg/session/manager.go:178, never called), the handler enforces it."""

    enabled: bool = True
    requests_per_minute: int = 100


@dataclass
class SessionConfig:
    ttl_s: float = 1800.0  # 30 min
    cleanup_interval_s: float = 300.0  # 5 min
    max_sessions: int = 10_000
    rate_limit: SessionRateLimitConfig = field(default_factory=SessionRateLimitConfig)


# ---------------------------------------------------------------------------
# Tools / schema generation
# ---------------------------------------------------------------------------


@dataclass
class SchemaCacheConfig:
    """Schema cache — configured AND implemented (the reference declared
    this but never wired it; pkg/tools/builder.go:18)."""

    enabled: bool = True
    max_entries: int = 4096


@dataclass
class ToolsConfig:
    max_schema_depth: int = 10
    emit_output_schema: bool = True
    include_comments: bool = True
    tensor_extensions: bool = True  # x-tensor dtype/shape hints in schemas
    # Expose server-streaming methods as tools (the reference rejected
    # all streaming, pkg/tools/builder.go:129-134; the gateway here
    # serves them aggregated or over SSE). Client streaming stays out.
    streaming_tools: bool = True
    cache: SchemaCacheConfig = field(default_factory=SchemaCacheConfig)


# ---------------------------------------------------------------------------
# TPU serving plane (no reference analogue — new capability)
# ---------------------------------------------------------------------------


@dataclass
class MeshConfig:
    """Logical device mesh for the serving plane.

    Axis sizes of 0 mean "infer from available devices". Axes follow the
    scaling-book convention: data / fsdp / tensor / sequence / expert /
    stage(pipeline).
    """

    data: int = 1
    fsdp: int = 1
    tensor: int = 0  # 0 → all remaining devices
    sequence: int = 1
    expert: int = 1
    stage: int = 1


@dataclass
class BatchingConfig:
    max_batch_size: int = 32
    max_queue_delay_ms: float = 5.0
    max_decode_steps: int = 512
    prefill_chunk: int = 512
    kv_cache_max_seq: int = 4096
    # Decode steps fused into one device call (lax.scan): the FULL
    # length of a tick, the upper bound the cache reserves derive from.
    # Streaming chunks are quantized to a tick and up to k-1 sampled
    # tokens per request are discarded at EOS/max_new. Under
    # pipeline_ticks the host's round trip hides behind the tick in
    # flight whatever k is; what k costs is admission latency: a new
    # request waits out the tick in flight and is launched behind the
    # next one. So the pipelined batcher dispatches a short tick
    # (short_tick_steps) while a request waits or a slot is free, and
    # this length with every slot live and nobody waiting, and after
    # admissions of more than k x prefill_chunk chunk tokens, which
    # held the decoding rows for longer than k steps (_tick_steps). 1 =
    # the classic one-call-per-token loop (CPU test meshes; short
    # equals full there). "auto" = DECODE_STEPS_TPU on TPU devices, 1
    # elsewhere (resolved by the batcher against the engine's mesh).
    decode_steps_per_tick: "int | str" = "auto"  # "auto" | int >= 1
    # Pipelined decode ticks: dispatch tick N+1 (with device-resident
    # token feedback) BEFORE blocking on tick N's host copy, so the
    # host↔device round-trip overlaps the next tick's compute instead
    # of stalling the device between ticks. Token values are identical
    # to the synchronous loop (same programs, same feedback); emission
    # lags one tick, and each request reserves one extra tick of cache
    # overshoot. "auto" = on when the engine's devices are TPUs (a real
    # accelerator to overlap with), off on CPU where host and "device"
    # share the core and the lagged tick is pure extra compute.
    pipeline_ticks: str = "auto"  # auto | on | off
    # Length-tiered KV cache: [[max_seq, slots], ...] ascending by
    # max_seq. Empty = one contiguous pool of max_batch_size ×
    # kv_cache_max_seq. With tiers, HBM is Σ slots×seq and admission
    # routes each request to the smallest tier that fits it
    # (serving/tiered.py).
    kv_tiers: list = field(default_factory=list)
    # Paged KV cache (docs/paged_kv.md): "on" replaces the contiguous
    # per-slot rows with one device arena of fixed-size pages per
    # layer, per-slot block tables, and a host-side refcounted
    # allocator (serving/pages.py) — token-level, page-aligned prefix
    # sharing with copy-on-write at the divergent page and LRU reuse of
    # refcount-0 pages: the one prefix-reuse mechanism. Greedy outputs
    # are bit-identical to "off" (the contiguous path, kept as the
    # provable baseline). Mutually exclusive with kv_ring; dense-Llama
    # and latent-attention families, non-pipeline serving only.
    paged_kv: str = "off"  # off | on
    # Page granularity in tokens. Smaller pages share shorter common
    # prefixes and waste less tail space; larger pages mean smaller
    # tables and fewer scatter indices. Must divide kv_cache_max_seq
    # (and every tier max_seq when tiering).
    paged_kv_page_size: int = 16
    # Arena size in pages. 0 = auto: max_batch_size × kv_cache_max_seq
    # / page_size — the same KV HBM as the contiguous pool, which
    # sharing then stretches (every shared prefix is stored once, and
    # freed pages are exact-fit reusable instead of padded rows).
    paged_kv_pages: int = 0
    # Host-tier KV page pool (docs/paged_kv.md "Host tier"): > 0 turns
    # arena eviction into DEMOTION — a refcount-0 indexed page's
    # contents move to this byte-budgeted host-RAM pool (one D2H copy;
    # int8 KV at half the bytes) and a later prefix hit on it RESTORES
    # with one H2D copy instead of recomputing the prefill. The
    # Mooncake/LMCache-style DRAM tier behind HBM: the hash-chain
    # prefix index spans both tiers. 0 = off (eviction discards, the
    # pre-tier behavior). With kv_tiers the budget splits across tiers
    # proportional to KV volume, like paged_kv_pages.
    paged_kv_host_bytes: int = 0
    # Optional mmap'd file tier BEHIND the RAM pool: demotions write
    # through to this append-only log, so a restarted replica warms
    # from disk (chain keys are stable across processes) — the fleet
    # supervisor's drain → restart cycle re-admits sessions from the
    # persisted pool instead of recomputing (docs/fleet.md). Requires
    # paged_kv_host_bytes > 0. With kv_tiers each tier logs to
    # "<path>.tier-<max_seq>" (tiers share no mutable state).
    paged_kv_host_path: str = ""
    # Cap on the file tier's log size in bytes (0 = unbounded; the log
    # is append-only, so long-lived replicas with churning working
    # sets should set this). When full, demotions keep landing in RAM
    # — the file just stops growing.
    paged_kv_host_file_bytes: int = 0
    # Latency SLO (SURVEY.md §7 hard part #2 — the batch-window vs p50
    # tradeoff). p50_budget_ms > 0 caps admission-induced decode
    # stalls: while slots are decoding, an admission round admits at
    # most as many rows as the EMA per-row prefill cost predicts will
    # fit in p50_budget_ms/4 of stall (further arrivals wait one tick).
    # 0 = admit every free slot's worth per round (max throughput).
    p50_budget_ms: float = 0.0
    # queue_deadline_ms > 0: a request still queued after this long is
    # failed with finish_reason "timeout" instead of being admitted
    # (its prefill would be wasted — the client has long given up).
    # 0 = wait forever.
    queue_deadline_ms: float = 0.0
    # Stall-free prefill/decode interleaving (the Sarathi-Serve
    # insight, Agrawal et al. 2024): "on" admits long prompts (>
    # prefill_chunk) arriving while slots are decoding as per-tick
    # chunk work — each fused device call runs the decode tick AND at
    # most one [R<=K, prefill_chunk] prefill chunk, so an active
    # slot's token emission never gaps by more than ~one chunk's
    # compute instead of the full prompt prefill. "off" keeps the
    # serialized fused-grid admission (whole [T, C] grid in one call —
    # still the fastest path when nothing is decoding, and what the
    # interleaved path itself falls back to on an idle pool).
    prefill_interleave: str = "off"  # off | on
    # Max admitting rows advanced per fused tick+chunk call (the K in
    # [R<=K, C]); also the carried mini-cache's row count, so HBM cost
    # is K x kv_cache_max_seq of KV. Further long prompts queue for a
    # free row.
    prefill_interleave_rows: int = 4
    # Bounded admission / load shedding. max_pending > 0 caps the
    # number of requests waiting for a slot; max_queue_tokens > 0 caps
    # the total prompt tokens they hold. A submit() that would exceed
    # either cap raises OverloadedError instead of queueing (the
    # sidecar maps it to gRPC RESOURCE_EXHAUSTED, the gateway to HTTP
    # 429 + Retry-After) — overload becomes controlled shedding with a
    # bounded queue instead of unbounded growth and deadline-timeout
    # collapse. 0 = unbounded (the pre-hardening behavior).
    max_pending: int = 0
    max_queue_tokens: int = 0
    # Tick-failure replay: a failed decode tick requeues each victim
    # with its prompt + already-emitted tokens as a replay prefix (the
    # consumer never sees duplicates; greedy outputs are bit-identical
    # to the fault-free run) up to this many times per request. Only
    # requests that exhaust the budget see finish_reason "error". 0 =
    # fail every victim immediately (the pre-replay behavior).
    tick_retry_limit: int = 1


# decode_steps_per_tick="auto" resolves to this on TPU meshes: the full
# tick, dispatched while every slot is live and nobody waits. What the
# chip showed (PERF.md sections 5 and 6, PRs 39, 49 and 50): on the
# pipelined loop the host's ~7.5 ms a tick is hidden behind the tick in
# flight (tick_handoff_share 0.9%, loop_lag_ms_mean 0.47 ms), so a long
# tick buys no throughput from the round trip; its length is admission
# latency, two ticks of it between a client's answer and its first
# token.
DECODE_STEPS_TPU = 8


def resolve_decode_steps(batching: "BatchingConfig", platform: str) -> int:
    """Resolve decode_steps_per_tick (the full tick) for a device
    platform ("tpu", "cpu", ...). The "auto" default is the fused
    multi-step tick on TPU, where the pipelined loop hides the host's
    turn behind the tick in flight, and the classic one-step loop on
    CPU test meshes (compute dominates; overshoot is pure waste)."""
    steps = batching.decode_steps_per_tick
    if steps == "auto":
        return DECODE_STEPS_TPU if platform == "tpu" else 1
    return max(1, int(steps))


def short_tick_steps(full: int) -> int:
    """The pipelined batcher's short tick, from the full one
    (batching.py _tick_steps: dispatched while a request waits or a
    slot is free). A half, never under 1. On the chip (PERF.md section
    6, PR 50) a quarter, 2 of 8 steps, read better where 8 rows decode
    (agent-shared call_ms_p50 -9.6% against the half's -7.7%,
    decode-steady -3.1% against -2.4%) and worse where 32 do and a row
    is admitted a call: chat-sessions -0.2% against -2.4%, with
    device_idle_share up 4.5 points against 1: two steps no longer
    cover an admission round's host turn there. The half held every
    cell's idle share within 1.3 points of the fixed loop's, and a
    step's device time within 0.3% where every tick is short."""
    return max(1, full // 2)


@dataclass
class TrainingConfig:
    """`python -m ggrmcp_tpu train` — the fine-tuning loop with
    checkpoint/resume (reference has no training; SURVEY.md §5.4)."""

    model: str = "tiny-llama"  # registry key in ggrmcp_tpu.models
    steps: int = 100
    batch_size: int = 8
    seq_len: int = 128
    learning_rate: float = 3e-4
    weight_decay: float = 0.01
    mesh: MeshConfig = field(default_factory=MeshConfig)
    # Checkpoint root ("" → no persistence). Each save writes
    # <dir>/step_N/state (full resume state) and <dir>/step_N/params
    # (weights-only, loadable by serving.checkpoint_path).
    checkpoint_dir: str = ""
    save_every_steps: int = 100
    resume: bool = True  # resume from the latest step_N under the dir
    data_path: str = ""  # raw text file ("" → synthetic token stream)
    log_every_steps: int = 10
    seed: int = 0


# Supported serving.quantize modes — the single source of truth for
# config.validate(), the engine's apply-time re-check, and bench knobs.
QUANTIZE_MODES = ("", "int8")


# Default latency-histogram bucket upper bounds (ms), log-spaced 1-2-5
# over 1 ms .. 60 s: FIXED bounds are what make the exported
# _bucket/_sum/_count series aggregatable across backends and
# re-windowable in PromQL (per-process adaptive bounds cannot merge).
# One list shared by ttft/e2e/queue/tick-duration so a dashboard can
# overlay them.
LATENCY_BUCKET_BOUNDS_MS = [
    1.0, 2.0, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0,
    1000.0, 2500.0, 5000.0, 10000.0, 30000.0, 60000.0,
]


@dataclass
class ObservabilityConfig:
    """Engine flight recorder + latency attribution
    (serving/flight_recorder.py): bounded rings of per-tick and
    per-request lifecycle records and fixed-bucket latency histograms,
    exported through ServingStats (gateway /metrics as true Prometheus
    histograms) and the DebugService.GetFlightRecord RPC (gateway
    /debug/ticks, /debug/requests). Disabled, every hook is one
    attribute check — near-zero overhead."""

    enabled: bool = True
    # Ring capacities: ticks are recorded per decode tick (512 ≈ the
    # last few seconds under load), requests per terminal chunk.
    tick_ring: int = 512
    request_ring: int = 2048
    # Histogram bucket upper bounds (ms), strictly ascending. Values
    # above the last bound land in an overflow bucket (+Inf).
    bucket_bounds_ms: list = field(
        default_factory=lambda: list(LATENCY_BUCKET_BOUNDS_MS)
    )


# Default QoS classes (slo.classes): per-class p99 latency objectives
# in milliseconds. TTFT = time to first token, TPOT = time per output
# token (decode interval). The three-tier shape follows DistServe's
# goodput framing (Zhong et al., OSDI'24): a request counts toward
# goodput only when it meets BOTH its class targets.
DEFAULT_SLO_CLASSES = {
    "interactive": {"ttft_p99_ms": 500.0, "tpot_p99_ms": 100.0},
    "batch": {"ttft_p99_ms": 5000.0, "tpot_p99_ms": 500.0},
    "background": {"ttft_p99_ms": 30000.0, "tpot_p99_ms": 2000.0},
}


@dataclass
class SloConfig:
    """Tenant & SLO accounting plane (serving/slo.py,
    docs/observability.md 'SLO accounting'): per-class goodput
    (met/violated/unevaluated partition the total exactly), per-class
    TTFT/TPOT/e2e histograms, SRE multi-window burn rate, and
    cardinality-bounded per-tenant VTC token attribution. Pure
    measurement — the ROADMAP item 2 scheduler consumes these numbers,
    this layer never influences placement. Requires
    observability.enabled (the terminal-chunk hook lives in the flight
    recorder path); disabled, every hook is one attribute check."""

    enabled: bool = True
    # Class a request lands in when it carries no (valid) x-qos-class.
    default_class: str = "interactive"
    # QoS class name → {"ttft_p99_ms": float, "tpot_p99_ms": float}.
    # Class names become Prometheus label values — keep them few and
    # stable (the per-tenant axis is the bounded one, not this).
    classes: dict = field(
        default_factory=lambda: {
            k: dict(v) for k, v in DEFAULT_SLO_CLASSES.items()
        }
    )
    # SRE multi-window burn-rate windows (seconds): burn = violation
    # rate over the window / error budget (0.01 for a p99 objective).
    # Fast window pages, slow window confirms (Google SRE workbook
    # ch. 5 shape).
    burn_windows_s: list = field(default_factory=lambda: [300.0, 3600.0])
    # Per-tenant table cardinality bound: at most this many distinct
    # tenants tracked per batcher; the least-recently-active tenant is
    # folded into the explicit "~overflow" bucket when a new one needs
    # the slot, so counters conserve while label growth stays bounded.
    tenant_top_k: int = 64
    # VTC weights (S-LoRA/VTC fairness accounting): weighted tokens =
    # vtc_prompt_weight * prompt_tokens + vtc_decode_weight *
    # decode_tokens. Decode tokens cost more than prefill tokens per
    # unit of service time, so they weigh heavier by default.
    vtc_prompt_weight: float = 1.0
    vtc_decode_weight: float = 2.0


@dataclass
class SchedulerConfig:
    """Preemptive SLO-aware scheduler (serving/scheduler.py,
    docs/scheduling.md): QoS-class priority queues with VTC fair share
    inside each class, demote-don't-kill preemption of low-priority
    decode slots when the high-priority class is about to breach its
    TTFT objective, and a Sarathi-style per-round prefill token budget
    so long-prompt admission never stalls interactive decode. Off by
    default: admission stays plain FIFO (_PendingQueue) and none of
    the knobs below influence placement. The per-class Retry-After
    derivation is the one surface that works even with the scheduler
    disabled — shed backoff cooperating with class priority costs
    nothing and fixes the flat-1s satellite."""

    enabled: bool = False
    # QoS class priority order, HIGHEST first. Names resolve against
    # serving.slo.classes (the scheduler consumes the measurement
    # plane's vocabulary — it never defines its own). A request whose
    # class is missing from this list schedules at the LAST (lowest)
    # listed class's priority.
    classes: list = field(
        default_factory=lambda: ["interactive", "batch", "background"]
    )
    # Preemption (demote-don't-kill): when the top waiting class has
    # no free slot and its objective is at risk, demote the
    # lowest-priority active slot — paged KV pages register + demote
    # to the host tier, the adapter lease releases back to the arena,
    # and the request parks for resume. False = priority queues and
    # fair share only, never touch running slots.
    preemption: bool = True
    # Preempt when the top waiting class's head-of-queue wait exceeds
    # this fraction of the class's TTFT p99 target (deterministic
    # trigger), OR its fast-window burn rate meets the threshold
    # below (load-signal trigger). Either alone suffices.
    preempt_wait_fraction: float = 0.5
    preempt_burn_threshold: float = 1.0
    # At most this many victims demoted per loop turn: preemption is
    # a scalpel, not a purge — one slot per turn keeps the executor
    # stream's demote work bounded by one admission's worth.
    max_preempts_per_turn: int = 1
    # A resumed request whose adapter row cannot be reacquired
    # (arena exhausted — every row pinned) re-parks and retries this
    # many times before shedding typed ("overloaded").
    resume_retry_limit: int = 8
    # Sarathi-style stall-free admission: cap the prompt tokens one
    # admission round may prefill while decode slots are active (the
    # chunked-prefill budget as a tick-time control knob). 0 = off.
    # Deferred requests requeue at the head — delayed one tick, never
    # starved, never reordered.
    prefill_budget_tokens: int = 0
    # TenantTable.shares() snapshot TTL (seconds) for fair-share
    # ordering — the scheduler reads live VTC counters at most this
    # often, so queue pops stay O(lanes) instead of O(tenants).
    shares_ttl_s: float = 0.05
    # Per-class Retry-After derivation for shed responses: class at
    # priority index i advertises base * factor**i seconds
    # (interactive 1s, batch 2s, background 4s at the defaults) —
    # background backs off longer, so retry pressure drains from the
    # classes the scheduler protects first.
    retry_after_base_s: float = 1.0
    retry_after_factor: float = 2.0


@dataclass
class GrammarConfig:
    """Schema-constrained decoding (ggrmcp_tpu/grammar): compile MCP
    tool output schemas into token-level DFAs and enforce them
    on-device during decode (GenerateRequest.constraint). Disabled,
    constrained requests are refused with INVALID_ARGUMENT and the
    batcher's table arena shrinks to the single accept-all state."""

    enabled: bool = True
    # Per-schema DFA state budget: compilation of a schema whose DFA
    # exceeds this raises a typed SchemaTooComplexError (the caller's
    # error, surfaced as INVALID_ARGUMENT — never a 500).
    max_states: int = 1024
    # Device table arena rows shared by ALL live grammars per batcher
    # (state 0 is the reserved accept-all state). HBM cost is
    # arena_states x vocab x 5 bytes (bool mask + int32 transition) —
    # ~5 MB at 4096 x 259. Too many DISTINCT schemas decoding at once
    # raises GrammarCapacityError (RESOURCE_EXHAUSTED).
    arena_states: int = 4096
    # Sidecar-side LRU of compiled DFAs, keyed by canonical schema hash.
    cache_entries: int = 32
    # Jump-ahead constrained decoding (SGLang compressed-FSM
    # jump-forward / XGrammar forced runs; docs/structured_output.md
    # "Jump-ahead"): when a slot's DFA state admits exactly one token
    # (or a chain of such states), the jitted tick emits up to jump_max
    # forced tokens in ONE multi-position forward instead of one
    # forward per token. 0 disables (plain one-token constrained
    # decoding); the window is static — shape-invariant across schema
    # mixes, so nothing recompiles — and bounded by the compiler's
    # per-state precompute cap (compiler.JUMP_CAP = 16). Greedy output
    # is bit-identical on vs off (forced tokens are what masked
    # sampling would emit anyway), so the default is on.
    jump_max: int = 8


# Replica-routing policies (gateway.routing.policy) — the single source
# of truth for config.validate() and rpc/router.py.
ROUTING_POLICIES = ("round_robin", "least_loaded", "affinity")

# Replica roles (serving.role) — the single source of truth for
# config.validate(), the sidecar, and the role-aware router
# (docs/routing.md). "mixed" is today's behavior bit-for-bit; "prefill"
# replicas take long-prompt admissions and ship the finished prompt's
# KV pages to a decode replica (sidecar→sidecar TransferKV); "decode"
# replicas admit those requests with pre-populated pages and skip
# prefill entirely (DistServe-style disaggregation, Zhong et al.
# OSDI'24, over Mooncake-style page shipping).
SERVING_ROLES = ("mixed", "prefill", "decode")


@dataclass
class RoutingConfig:
    """Load-aware replica routing over DP replica pools
    (rpc/router.py, docs/routing.md). Applies whenever several
    discovered backends serve the SAME method full name — the gateway
    then chooses the serving replica per call instead of pinning to
    one upstream (the reference's single-target limitation)."""

    # "round_robin" — per-tool cursors over the healthy replica set
    #   (the historical default; bitwise behavior-compatible with the
    #   pre-router path).
    # "least_loaded" — score each replica from the background
    #   ServingStats snapshot (pending queue depth + EWMA TTFT) and
    #   place on the cheapest one; routing never blocks on a gRPC
    #   fan-out, and a stale/wedged snapshot degrades LOUDLY to
    #   round-robin, never to a stall.
    # "affinity" — rendezvous(HRW)-hash a stable per-call key
    #   (x-session-id header, else tool name + the serialized-request
    #   preamble) over the healthy replica set, so one replica
    #   accumulates a session's paged-KV prefix pages instead of every
    #   replica cold-prefilling them (docs/paged_kv.md). Affinity is a
    #   PREFERENCE: an overloaded home replica spills to the least
    #   loaded one (spill_threshold).
    policy: str = "round_robin"
    # Affinity key fallback: first N bytes of the canonically
    # serialized arguments (sorted-key JSON), hashed with the tool
    # name. Big enough to span a system-prompt preamble, small enough
    # that the key derivation stays off the hot path's flamegraph.
    affinity_preamble_bytes: int = 256
    # Spill when the affinity-chosen replica's load score exceeds this
    # (score units: 1.0 per queued request + EWMA TTFT / 100 ms).
    # 0 disables spilling (strict affinity).
    spill_threshold: float = 8.0
    # DEPRECATED heuristic (off by default), superseded by real
    # prefill/decode disaggregation (serving.role + the disagg knobs
    # below): steer requests whose estimated prefill work exceeds
    # steer_prefill_min_tokens toward replicas whose cumulative
    # tick-phase attribution shows the smallest admit-phase (prefill)
    # share. Only consulted when no affinity key applies. The moment
    # any replica declares a non-"mixed" serving.role, steer_prefill=on
    # is rejected with a typed error naming the migration — the two
    # mechanisms must not fight over placement (docs/routing.md).
    steer_prefill: str = "off"  # off | on
    steer_prefill_min_tokens: int = 1024
    # Prefill/decode disaggregation (serving.role, docs/routing.md).
    # "auto" (default): the two-leg prefill→TransferKV→decode placement
    # engages as soon as the ServingStats snapshot shows a prefill-role
    # replica AND a decode-capable one — a pure-mixed fleet routes
    # exactly as before, bit-for-bit. "off": never split, even with
    # roles declared (prefill replicas are then simply excluded from
    # short-request placement).
    disagg: str = "auto"  # auto | off
    # Requests whose estimated prefill work (prompt bytes; exact for
    # the byte tokenizer, ~4x high for BPE) is below this never take
    # the two-leg path — a short prompt's prefill costs less than the
    # transfer round-trip it would save.
    disagg_min_prompt_tokens: int = 1024
    # ServingStats snapshots older than this are considered wedged:
    # score-based policies fall back to round-robin (with a warning)
    # until the background refresh recovers.
    stale_stats_max_age_s: float = 30.0


@dataclass
class FleetConfig:
    """Self-healing elastic fleet supervisor (serving/fleet.py,
    docs/fleet.md). The closed observe→decide→act loop over a set of
    replica child processes: scale up on sustained shed, drain+retire
    on sustained idle, and heal — restart a replica whose process
    exits or whose health flaps — with exponential backoff + jitter,
    all under a max-churn budget so the supervisor provably cannot
    flap itself. Every decision is a typed FleetAction with a reason;
    `POST /admin/fleet?action=pause|resume` gates the whole loop."""

    enabled: bool = False
    # Replica-count floor/ceiling. The supervisor NEVER drains or
    # retires below min_replicas — including during heal actions
    # (tests/test_fleet.py property suite) — and never spawns above
    # max_replicas.
    min_replicas: int = 1
    max_replicas: int = 4
    # Scale-up pressure signals: sustained shed (any backend's
    # shed_requests counter rising) or windowed backend TTFT p99 above
    # this SLO target (ms).
    slo_ttft_p99_ms: float = 2000.0
    # A shed-counter rise asserts pressure for this long (seconds).
    # The ServingStats snapshot refreshes slower than the decide loop
    # ticks, so without the hold, consecutive observes of the SAME
    # cached counter would reset the sustain clock between every
    # refresh and pressure could never accumulate. Must stay below
    # scale_up_sustain_s or a single rise could fake a sustained
    # episode (validate() enforces it). 0 = no hold (a rise counts
    # only on the step that sees it — deterministic-test mode).
    shed_hold_s: float = 6.0
    # Hysteresis gates: pressure/idle must hold this long before ONE
    # action fires (then the clock re-arms — a sustained episode
    # produces one spawn per sustain period, never a double-spawn).
    scale_up_sustain_s: float = 10.0
    scale_down_sustain_s: float = 60.0
    # Heal trigger: this many health transitions (healthy↔unhealthy
    # edges) within flap_window_s marks a replica flapping — it is
    # drained (when the pool floor allows), killed, and restarted.
    flap_threshold: int = 3
    flap_window_s: float = 60.0
    # Churn budget: state-changing actions (spawn/drain/kill/restart)
    # allowed per sliding action_window_s. Exhausted budget suppresses
    # further actions (counted + logged) — the supervisor's own
    # anti-flap bound.
    max_actions_per_window: int = 4
    action_window_s: float = 60.0
    # Restart backoff: min(backoff_max_s, backoff_base_s * 2^attempt)
    # plus up to backoff_jitter fraction of that (deterministic
    # per-supervisor RNG), so a crash-looping fleet doesn't
    # thundering-herd its own restarts. After restart_max_attempts
    # consecutive failed restarts the replica is given up (retired
    # loudly) and a fresh spawn replaces it when below min_replicas.
    backoff_base_s: float = 1.0
    backoff_max_s: float = 60.0
    backoff_jitter: float = 0.2
    restart_max_attempts: int = 5
    # Control-loop period (observe→decide→act) and the grace between
    # draining a retiring replica and killing it.
    decide_interval_s: float = 2.0
    drain_grace_s: float = 10.0
    # Bounded action-log ring exported on /stats and /debug/requests.
    action_log: int = 256


@dataclass
class GatewayConfig:
    """Gateway-side behavior knobs (no reference analogue)."""

    # Replica routing policy + affinity/drain knobs (rpc/router.py).
    routing: RoutingConfig = field(default_factory=RoutingConfig)
    # Per-tool structured-output opt-in: MCP tool name → source of the
    # schema to enforce on that tool's generated text. "self" (or "")
    # enforces the tool's OWN output schema; any other value names a
    # discovered tool whose output schema to enforce. The gateway
    # inlines the resolved schema into GenerateRequest.constraint on
    # every call to the tool; only tools whose input message carries a
    # `constraint` field (the TPU Generate surface) are eligible.
    # Callers can also pass `constraint.toolOutputSchemaRef` per call —
    # the gateway resolves it the same way.
    structured_output: dict = field(default_factory=dict)
    # Per-MCP-tool settings: tool name → {"adapter": <name>}. The
    # adapter binding injects `adapter=<name>` into every call of that
    # tool whose input message carries an `adapter` field (the TPU
    # Generate surface), so one pod serves a thousand fine-tunes
    # behind one tool list (docs/multi_lora.md). Per-call/per-session
    # override: the forwarded `x-adapter-id` header beats the binding;
    # an explicit `adapter` argument beats both.
    tools: dict = field(default_factory=dict)


@dataclass
class ServingConfig:
    model: str = "tiny-llama"  # registry key in ggrmcp_tpu.models
    dtype: str = "bfloat16"
    # Replica role in a disaggregated fleet (SERVING_ROLES,
    # docs/routing.md): "mixed" (default — serve everything, today's
    # behavior bit-for-bit), "prefill" (take long-prompt admissions,
    # ship the finished prompt's KV pages to a decode replica via the
    # sidecar→sidecar TransferKV RPC), or "decode" (admit transferred
    # requests with pre-populated pages and skip prefill). Non-mixed
    # roles require batching.paged_kv=on (pages ARE the transfer
    # format) and no kv_tiers (one arena per replica to import into).
    role: str = "mixed"
    mesh: MeshConfig = field(default_factory=MeshConfig)
    batching: BatchingConfig = field(default_factory=BatchingConfig)
    port: int = 50051
    # Unix-domain-socket listen path. When set, the sidecar binds
    # `unix:{uds_path}` instead of TCP. The co-located deployment
    # (gateway --tpu) defaults to a private UDS because the hop is
    # loopback-only by construction and a UDS round trip costs less
    # shared-core CPU than TCP loopback.
    uds_path: str = ""
    # `--tpu` co-launch transport: auto-generate a per-process UDS for
    # the gateway→sidecar hop (uds_path, when set, pins the path).
    # False restores a TCP loopback hop on serving.port.
    colaunch_uds: bool = True
    # Orbax checkpoint directory with model params (empty → random init).
    checkpoint_path: str = ""
    # HuggingFace Llama checkpoint directory (config.json +
    # *.safetensors). When set, the model architecture comes from the
    # checkpoint's config.json and `model` is ignored
    # (serving/weights.py). Mutually exclusive with checkpoint_path.
    hf_checkpoint_path: str = ""
    # Flagship-fallback opt-in (ROADMAP item 1 / the TP watcher ladder):
    # when hf_checkpoint_path is set but the directory is ABSENT, fall
    # back to serving `model` with random init (real geometry and
    # tokenizer, meaningless text) instead of failing startup. Off by
    # default — a production config pointing at missing weights must
    # die loudly, not quietly serve noise.
    hf_checkpoint_optional: bool = False
    # HuggingFace tokenizer.json path (empty → hermetic byte tokenizer).
    tokenizer_path: str = ""
    # Weight quantization for decoder serving: "" (off) or "int8"
    # (per-channel weight-only — halves HBM traffic on decode).
    quantize: str = ""
    # KV-cache storage: "" (model dtype) or "int8" (per-position/head
    # scales — halves KV HBM and the per-step KV bandwidth, doubling
    # context/slot headroom; decode attention takes the XLA path so
    # the cast+scale fuse into the matmuls). Composes with `quantize`.
    # "fp8": float8_e4m3fn storage without scales, for the latent
    # family's cache only (models/mla_moe.py; refused for the others
    # in engine._UNSUPPORTED): half the arena's bytes again, for twice
    # the sessions or context, at 4 significant bits. What that costs
    # in accuracy, as read on the chip: docs/paged_kv.md.
    kv_cache_dtype: str = ""
    # Benchmark staging: initialize the int8-quantized weight structure
    # DIRECTLY with synthetic values (random int8 + small scales)
    # instead of dense-init-then-quantize. Serving throughput and MFU
    # are weight-value independent, so this gives honest perf numbers
    # for models whose dense init would not fit the chip (llama3-8b
    # bf16 is 16 GB — a v5e-1's entire HBM — while its int8 form is
    # ~8 GB). Outputs are meaningless; requires quantize="int8" and no
    # checkpoint. The bench labels runs using it.
    synthetic_weights: bool = False
    # Ring-buffer KV for sliding-window models: cache capacity becomes
    # window + prefill_chunk - 1 instead of the full context, and
    # generation length is bounded by the model's RoPE range, not KV
    # HBM (docs/kv_ring_design.md). Batcher-path only; incompatible
    # with kv_tiers; composes with int8 KV and pipeline serving
    # (validate() below, tests/test_pp_serving.py).
    kv_ring: bool = False
    # Sequence-parallel prefill over the mesh `sequence` axis: "ring"
    # (ppermute K/V rotation) or "ulysses" (all_to_all head re-shard);
    # "" disables. Engages for fresh prefills of at least
    # sp_prefill_min_seq tokens when the sequence axis is > 1
    # (serving/engine.py::prefill_forward, SURVEY §5.7).
    sp_prefill: str = "ring"
    sp_prefill_min_seq: int = 1024
    # Multi-LoRA serving (ops/lora.py): named adapters served from the
    # SAME continuous batch via per-row low-rank deltas on the fused
    # qkv projection. Dense Llama, single-stage meshes only (the
    # engine validates); empty adapter list = off.
    lora: "LoraConfig" = field(default_factory=lambda: LoraConfig())
    # Deterministic fault injection (utils/failpoints.py), e.g.
    # "tick_fail:every=7,admit_slow:ms=50". Armed at engine init; the
    # GGRMCP_FAILPOINTS env var arms the same registry at import.
    # "" = nothing armed. Chaos testing only — never set in production.
    failpoints: str = ""
    # Flight recorder + latency attribution (ring sizes, histogram
    # bucket bounds, enable flag) — see ObservabilityConfig.
    observability: "ObservabilityConfig" = field(
        default_factory=lambda: ObservabilityConfig()
    )
    # Schema-constrained decoding (DFA logit masking) — GrammarConfig.
    grammar: "GrammarConfig" = field(default_factory=lambda: GrammarConfig())
    # Tenant & SLO accounting plane (per-class goodput/burn, per-tenant
    # VTC token attribution) — SloConfig.
    slo: "SloConfig" = field(default_factory=lambda: SloConfig())
    # Preemptive SLO-aware scheduler (QoS priority queues, VTC fair
    # share, demote-don't-kill preemption) — SchedulerConfig.
    scheduler: "SchedulerConfig" = field(
        default_factory=lambda: SchedulerConfig()
    )


@dataclass
class LoraConfig:
    # BOOT-TIME adapter names; request field `adapter` selects one.
    # Served ids are 1..N in list order (0 = the base model). Empty =
    # LoRA off (unless `registry` is set — the dynamic mode below).
    # Kept supported as the static migration path from PR-era configs;
    # docs/multi_lora.md has the registry migration.
    adapters: list = field(default_factory=list)
    rank: int = 8  # low-rank dimension r (factors stored pre-scaled)
    # Directory of trained factors for the BOOT-TIME adapters, one
    # `{name}.npz` per adapter with arrays `a` [L, D, r] and `b`
    # [L, r, (H+2KVH)*Dh] (pre-scaled by alpha/r). Missing files leave
    # that adapter a zero-init no-op; "" loads nothing.
    path: str = ""
    # DYNAMIC adapter registry (serving/adapter_arena.py,
    # docs/multi_lora.md): a directory of `{name}.npz` factor pairs,
    # scanned at REQUEST time — dropping a new file serves a new
    # tenant with no restart and no recompile. Adapter capacity is the
    # registry, not HBM: only `arena_rows` adapters are device-resident
    # at once (refcounted, LRU-evicted under churn; all-pinned sheds
    # typed RESOURCE_EXHAUSTED). Mutually exclusive with `adapters`
    # (the static list) — every adapter rides the arena in this mode.
    registry: str = ""
    # Device-resident adapter rows beside the reserved base row 0.
    # HBM cost is arena_rows × L × r × (D + qkv_out) in the model
    # dtype; the `lora` memory-ledger component reports the real bytes.
    arena_rows: int = 8


# ---------------------------------------------------------------------------
# Logging / observability
# ---------------------------------------------------------------------------


@dataclass
class LoggingConfig:
    level: str = "info"
    development: bool = False
    json_output: bool = True
    # "json" switches gateway AND sidecar logging to structured
    # one-line JSON records (utils/jsonlog.JsonFormatter): every line
    # is parseable json.dumps output carrying ts/level/logger/msg plus
    # the current trace id from the tracing contextvar, so process
    # logs join /debug/traces, /debug/requests, and /debug/timeline by
    # trace id. "" keeps the legacy format strings above (json_output
    # interpolates into a JSON-shaped template without escaping —
    # greppable, not parseable). GGRMCP_LOG_JSON=1 is the config-free
    # opt-in for both processes.
    format: str = ""  # "" | "json"


@dataclass
class MetricsConfig:
    enabled: bool = True
    prometheus: bool = True  # real text-format metrics, not a JSON stub


# ---------------------------------------------------------------------------
# Root
# ---------------------------------------------------------------------------


@dataclass
class Config:
    server: ServerConfig = field(default_factory=ServerConfig)
    grpc: GRPCConfig = field(default_factory=GRPCConfig)
    mcp: MCPConfig = field(default_factory=MCPConfig)
    session: SessionConfig = field(default_factory=SessionConfig)
    tools: ToolsConfig = field(default_factory=ToolsConfig)
    gateway: GatewayConfig = field(default_factory=GatewayConfig)
    serving: ServingConfig = field(default_factory=ServingConfig)
    fleet: FleetConfig = field(default_factory=FleetConfig)
    training: TrainingConfig = field(default_factory=TrainingConfig)
    logging: LoggingConfig = field(default_factory=LoggingConfig)
    metrics: MetricsConfig = field(default_factory=MetricsConfig)

    # -- validation ---------------------------------------------------------

    def validate(self, colaunch: bool = False) -> None:
        """Raise ValueError on nonsense values (config.go:328-357 parity).
        `colaunch`: this config is for `gateway --tpu`, whose own
        process holds the host's chips."""
        if not (0 < self.server.port < 65536):
            raise ValueError(f"invalid HTTP port: {self.server.port}")
        if self.server.workers < 1:
            raise ValueError("server.workers must be >= 1")
        if self.server.http_impl not in ("fastlane", "aiohttp"):
            raise ValueError(
                f"unknown server.http_impl {self.server.http_impl!r}; "
                "supported: 'fastlane', 'aiohttp'"
            )
        if not (0 < self.grpc.port < 65536):
            raise ValueError(f"invalid gRPC port: {self.grpc.port}")
        if self.server.request_timeout_s <= 0:
            raise ValueError("request timeout must be positive")
        if self.grpc.connect_timeout_s <= 0:
            raise ValueError("gRPC connect timeout must be positive")
        if self.grpc.max_message_bytes <= 0:
            raise ValueError("gRPC max message size must be positive")
        if self.session.max_sessions <= 0:
            raise ValueError("session capacity must be positive")
        if self.tools.max_schema_depth <= 0:
            raise ValueError("schema depth must be positive")
        if self.grpc.descriptor_set.enabled and not self.grpc.descriptor_set.path:
            raise ValueError("descriptor set enabled but no path given")
        _steps = self.serving.batching.decode_steps_per_tick
        if isinstance(_steps, str) and _steps != "auto" and _steps.isdigit():
            # Env overrides arrive as strings (the field's default is
            # the string "auto", so _coerce can't know to int them).
            _steps = int(_steps)
            self.serving.batching.decode_steps_per_tick = _steps
        if _steps != "auto" and (
            isinstance(_steps, bool)
            or not isinstance(_steps, int)
            or _steps < 1
        ):
            raise ValueError(
                "decode_steps_per_tick must be 'auto' or an int >= 1"
            )
        if self.serving.batching.pipeline_ticks not in ("auto", "on", "off"):
            raise ValueError(
                "batching.pipeline_ticks must be one of auto/on/off"
            )
        # Validated against the WORST-CASE resolved mode: "auto" steps
        # resolve to DECODE_STEPS_TPU on TPU (1 on CPU), and
        # pipeline_ticks="auto" doubles the reserve only there — but a
        # config must be valid wherever it is deployed, so the check
        # uses the TPU resolution. A CPU-only deployment hitting this
        # error can set decode_steps_per_tick=1 / pipeline_ticks="off"
        # explicitly (the batcher would resolve to that anyway).
        _ticks_deep = resolve_decode_steps(self.serving.batching, "tpu") * (
            1 if self.serving.batching.pipeline_ticks == "off" else 2
        )
        if _ticks_deep >= self.serving.batching.kv_cache_max_seq:
            # The batcher reserves steps_per_tick-1 cache positions for
            # tick overshoot (2x-1 when pipeline_ticks adds a tick of
            # emission lag); at >= max_seq the admissible request size
            # degenerates to nothing and overshoot can clamp-write at
            # the cache tail.
            raise ValueError(
                "decode_steps_per_tick (x2 under pipeline_ticks) must be "
                "< batching.kv_cache_max_seq (worst-case TPU resolution "
                "of 'auto')"
            )
        if self.serving.batching.p50_budget_ms < 0:
            raise ValueError("p50_budget_ms must be >= 0 (0 = off)")
        if self.serving.batching.queue_deadline_ms < 0:
            raise ValueError("queue_deadline_ms must be >= 0 (0 = off)")
        if self.serving.batching.prefill_interleave not in ("off", "on"):
            raise ValueError(
                "batching.prefill_interleave must be one of off/on"
            )
        if self.serving.batching.prefill_interleave_rows < 1:
            raise ValueError("batching.prefill_interleave_rows must be >= 1")
        if self.serving.batching.max_pending < 0:
            raise ValueError("batching.max_pending must be >= 0 (0 = unbounded)")
        if self.serving.batching.max_queue_tokens < 0:
            raise ValueError(
                "batching.max_queue_tokens must be >= 0 (0 = unbounded)"
            )
        if self.serving.batching.tick_retry_limit < 0:
            raise ValueError(
                "batching.tick_retry_limit must be >= 0 (0 = no replay)"
            )
        if self.serving.failpoints:
            from ggrmcp_tpu.utils.failpoints import parse_spec

            try:
                parse_spec(self.serving.failpoints)
            except ValueError as exc:
                # A chaos config with a typo must fail at parse time,
                # not silently inject nothing.
                raise ValueError(f"serving.failpoints: {exc}")
        obs = self.serving.observability
        if obs.tick_ring < 1 or obs.request_ring < 1:
            raise ValueError(
                "observability.tick_ring/request_ring must be >= 1"
            )
        try:
            bounds = [float(b) for b in obs.bucket_bounds_ms]
        except (TypeError, ValueError):
            raise ValueError(
                "observability.bucket_bounds_ms must be numbers"
            )
        if not bounds or any(b <= 0 for b in bounds) or bounds != sorted(
            set(bounds)
        ):
            # Strictly ascending positive bounds: Prometheus le labels
            # must be unique and ordered or the exposition is invalid.
            raise ValueError(
                "observability.bucket_bounds_ms must be strictly "
                "ascending positive values"
            )
        grammar = self.serving.grammar
        if grammar.max_states < 2:
            raise ValueError("grammar.max_states must be >= 2")
        if grammar.arena_states < grammar.max_states + 1:
            # State 0 is reserved (accept-all); the arena must hold at
            # least one maximal compiled schema beside it.
            raise ValueError(
                "grammar.arena_states must be > grammar.max_states "
                "(state 0 is the reserved accept-all state)"
            )
        if grammar.cache_entries < 1:
            raise ValueError("grammar.cache_entries must be >= 1")
        if not 0 <= grammar.jump_max <= 16:
            # Upper bound = compiler.JUMP_CAP: runs are precomputed to
            # 16 tokens per state; a wider serving window would jump
            # shorter than configured, silently.
            raise ValueError(
                "grammar.jump_max must be in [0, 16] (0 disables "
                "jump-ahead; 16 is the compiler's forced-run cap)"
            )
        slo = self.serving.slo
        if not isinstance(slo.classes, dict) or not slo.classes:
            raise ValueError(
                "serving.slo.classes must be a non-empty dict of "
                "class name -> {ttft_p99_ms, tpot_p99_ms}"
            )
        for cname, targets in slo.classes.items():
            if not isinstance(cname, str) or not cname:
                raise ValueError(
                    "serving.slo.classes keys must be non-empty class names"
                )
            if not isinstance(targets, dict):
                raise ValueError(
                    f"serving.slo.classes[{cname!r}] must be a dict "
                    "with ttft_p99_ms/tpot_p99_ms"
                )
            unknown = set(targets) - {"ttft_p99_ms", "tpot_p99_ms"}
            if unknown:
                raise ValueError(
                    f"serving.slo.classes[{cname!r}]: unknown keys "
                    f"{sorted(unknown)}; supported: ttft_p99_ms, "
                    "tpot_p99_ms"
                )
            for key in ("ttft_p99_ms", "tpot_p99_ms"):
                try:
                    val = float(targets.get(key, 0))
                except (TypeError, ValueError):
                    val = -1.0
                if val <= 0:
                    raise ValueError(
                        f"serving.slo.classes[{cname!r}].{key} must be "
                        "a positive number of milliseconds"
                    )
        if slo.default_class not in slo.classes:
            raise ValueError(
                f"serving.slo.default_class {slo.default_class!r} is not "
                f"in serving.slo.classes {sorted(slo.classes)}"
            )
        try:
            windows = [float(w) for w in slo.burn_windows_s]
        except (TypeError, ValueError):
            raise ValueError("serving.slo.burn_windows_s must be numbers")
        if not windows or any(w <= 0 for w in windows) or windows != sorted(
            set(windows)
        ):
            raise ValueError(
                "serving.slo.burn_windows_s must be strictly ascending "
                "positive window lengths (seconds)"
            )
        if slo.tenant_top_k < 1:
            raise ValueError("serving.slo.tenant_top_k must be >= 1")
        if slo.vtc_prompt_weight < 0 or slo.vtc_decode_weight < 0:
            raise ValueError(
                "serving.slo.vtc_prompt_weight/vtc_decode_weight must "
                "be >= 0"
            )
        sched = self.serving.scheduler
        if not isinstance(sched.classes, list) or not sched.classes or not all(
            isinstance(c, str) and c for c in sched.classes
        ):
            raise ValueError(
                "serving.scheduler.classes must be a non-empty list of "
                "class names, highest priority first"
            )
        if len(set(sched.classes)) != len(sched.classes):
            raise ValueError(
                "serving.scheduler.classes must not repeat a class name"
            )
        if sched.enabled:
            unknown = [c for c in sched.classes if c not in slo.classes]
            if unknown:
                # The scheduler consumes the SLO plane's vocabulary:
                # a priority class with no objectives has no TTFT
                # target to trigger preemption against.
                raise ValueError(
                    f"serving.scheduler.classes {unknown} are not in "
                    f"serving.slo.classes {sorted(slo.classes)}"
                )
            if not slo.enabled or not self.serving.observability.enabled:
                raise ValueError(
                    "serving.scheduler.enabled requires serving.slo."
                    "enabled and serving.observability.enabled (the "
                    "scheduler orders by live VTC counters and triggers "
                    "preemption off burn rate — both live in the SLO "
                    "plane)"
                )
        if not 0 < sched.preempt_wait_fraction <= 10:
            raise ValueError(
                "serving.scheduler.preempt_wait_fraction must be in "
                "(0, 10] (fraction of the class TTFT target)"
            )
        if sched.preempt_burn_threshold <= 0:
            raise ValueError(
                "serving.scheduler.preempt_burn_threshold must be > 0"
            )
        if sched.max_preempts_per_turn < 0:
            raise ValueError(
                "serving.scheduler.max_preempts_per_turn must be >= 0"
            )
        if sched.resume_retry_limit < 0:
            raise ValueError(
                "serving.scheduler.resume_retry_limit must be >= 0"
            )
        if sched.prefill_budget_tokens < 0:
            raise ValueError(
                "serving.scheduler.prefill_budget_tokens must be >= 0 "
                "(0 disables the per-round prefill budget)"
            )
        if sched.shares_ttl_s < 0:
            raise ValueError(
                "serving.scheduler.shares_ttl_s must be >= 0"
            )
        if sched.retry_after_base_s <= 0 or sched.retry_after_factor < 1:
            raise ValueError(
                "serving.scheduler.retry_after_base_s must be > 0 and "
                "retry_after_factor >= 1 (lower-priority classes must "
                "never be told to retry SOONER)"
            )
        so = self.gateway.structured_output
        if not isinstance(so, dict) or not all(
            isinstance(k, str) and isinstance(v, str)
            for k, v in so.items()
        ):
            raise ValueError(
                "gateway.structured_output must map tool names to "
                "'self' (or '') or another tool name"
            )
        tools_cfg = self.gateway.tools
        if not isinstance(tools_cfg, dict):
            raise ValueError(
                "gateway.tools must map tool names to per-tool settings"
            )
        for tool, entry in tools_cfg.items():
            if not isinstance(tool, str) or not isinstance(entry, dict):
                raise ValueError(
                    "gateway.tools must map tool names to settings dicts "
                    "(e.g. {\"adapter\": \"acme\"})"
                )
            unknown = set(entry) - {"adapter"}
            if unknown:
                raise ValueError(
                    f"gateway.tools[{tool!r}]: unknown keys "
                    f"{sorted(unknown)}; supported: 'adapter'"
                )
            adapter = entry.get("adapter", "")
            if not isinstance(adapter, str) or not adapter:
                raise ValueError(
                    f"gateway.tools[{tool!r}].adapter must be a "
                    "non-empty adapter name"
                )
        lora = self.serving.lora
        if lora.registry and lora.adapters:
            raise ValueError(
                "serving.lora.registry (dynamic arena) and lora.adapters "
                "(boot-time list) are mutually exclusive — move the "
                "static adapters' .npz files into the registry "
                "(docs/multi_lora.md migration)"
            )
        if (lora.registry or lora.adapters) and lora.rank < 1:
            raise ValueError("serving.lora.rank must be >= 1")
        if lora.arena_rows < 1:
            raise ValueError("serving.lora.arena_rows must be >= 1")
        routing = self.gateway.routing
        if routing.policy not in ROUTING_POLICIES:
            raise ValueError(
                f"unknown gateway.routing.policy {routing.policy!r}; "
                f"supported: {', '.join(ROUTING_POLICIES)}"
            )
        if routing.affinity_preamble_bytes < 1:
            raise ValueError(
                "gateway.routing.affinity_preamble_bytes must be >= 1"
            )
        if routing.spill_threshold < 0:
            raise ValueError(
                "gateway.routing.spill_threshold must be >= 0 "
                "(0 = strict affinity, never spill)"
            )
        if routing.steer_prefill not in ("off", "on"):
            raise ValueError(
                "gateway.routing.steer_prefill must be 'off' or 'on' "
                "(experimental — docs/routing.md)"
            )
        if routing.steer_prefill_min_tokens < 1:
            raise ValueError(
                "gateway.routing.steer_prefill_min_tokens must be >= 1"
            )
        if routing.stale_stats_max_age_s <= 0:
            raise ValueError(
                "gateway.routing.stale_stats_max_age_s must be > 0"
            )
        if routing.disagg not in ("auto", "off"):
            raise ValueError(
                f"unknown gateway.routing.disagg {routing.disagg!r}; "
                "supported: 'auto', 'off'"
            )
        if routing.disagg_min_prompt_tokens < 1:
            raise ValueError(
                "gateway.routing.disagg_min_prompt_tokens must be >= 1"
            )
        fleet = self.fleet
        if fleet.min_replicas < 1:
            raise ValueError("fleet.min_replicas must be >= 1")
        if fleet.max_replicas < fleet.min_replicas:
            raise ValueError(
                "fleet.max_replicas must be >= fleet.min_replicas"
            )
        if fleet.slo_ttft_p99_ms <= 0:
            raise ValueError("fleet.slo_ttft_p99_ms must be > 0")
        if fleet.scale_up_sustain_s <= 0 or fleet.scale_down_sustain_s <= 0:
            raise ValueError(
                "fleet.scale_up_sustain_s/scale_down_sustain_s must be > 0"
            )
        if not (0 <= fleet.shed_hold_s < fleet.scale_up_sustain_s):
            raise ValueError(
                "fleet.shed_hold_s must be >= 0 and < scale_up_sustain_s "
                "(a single shed rise must never fake a sustained episode)"
            )
        if fleet.flap_threshold < 2:
            # One transition is any ordinary failure; flapping needs at
            # least a down-up pair to be distinguishable from a crash.
            raise ValueError("fleet.flap_threshold must be >= 2")
        if fleet.flap_window_s <= 0 or fleet.action_window_s <= 0:
            raise ValueError(
                "fleet.flap_window_s/action_window_s must be > 0"
            )
        if fleet.max_actions_per_window < 1:
            raise ValueError("fleet.max_actions_per_window must be >= 1")
        if fleet.backoff_base_s <= 0 or fleet.backoff_max_s < fleet.backoff_base_s:
            raise ValueError(
                "fleet.backoff_base_s must be > 0 and <= fleet.backoff_max_s"
            )
        if not (0 <= fleet.backoff_jitter < 1):
            raise ValueError("fleet.backoff_jitter must be in [0, 1)")
        if fleet.restart_max_attempts < 1:
            raise ValueError("fleet.restart_max_attempts must be >= 1")
        if fleet.decide_interval_s <= 0:
            raise ValueError("fleet.decide_interval_s must be > 0")
        if fleet.drain_grace_s < 0:
            raise ValueError("fleet.drain_grace_s must be >= 0 (0 = kill "
                             "immediately after drain)")
        if fleet.action_log < 1:
            raise ValueError("fleet.action_log must be >= 1")
        if fleet.enabled and colaunch and not cpu_requested():
            # One process per chip: the co-launched sidecar holds every
            # visible chip, so a fleet child could never get one.
            raise ValueError(
                "fleet.enabled cannot combine with --tpu on a TPU "
                "backend: the co-launched sidecar holds the host's chips "
                "and a replica child process would fail or hang waiting "
                "for them. Process replicas are one per host today "
                "(docs/fleet.md): run one `gateway --tpu` per host, or "
                "the fleet over CPU replicas with JAX_PLATFORMS=cpu"
            )
        if self.serving.role not in SERVING_ROLES:
            raise ValueError(
                f"unknown serving.role {self.serving.role!r}; "
                f"supported: {', '.join(SERVING_ROLES)}"
            )
        if self.serving.role != "mixed":
            if routing.steer_prefill == "on":
                raise ValueError(
                    "gateway.routing.steer_prefill=on is superseded by "
                    "replica roles: a non-'mixed' serving.role does the "
                    "real prefill/decode split (page-granular KV "
                    "shipping). Migrate to serving.role + "
                    "gateway.routing.disagg and drop steer_prefill "
                    "(docs/routing.md role-split runbook)"
                )
            if self.serving.batching.paged_kv != "on":
                raise ValueError(
                    f"serving.role={self.serving.role!r} requires "
                    "batching.paged_kv=on: KV pages are the transfer "
                    "format (docs/paged_kv.md 'pages over the wire')"
                )
            if self.serving.batching.kv_tiers:
                raise ValueError(
                    f"serving.role={self.serving.role!r} does not "
                    "compose with batching.kv_tiers: page import needs "
                    "ONE arena per replica to land transferred pages in"
                )
        if self.logging.format not in ("", "json"):
            raise ValueError(
                f"unknown logging.format {self.logging.format!r}; "
                "supported: 'json' (or '' for the legacy formats)"
            )
        if self.training.steps < 1 or self.training.batch_size < 1:
            raise ValueError("training steps/batch_size must be >= 1")
        if self.training.seq_len < 2:
            raise ValueError("training seq_len must be >= 2 (shift-by-one loss)")
        if self.training.log_every_steps < 1 or self.training.save_every_steps < 1:
            raise ValueError(
                "training log_every_steps/save_every_steps must be >= 1"
            )
        if self.serving.checkpoint_path and self.serving.hf_checkpoint_path:
            raise ValueError(
                "checkpoint_path and hf_checkpoint_path are mutually "
                "exclusive (Orbax vs HuggingFace format)"
            )
        tiers = self.serving.batching.kv_tiers
        if tiers:
            if not all(
                isinstance(t, (list, tuple)) and len(t) == 2
                and int(t[0]) > 0 and int(t[1]) > 0
                for t in tiers
            ):
                raise ValueError(
                    "batching.kv_tiers entries must be [max_seq, slots] "
                    "with positive max_seq/slots"
                )
            seqs = [int(t[0]) for t in tiers]
            if seqs != sorted(seqs) or len(set(seqs)) != len(seqs):
                raise ValueError(
                    "batching.kv_tiers must be strictly ascending by max_seq"
                )
            if _ticks_deep >= seqs[0]:
                raise ValueError(
                    "decode_steps_per_tick (x2 under pipeline_ticks) must "
                    "be < the smallest tier's max_seq"
                )
        batching = self.serving.batching
        if batching.paged_kv not in ("off", "on"):
            raise ValueError("batching.paged_kv must be 'off' or 'on'")
        if batching.paged_kv_page_size < 1:
            raise ValueError("batching.paged_kv_page_size must be >= 1")
        if batching.paged_kv_pages < 0:
            raise ValueError(
                "batching.paged_kv_pages must be >= 0 (0 = auto-size)"
            )
        if batching.paged_kv == "on":
            page = batching.paged_kv_page_size
            if self.serving.kv_ring:
                raise ValueError(
                    "batching.paged_kv and kv_ring are mutually "
                    "exclusive: a ring stores positions mod its "
                    "capacity, a page table maps them — one indirection "
                    "scheme per cache"
                )
            if batching.kv_cache_max_seq % page:
                raise ValueError(
                    f"batching.paged_kv_page_size ({page}) must divide "
                    f"kv_cache_max_seq ({batching.kv_cache_max_seq}): "
                    f"block tables map whole pages"
                )
            for t in tiers or []:
                if int(t[0]) % page:
                    raise ValueError(
                        f"batching.paged_kv_page_size ({page}) must "
                        f"divide every tier max_seq (tier {int(t[0])})"
                    )
        if batching.paged_kv_host_bytes < 0:
            raise ValueError(
                "batching.paged_kv_host_bytes must be >= 0 (0 = no "
                "host tier)"
            )
        if batching.paged_kv_host_file_bytes < 0:
            raise ValueError(
                "batching.paged_kv_host_file_bytes must be >= 0 "
                "(0 = unbounded log)"
            )
        if batching.paged_kv_host_bytes and batching.paged_kv != "on":
            raise ValueError(
                "batching.paged_kv_host_bytes requires paged_kv=on: "
                "the host tier demotes and restores PAGES "
                "(docs/paged_kv.md 'Host tier')"
            )
        if batching.paged_kv_host_path and not batching.paged_kv_host_bytes:
            raise ValueError(
                "batching.paged_kv_host_path is the file tier BEHIND "
                "the host RAM pool: set paged_kv_host_bytes > 0"
            )
        if (
            batching.paged_kv_host_file_bytes
            and not batching.paged_kv_host_path
        ):
            raise ValueError(
                "batching.paged_kv_host_file_bytes caps the file-tier "
                "log: set paged_kv_host_path"
            )
        if self.serving.sp_prefill not in ("", "ring", "ulysses"):
            raise ValueError(
                f"unknown serving.sp_prefill {self.serving.sp_prefill!r}; "
                f"supported: 'ring', 'ulysses'"
            )
        if len(self.serving.uds_path.encode()) > 100:
            # AF_UNIX sun_path caps at ~108 bytes; fail at parse time,
            # not as an opaque bind error after model load.
            raise ValueError(
                f"serving.uds_path too long for AF_UNIX "
                f"({len(self.serving.uds_path.encode())} > 100 bytes)"
            )
        if self.serving.quantize not in QUANTIZE_MODES:
            # Catch typos at parse time, before minutes of checkpoint
            # loading (the engine re-checks at apply time).
            raise ValueError(
                f"unknown serving.quantize {self.serving.quantize!r}; "
                f"supported: 'int8'"
            )
        if self.serving.kv_cache_dtype not in (*QUANTIZE_MODES, "fp8"):
            raise ValueError(
                f"unknown serving.kv_cache_dtype "
                f"{self.serving.kv_cache_dtype!r}; supported: 'int8', 'fp8'"
            )
        if self.serving.synthetic_weights:
            if self.serving.quantize != "int8":
                raise ValueError(
                    "serving.synthetic_weights initializes the int8 "
                    "weight structure; it requires quantize='int8'"
                )
            if self.serving.checkpoint_path or self.serving.hf_checkpoint_path:
                raise ValueError(
                    "serving.synthetic_weights is random-weight perf "
                    "staging; it cannot combine with a checkpoint"
                )
        # kv_cache_dtype='int8' composes with mesh.stage > 1: the
        # staged forward threads QuantizedArray K/V leaves through its
        # tick schedule (parallel/pipeline.py::_pipelined_cached).
        if self.serving.kv_ring:
            if self.serving.batching.kv_tiers:
                raise ValueError(
                    "kv_ring and kv_tiers are mutually exclusive (a "
                    "ring has ONE capacity: window + prefill_chunk - 1)"
                )
            # mesh.stage > 1 composes (round 3): the staged forward
            # threads the ring layout into each stage's cache block.


def default() -> Config:
    return Config()


def development() -> Config:
    """Development overrides (config.go:315-325 parity)."""
    cfg = Config()
    cfg.logging.level = "debug"
    cfg.logging.development = True
    cfg.logging.json_output = False
    cfg.server.rate_limit.enabled = False
    return cfg


# ---------------------------------------------------------------------------
# Loading: defaults → file → env → overrides
# ---------------------------------------------------------------------------


def _merge(obj: Any, data: dict[str, Any], path: str = "") -> None:
    for key, value in data.items():
        attr = key.replace("-", "_")
        if not hasattr(obj, attr):
            raise ValueError(f"unknown config key: {path}{key}")
        current = getattr(obj, attr)
        if dataclasses.is_dataclass(current) and isinstance(value, dict):
            _merge(current, value, f"{path}{key}.")
        else:
            if current is not None and not isinstance(value, type(current)):
                # Allow int→float promotion, nothing else silently.
                if isinstance(current, float) and isinstance(value, int):
                    value = float(value)
                elif isinstance(current, bool) != isinstance(value, bool):
                    raise ValueError(
                        f"config key {path}{key}: expected "
                        f"{type(current).__name__}, got {type(value).__name__}"
                    )
            setattr(obj, attr, value)


def load_file(path: str, base: Optional[Config] = None) -> Config:
    """Load YAML or JSON config over the defaults."""
    cfg = base or default()
    with open(path, "r", encoding="utf-8") as fh:
        text = fh.read()
    if path.endswith((".yaml", ".yml")):
        import yaml

        data = yaml.safe_load(text) or {}
    else:
        data = json.loads(text or "{}")
    _merge(cfg, data)
    return cfg


_ENV_PREFIX = "GGRMCP_"

# GGRMCP_-prefixed control vars that are NOT config-tree paths: the
# chaos registry reads GGRMCP_FAILPOINTS at import
# (utils/failpoints.py), setup_logging reads GGRMCP_LOG_JSON
# (gateway/app.py), GGRMCP_BENCH_* are bench knobs that leak into
# co-launched serving processes' environments, and
# GGRMCP_FLEET_WORKER_* is the fleet replica-worker spawn handshake
# (serving/fleet.py — read directly by the worker, never a config
# path). Without the skip, a process launched with any of them dies at
# config load with "unknown config env var".
_ENV_SKIP = frozenset({"GGRMCP_FAILPOINTS", "GGRMCP_LOG_JSON"})
_ENV_SKIP_PREFIXES = ("GGRMCP_BENCH_", "GGRMCP_FLEET_WORKER_")


def apply_env(cfg: Config, environ: Optional[dict[str, str]] = None) -> Config:
    """Apply GGRMCP_SECTION_KEY=value environment overrides.

    E.g. GGRMCP_SERVER_PORT=8080, GGRMCP_GRPC_HOST=tpu-vm-1,
    GGRMCP_SERVING_MODEL=llama3-8b. Nested paths use single underscores
    resolved greedily against the config tree.
    """
    environ = environ if environ is not None else dict(os.environ)
    for key, raw in environ.items():
        if not key.startswith(_ENV_PREFIX):
            continue
        if key in _ENV_SKIP or key.startswith(_ENV_SKIP_PREFIXES):
            continue
        parts = key[len(_ENV_PREFIX) :].lower().split("_")
        _apply_env_path(cfg, parts, raw, key)
    return cfg


def _apply_env_path(obj: Any, parts: list[str], raw: str, orig: str) -> None:
    # Greedy match: join as many parts as needed to hit an attribute.
    for take in range(len(parts), 0, -1):
        attr = "_".join(parts[:take])
        if hasattr(obj, attr):
            current = getattr(obj, attr)
            rest = parts[take:]
            if dataclasses.is_dataclass(current):
                if not rest:
                    raise ValueError(f"{orig}: points at a section, not a value")
                _apply_env_path(current, rest, raw, orig)
            else:
                if rest:
                    continue  # try a shorter attr match
                setattr(obj, attr, _coerce(current, raw, orig))
            return
    raise ValueError(f"unknown config env var: {orig}")


def _coerce(current: Any, raw: str, orig: str) -> Any:
    if isinstance(current, bool):
        if raw.lower() in ("1", "true", "yes", "on"):
            return True
        if raw.lower() in ("0", "false", "no", "off"):
            return False
        raise ValueError(f"{orig}: expected boolean, got {raw!r}")
    if isinstance(current, int):
        return int(raw)
    if isinstance(current, float):
        return float(raw)
    if isinstance(current, list):
        return [item.strip() for item in raw.split(",") if item.strip()]
    return raw


def load(
    path: Optional[str] = None,
    env: bool = True,
    overrides: Optional[dict[str, Any]] = None,
    dev: bool = False,
) -> Config:
    """Full load pipeline: defaults → file → env → explicit overrides."""
    cfg = development() if dev else default()
    if path:
        cfg = load_file(path, base=cfg)
    if env:
        apply_env(cfg)
    if overrides:
        _merge(cfg, overrides)
    cfg.validate()
    return cfg
