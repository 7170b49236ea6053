"""Engine flight recorder: bounded rings of per-tick, per-admission,
per-hand-off and per-request records plus fixed-bucket latency
histograms.

The postmortem layer (ISSUE 3 / SURVEY.md §5.1): the batcher's existing
counters say HOW MUCH happened; this module records WHAT happened —
what the batcher did at tick N (composition, duration, lifecycle-event
deltas, participating trace ids), which admission round preceded it,
how the loop's turn was handed between event loop and executor, and why
THIS request was slow (t_submit → t_pop → t_admit → t_first_token →
t_finish, from which pending_ms / prefill_ms / queue_ms / ttft_ms /
e2e_ms / decode_tps derive). One trace id walks gateway span → request
record → admission record → tick records.

The histograms are fixed log-spaced bucket counters
(core/config.py::LATENCY_BUCKET_BOUNDS_MS) that the gateway renders as
true Prometheus `_bucket`/`_sum`/`_count` series, so PromQL can sum
across backends and compute windowed quantiles — and a benchmark reads
sum/count as deltas over its window.

Threading: records are appended from the batcher's serialized executor
calls and (for queue-side terminal events) the event loop; deque
appends are atomic under the GIL and the histogram increments take a
micro-lock. Snapshots are lock-free list() copies — same stale-read
contract as the rest of the batcher's counters. Disabled
(observability.enabled=false), every hook is one attribute check.
"""

from __future__ import annotations

import bisect
import dataclasses
import threading
import time
from collections import deque
from typing import Optional

from ggrmcp_tpu.core.config import ObservabilityConfig

# The tick phases the per-tick PhaseTimer attributes, in wall-clock
# order within a tick: admit (queue drain + admission prefill since the
# previous dispatch), sync (host-state snapshots — block tables,
# cur/prev tokens, grammar tables), dispatch (building + launching the
# jitted tick), wait (the blocking token collect: device wait +
# transfer, plus the deliberate in-flight lag under pipelined ticks),
# host (emission, finish handling, allocator bookkeeping). The phases
# PARTITION a tick's duration_ms: their sum equals it by construction
# (contiguous perf_counter marks), which is what makes "this tick lost
# 3.1 ms to host-side table sync" a trustworthy statement.
PHASE_NAMES = ("admit", "sync", "dispatch", "wait", "host")

# The latencies the recorder distributes: the four lifecycle histograms
# (ServingStatsResponse 34-45), one histogram per tick phase (fields
# 67-81), the inter-token-latency (TPOT) histogram (106-108) — per
# finished request, the mean gap between consecutive token emissions,
# derived from the existing first/last lifecycle stamps — and the two
# halves of queue_ms (144-149): pending (submit → the pop that put the
# request into an admission batch) and prefill (that pop → activation).
# Then the admission round's two (169-174): admit_device_ms, one
# observation per admission program call (the program's own time on
# the device: note_admission), and admit_host_ms, one per round (its
# host segments).
# Keys double as the stats() field prefixes:
# <name>_bucket / <name>_sum / <name>_count.
HISTOGRAM_NAMES = ("ttft_ms", "e2e_ms", "queue_ms", "tick_duration_ms") + tuple(
    f"tick_phase_{p}_ms" for p in PHASE_NAMES
) + ("tpot_ms", "pending_ms", "prefill_ms", "admit_device_ms", "admit_host_ms")

# The segments an admission round's PhaseTimer marks (serving/
# batching.py): by every program call (_admission_program), build
# (numpy grids, block tables, argument transfers, grammar tables, table
# sync — everything since the previous mark) and launch (the jitted
# call until it returns: the enqueue); by every seat and by the settle
# (_activate_rows, _settle_round), activate; and by the waits, wherever
# the round makes them (before a second program's launch, else in the
# settle): tick_wait (once, only while a pipelined tick dispatched
# before the round is still on the device: the round's programs queue
# behind it) and device (the host's wait for one program, ending when
# that program's first tokens are ready). A round settled after the
# next tick's dispatch marks the stretch between its return and its
# settle dispatch: the loop's hop and that tick's dispatch, which the
# tick's own record counts as its sync and dispatch phases. build,
# launch and activate are the round's host work.
ADMIT_HOST_MARKS = ("build", "launch", "activate")


class PhaseTimer:
    """Contiguous segment timer: mark(phase) charges the time since the
    previous mark to `phase` and keeps where that segment started, so
    every phase is an INTERVAL on the clock the timer was opened on
    (t0 is paired with a wall stamp by whoever owns the timer).
    Because segments are contiguous from t0, the accumulated phases
    always sum to (last - t0) exactly — the closure property the
    tick-phase acceptance test asserts. Repeated marks of the same
    phase accumulate in `acc` and stay separate in `marks`."""

    __slots__ = ("t0", "last", "acc", "marks")

    def __init__(self) -> None:
        self.t0 = self.last = time.perf_counter()
        self.acc: dict = {}
        # (phase, start) per mark, perf_counter seconds; a segment ends
        # where the next one starts, the last at `last`.
        self.marks: list = []

    def mark(self, phase: str) -> float:
        """Close the open segment as `phase`; returns its length (ms)."""
        now = time.perf_counter()
        ms = (now - self.last) * 1000.0
        self.acc[phase] = self.acc.get(phase, 0.0) + ms
        self.marks.append((phase, self.last))
        self.last = now
        return ms

    def segments(self) -> list:
        """(phase, start, end) per mark, perf_counter seconds."""
        ends = [start for _, start in self.marks[1:]] + [self.last]
        return [(p, s, e) for (p, s), e in zip(self.marks, ends)]


@dataclasses.dataclass
class TickRecord:
    """One decode tick as dispatched (fields mirror protos/serving.proto
    TickRecord; `finished`/`duration_ms` are completed at collect)."""

    seq: int
    t_wall: float
    t_mono: float
    active_slots: int
    admitted: int
    interleaved_rows: int
    shed_total: int
    replayed_total: int
    timed_out_total: int
    trace_ids: list
    duration_ms: float = 0.0
    finished: int = 0
    source: str = ""
    # Jump-ahead tick (grammar.jump_max > 0): forced tokens emitted by
    # multi-token advances on THIS tick and runs advanced (0/0 on
    # plain ticks) — the per-tick jump trace. Completed at collect,
    # like finished/duration_ms.
    jump_tokens: int = 0
    jump_runs: int = 0
    # Decode steps this tick advanced a row by (the full or the short
    # length of the plain tick; a jump tick's 1 + jump_max).
    steps: int = 0
    # Paged KV arena occupancy at dispatch (batching.paged_kv=on; 0
    # off): resident pages — live + reuse-cached — so a tick window
    # shows page pressure next to its admissions/finishes.
    kv_pages_in_use: int = 0
    # Tick-phase attribution (PHASE_NAMES): where this tick's
    # duration_ms went — admit/sync/dispatch/wait/host partition it, so
    # the five always sum to duration_ms (PhaseTimer closure). admit is
    # seeded at dispatch (executor admission time since the previous
    # dispatch); the rest are stamped by contiguous marks and completed
    # at collect, like finished/duration_ms.
    phase_admit_ms: float = 0.0
    phase_sync_ms: float = 0.0
    phase_dispatch_ms: float = 0.0
    phase_wait_ms: float = 0.0
    phase_host_ms: float = 0.0
    # The same phases as intervals: (phase, start offset in ms from
    # t_mono/t_wall) per PhaseTimer mark, in order; each ends where the
    # next starts, the last at duration_ms - phase_admit_ms. Settled at
    # tick_done (proto phase_marks / phase_mark_start_ms).
    marks: list = dataclasses.field(default_factory=list)
    # Device-memory ledger snapshot at dispatch (component -> bytes;
    # empty when the ledger is off) — the timeline's counter-track
    # source (proto memory_components/memory_component_bytes).
    memory: dict = dataclasses.field(default_factory=dict)
    # The live timer carrying this tick's contiguous marks (None when
    # the recorder is disabled); not part of the proto mirror.
    phases: Optional[PhaseTimer] = dataclasses.field(
        default=None, repr=False, compare=False
    )

    def to_dict(self) -> dict:
        return {
            "seq": self.seq,
            "tWall": round(self.t_wall, 6),
            "durationMs": round(self.duration_ms, 3),
            "activeSlots": self.active_slots,
            "admitted": self.admitted,
            "finished": self.finished,
            "interleavedRows": self.interleaved_rows,
            "shedTotal": self.shed_total,
            "replayedTotal": self.replayed_total,
            "timedOutTotal": self.timed_out_total,
            "traceIds": self.trace_ids,
            "source": self.source,
            "jumpTokens": self.jump_tokens,
            "jumpRuns": self.jump_runs,
            "steps": self.steps,
            "kvPagesInUse": self.kv_pages_in_use,
            "phaseAdmitMs": round(self.phase_admit_ms, 3),
            "phaseSyncMs": round(self.phase_sync_ms, 3),
            "phaseDispatchMs": round(self.phase_dispatch_ms, 3),
            "phaseWaitMs": round(self.phase_wait_ms, 3),
            "phaseHostMs": round(self.phase_host_ms, 3),
            "phaseMarks": [p for p, _ in self.marks],
            "phaseMarkStartMs": [round(ms, 3) for _, ms in self.marks],
            "memoryComponents": list(self.memory),
            "memoryComponentBytes": [
                int(b) for b in self.memory.values()
            ],
        }


@dataclasses.dataclass
class AdmissionRecord:
    """One admission round — a _prefill_into_slots executor call
    (protos/serving.proto AdmissionRecord). The span whose duration the
    request-level prefill_ms is mostly made of; `tick_seq` is the tick
    it precedes (whose admit phase carries this round's time)."""

    seq: int
    t_wall: float
    t_mono: float
    duration_ms: float
    family: str
    rows: int
    prompt_tokens: int
    reused_tokens: int
    trace_ids: list
    tick_seq: int
    source: str = ""
    # duration_ms split where the round's timer marked it: host =
    # build + launch + activate (ADMIT_HOST_MARKS), tick_wait = waiting
    # for the tick in flight to leave the device, device = waiting for
    # the admission programs alone on it, dispatch = between the
    # round's return and its settle, where the next tick was
    # dispatched first (`deferred`; 0 otherwise); the four sum to
    # duration_ms. programs = admission program calls in the round.
    host_ms: float = 0.0
    tick_wait_ms: float = 0.0
    device_ms: float = 0.0
    programs: int = 0
    dispatch_ms: float = 0.0
    deferred: bool = False

    def to_dict(self) -> dict:
        return {
            "seq": self.seq,
            "tWall": round(self.t_wall, 6),
            "durationMs": round(self.duration_ms, 3),
            "family": self.family,
            "rows": self.rows,
            "promptTokens": self.prompt_tokens,
            "reusedTokens": self.reused_tokens,
            "traceIds": self.trace_ids,
            "tickSeq": self.tick_seq,
            "source": self.source,
            "hostMs": round(self.host_ms, 3),
            "tickWaitMs": round(self.tick_wait_ms, 3),
            "deviceMs": round(self.device_ms, 3),
            "programs": self.programs,
            "dispatchMs": round(self.dispatch_ms, 3),
            "deferred": self.deferred,
        }


@dataclasses.dataclass
class HandoffRecord:
    """One executor call of the batcher loop with the hand-offs around
    it (protos/serving.proto HandoffRecord): host_ms of loop-side
    python before the submission at t_wall/t_mono, then exec_wait,
    work and lag, contiguous. The per-call form of the ServingStats
    loop_*_ms sums."""

    seq: int
    kind: str
    t_wall: float
    t_mono: float
    host_ms: float
    exec_wait_ms: float
    work_ms: float
    lag_ms: float
    tick_seq: int
    source: str = ""

    def to_dict(self) -> dict:
        return {
            "seq": self.seq,
            "kind": self.kind,
            "tWall": round(self.t_wall, 6),
            "hostMs": round(self.host_ms, 3),
            "execWaitMs": round(self.exec_wait_ms, 3),
            "workMs": round(self.work_ms, 3),
            "lagMs": round(self.lag_ms, 3),
            "tickSeq": self.tick_seq,
            "source": self.source,
        }


@dataclasses.dataclass
class RequestRecord:
    """One request's lifecycle at its terminal chunk (protos/
    serving.proto RequestRecord)."""

    trace_id: str
    t_submit: float  # wall-clock epoch seconds
    queue_ms: float
    # queue_ms split where it splits: submit → the pop that put the
    # request into an admission batch, and that pop → activation.
    # pending_ms + prefill_ms == queue_ms for every record.
    pending_ms: float
    prefill_ms: float
    ttft_ms: float
    e2e_ms: float
    prompt_tokens: int
    tokens: int
    finish_reason: str
    decode_tps: float
    first_tick: int
    last_tick: int
    source: str = ""
    # Grammar-constrained decode (ggrmcp_tpu/grammar): this request's
    # tokens were DFA-masked — "why is this request's output shaped
    # like that" answered from the ring.
    constrained: bool = False
    # Tenant & SLO identity and verdict (serving/slo.py): who the
    # request belonged to, which QoS class judged it, and whether it
    # landed in the `violated` partition — carried on the record so
    # /debug/requests?tenant= and the timeline's violation instants
    # need no re-derivation of class targets.
    tenant: str = ""
    qos_class: str = ""
    slo_violated: bool = False

    def to_dict(self) -> dict:
        return {
            "traceId": self.trace_id,
            "tSubmit": round(self.t_submit, 6),
            "queueMs": round(self.queue_ms, 3),
            "pendingMs": round(self.pending_ms, 3),
            "prefillMs": round(self.prefill_ms, 3),
            "ttftMs": round(self.ttft_ms, 3),
            "e2eMs": round(self.e2e_ms, 3),
            "promptTokens": self.prompt_tokens,
            "tokens": self.tokens,
            "finishReason": self.finish_reason,
            "decodeTps": round(self.decode_tps, 3),
            "firstTick": self.first_tick,
            "lastTick": self.last_tick,
            "source": self.source,
            "constrained": self.constrained,
            "tenant": self.tenant,
            "qosClass": self.qos_class,
            "sloViolated": self.slo_violated,
        }


class LatencyHistogram:
    """Fixed-bound latency histogram: per-bucket (NON-cumulative)
    counts with one overflow slot, plus sum/count — exactly the wire
    shape of the ServingStats *_bucket/_sum/_count fields. The gateway
    cumsums to Prometheus `le` semantics at render time."""

    __slots__ = ("bounds", "counts", "total", "sum")

    def __init__(self, bounds):
        self.bounds = tuple(float(b) for b in bounds)
        self.counts = [0] * (len(self.bounds) + 1)
        self.total = 0
        self.sum = 0.0

    def observe(self, ms: float) -> None:
        # bisect_left: an observation equal to a bound lands in that
        # bound's bucket (Prometheus le is inclusive).
        self.counts[bisect.bisect_left(self.bounds, ms)] += 1
        self.total += 1
        self.sum += ms


class FlightRecorder:
    """Rings + histograms for ONE batcher (each KV tier owns an
    instance; the tiered facade merges views)."""

    def __init__(self, cfg: Optional[ObservabilityConfig] = None,
                 source: str = ""):
        cfg = cfg or ObservabilityConfig()
        self.enabled = bool(cfg.enabled)
        self.source = source
        self._ticks: deque = deque(maxlen=max(1, int(cfg.tick_ring)))
        # Admission rounds and executor hand-offs ride beside the ticks
        # (a loop turn is a few of each per tick), bounded alike.
        self._admissions: deque = deque(maxlen=max(1, int(cfg.tick_ring)))
        self._handoffs: deque = deque(maxlen=max(1, int(cfg.tick_ring)))
        self._requests: deque = deque(maxlen=max(1, int(cfg.request_ring)))
        self._bounds = tuple(float(b) for b in cfg.bucket_bounds_ms)
        self._hists = {
            name: LatencyHistogram(self._bounds) for name in HISTOGRAM_NAMES
        }
        self._lock = threading.Lock()
        # Slots activated since the last tick record (consumed at the
        # next dispatch → TickRecord.admitted).
        self._admitted_since_tick = 0

    # -- batcher-side hooks -------------------------------------------------

    def note_admit(self) -> None:
        if self.enabled:
            self._admitted_since_tick += 1

    def tick_start(
        self,
        seq: int,
        active: int,
        interleaved_rows: int,
        trace_ids: list,
        shed: int,
        replayed: int,
        timed_out: int,
        kv_pages_in_use: int = 0,
        admit_ms: float = 0.0,
        memory: Optional[dict] = None,
        steps: int = 0,
    ) -> Optional[TickRecord]:
        """Record a tick at dispatch; returns the record so the caller
        can carry it alongside the in-flight device call and complete
        it at collect (tick_done). `admit_ms` seeds the record's admit
        phase (executor admission time since the previous dispatch);
        the remaining phases come from the record's PhaseTimer, whose
        t0 doubles as t_mono so the phase sum closes on duration_ms."""
        if not self.enabled:
            return None
        timer = PhaseTimer()
        rec = TickRecord(
            seq=seq,
            t_wall=time.time(),
            t_mono=timer.t0,
            phases=timer,
            phase_admit_ms=admit_ms,
            active_slots=active,
            admitted=self._admitted_since_tick,
            interleaved_rows=interleaved_rows,
            shed_total=shed,
            replayed_total=replayed,
            timed_out_total=timed_out,
            trace_ids=trace_ids,
            source=self.source,
            kv_pages_in_use=kv_pages_in_use,
            memory=memory or {},
            steps=steps,
        )
        self._admitted_since_tick = 0
        self._ticks.append(rec)
        return rec

    def tick_done(
        self,
        rec: Optional[TickRecord],
        finished: int,
        jump_tokens: int = 0,
        jump_runs: int = 0,
    ) -> None:
        """Complete a tick at its token collect: stamp the tick's
        duration (admit seed + the contiguous admit-to-host span;
        includes the deliberate one-tick lag under pipelining), settle
        the phase attribution (the final `host` mark covers emission
        and finish bookkeeping — the caller marked sync/dispatch/wait),
        how many requests finished on it, and — on jump ticks — the
        round's forced-run counts (the per-tick jump trace)."""
        if rec is None:
            return
        if rec.phases is not None:
            rec.phases.mark("host")
            acc = rec.phases.acc
            rec.phase_sync_ms = acc.get("sync", 0.0)
            rec.phase_dispatch_ms = acc.get("dispatch", 0.0)
            rec.phase_wait_ms = acc.get("wait", 0.0)
            rec.phase_host_ms = acc.get("host", 0.0)
            rec.marks = [
                (phase, (start - rec.t_mono) * 1000.0)
                for phase, start in rec.phases.marks
            ]
            # t_mono == the timer's t0, so this equals the phase sum
            # exactly (the closure contract the acceptance test pins).
            rec.duration_ms = rec.phase_admit_ms + (
                rec.phases.last - rec.t_mono
            ) * 1000.0
        else:
            rec.duration_ms = (time.perf_counter() - rec.t_mono) * 1000.0
        rec.finished = finished
        rec.jump_tokens = jump_tokens
        rec.jump_runs = jump_runs
        with self._lock:
            self._hists["tick_duration_ms"].observe(rec.duration_ms)
            for phase in PHASE_NAMES:
                self._hists[f"tick_phase_{phase}_ms"].observe(
                    getattr(rec, f"phase_{phase}_ms")
                )

    def record_request(
        self,
        trace_id: str,
        t_submit: float,  # perf_counter stamp from _Request.t_submit
        t_admit: float,
        t_first: float,
        prompt_tokens: int,
        tokens: int,
        finish_reason: str,
        first_tick: int,
        last_tick: int,
        constrained: bool = False,
        tenant: str = "",
        qos_class: str = "",
        slo_violated: bool = False,
        t_pop: float = 0.0,
    ) -> None:
        """Record a request's terminal chunk; derives ttft/queue/e2e
        (and queue's two halves from `t_pop`, the stamp of the pop that
        put the request into its admission batch) and feeds the
        histograms. Stamps that never happened (a timeout that was
        never admitted) stay 0 in the record and are skipped by their
        histograms — a queue-death must not pollute the TTFT
        distribution with zeros."""
        if not self.enabled:
            return
        now = time.perf_counter()
        # Clamped at 0: a tick-failure replay resets t_submit (the
        # queue-deadline clock) while t_first keeps its original stamp,
        # so the splits can otherwise go negative for replayed requests.
        queue_ms = max(0.0, (t_admit - t_submit) * 1000.0) if t_admit else 0.0
        # pending is clamped into [0, queue_ms] (the same replay case,
        # and a path that stamps no pop) and prefill is the rest, so
        # the two always add up to queue_ms.
        pending_ms = min(queue_ms, max(0.0, (t_pop - t_submit) * 1000.0))
        prefill_ms = queue_ms - pending_ms
        ttft_ms = max(0.0, (t_first - t_submit) * 1000.0) if t_first else 0.0
        e2e_ms = max(0.0, (now - t_submit) * 1000.0)
        decode_s = (now - t_first) if t_first else 0.0
        rec = RequestRecord(
            trace_id=trace_id,
            t_submit=time.time() - e2e_ms / 1000.0,
            queue_ms=queue_ms,
            pending_ms=pending_ms,
            prefill_ms=prefill_ms,
            ttft_ms=ttft_ms,
            e2e_ms=e2e_ms,
            prompt_tokens=prompt_tokens,
            tokens=tokens,
            finish_reason=finish_reason,
            decode_tps=(tokens / decode_s) if decode_s > 1e-9 else 0.0,
            first_tick=first_tick,
            last_tick=last_tick,
            source=self.source,
            constrained=constrained,
            tenant=tenant,
            qos_class=qos_class,
            slo_violated=slo_violated,
        )
        self._requests.append(rec)
        with self._lock:
            if t_first:
                self._hists["ttft_ms"].observe(ttft_ms)
            if t_admit:
                self._hists["queue_ms"].observe(queue_ms)
                self._hists["pending_ms"].observe(pending_ms)
                self._hists["prefill_ms"].observe(prefill_ms)
            self._hists["e2e_ms"].observe(e2e_ms)
            if t_first and tokens > 1:
                # TPOT: mean inter-token gap over the decode span,
                # derived from the stamps already taken — one
                # observation per multi-token request (a single-token
                # request has no gaps and is skipped, exactly like a
                # never-admitted timeout skips TTFT).
                self._hists["tpot_ms"].observe(
                    decode_s * 1000.0 / (tokens - 1)
                )

    def note_admission(
        self,
        timer: PhaseTimer,
        family: str,
        batch_trace_ids: list,
        rows: int,
        prompt_tokens: int,
        reused_tokens: int,
        tick_seq: int,
        seq: int,
        deferred: bool = False,
    ) -> None:
        """Record one admission round from its PhaseTimer (t0 = the
        round's start, last = its settle's end; the wall stamp is
        paired here, at the end), and observe its split: admit_host_ms
        once, the round's host segments, and admit_device_ms once per
        `device` segment, which is once per admission program call:
        the program's OWN time on the device, from the later of its
        launch's return and the wait before it ending (the tick in
        flight, or the program before it, leaving the device) to its
        first tokens being ready. Where the host only waited that is
        the `device` segment itself; where it worked on while the
        program ran (a seat, the next tick's dispatch) the segment is
        what was left of the program's time when the host came to wait
        for it."""
        if not self.enabled:
            return
        device, launched, free_at = [], [], timer.t0
        for phase, start, end in timer.segments():
            if phase == "launch":
                launched.append(end)
            elif phase == "device":
                began = launched[len(device)] if (
                    len(device) < len(launched)) else start
                device.append((end - max(began, free_at)) * 1000.0)
            if phase in ("tick_wait", "device"):
                free_at = end
        host_ms = sum(timer.acc.get(p, 0.0) for p in ADMIT_HOST_MARKS)
        with self._lock:
            self._hists["admit_host_ms"].observe(host_ms)
            for ms in device:
                self._hists["admit_device_ms"].observe(ms)
        self._admissions.append(AdmissionRecord(
            seq=seq,
            t_wall=time.time() - (time.perf_counter() - timer.t0),
            t_mono=timer.t0,
            duration_ms=(timer.last - timer.t0) * 1000.0,
            family=family,
            rows=rows,
            prompt_tokens=prompt_tokens,
            reused_tokens=reused_tokens,
            trace_ids=batch_trace_ids,
            tick_seq=tick_seq,
            source=self.source,
            host_ms=host_ms,
            tick_wait_ms=timer.acc.get("tick_wait", 0.0),
            device_ms=timer.acc.get("device", 0.0),
            programs=len(device),
            dispatch_ms=timer.acc.get("dispatch", 0.0),
            deferred=deferred,
        ))

    def note_handoff(
        self,
        seq: int,
        kind: str,
        host_ms: float,
        t_submitted: float,
        t_started: float,
        t_ended: float,
        t_resumed: float,
        tick_seq: int,
    ) -> None:
        """Record one executor call's four stamps (perf_counter
        seconds) as a hand-off record; the wall stamp of the submission
        is paired here, on resume."""
        if not self.enabled:
            return
        self._handoffs.append(HandoffRecord(
            seq=seq,
            kind=kind,
            t_wall=time.time() - (time.perf_counter() - t_submitted),
            t_mono=t_submitted,
            host_ms=host_ms,
            exec_wait_ms=(t_started - t_submitted) * 1000.0,
            work_ms=(t_ended - t_started) * 1000.0,
            lag_ms=(t_resumed - t_ended) * 1000.0,
            tick_seq=tick_seq,
            source=self.source,
        ))

    # -- snapshots ----------------------------------------------------------

    def tick_snapshot(self) -> list:
        return list(self._ticks)

    def admission_snapshot(self) -> list:
        return list(self._admissions)

    def handoff_snapshot(self) -> list:
        return list(self._handoffs)

    def request_snapshot(self) -> list:
        return list(self._requests)

    def request_record(self, trace_id: str) -> Optional[RequestRecord]:
        """Latest record for a trace id (the span-attribution lookup),
        newest first."""
        if not trace_id:
            return None
        for rec in reversed(self._requests):
            if rec.trace_id == trace_id:
                return rec
        return None

    def histogram_stats(self) -> dict:
        """The ServingStats histogram fields (proto 33-45, the
        per-phase triplets 67-81 and the later ones HISTOGRAM_NAMES
        lists), keyed by exact proto field name so
        ServingStatsResponse(**stats) drift fails loudly."""
        out = {"latency_bucket_bounds_ms": list(self._bounds)}
        with self._lock:
            for name, hist in self._hists.items():
                out[f"{name}_bucket"] = list(hist.counts)
                out[f"{name}_sum"] = hist.sum
                out[f"{name}_count"] = hist.total
        return out

    @staticmethod
    def merge_histogram_stats(parts: list) -> dict:
        """Elementwise merge of histogram_stats() dicts (the tiered
        facade and the sidecar's batcher+spec merge): bucket counts and
        sums add; the shared bounds pass through (every recorder in one
        process is built from the same ObservabilityConfig)."""
        parts = [p for p in parts if p]
        if not parts:
            return {}
        out = {"latency_bucket_bounds_ms": parts[0]["latency_bucket_bounds_ms"]}
        for name in HISTOGRAM_NAMES:
            key = f"{name}_bucket"
            counts = [0] * len(parts[0][key])
            for p in parts:
                for i, c in enumerate(p[key]):
                    counts[i] += c
            out[key] = counts
            out[f"{name}_sum"] = sum(p[f"{name}_sum"] for p in parts)
            out[f"{name}_count"] = sum(p[f"{name}_count"] for p in parts)
        return out
