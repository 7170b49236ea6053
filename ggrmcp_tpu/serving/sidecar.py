"""The TPU serving sidecar: a gRPC server exposing JAX model engines.

The model plane's front door (SURVEY.md §7 stage 4, BASELINE.json north
star): EmbedService / GenerateService / ModelInfoService plus standard
reflection and health — so the gateway discovers a TPU model exactly
like any gRPC backend, while the implementations dispatch into jitted,
mesh-sharded engines. Server-streaming GenerateStream feeds the
gateway's MCP streaming path.
"""

from __future__ import annotations

import asyncio
import logging
import os
import re
import tempfile
import time
from typing import Optional

import grpc
import grpc.aio
import numpy as np

from ggrmcp_tpu.core.config import SERVING_ROLES, Config, ServingConfig
from ggrmcp_tpu.grammar import (
    CompiledGrammar,
    GrammarCache,
    GrammarCapacityError,
    GrammarError,
)
from ggrmcp_tpu.models import get_model
from ggrmcp_tpu.ops.sampling import SamplingConfig
from ggrmcp_tpu.rpc.pb import serving_pb2
from ggrmcp_tpu.rpc.server_utils import (
    HealthService,
    MethodDef,
    ReflectionService,
    add_service,
)
from ggrmcp_tpu.serving import tensors
from ggrmcp_tpu.serving.batching import (
    ContinuousBatcher,
    KVTransferError,
    OverloadedError,
)
from ggrmcp_tpu.serving.pages import PageExhaustedError
from ggrmcp_tpu.serving.scheduler import retry_after_for
from ggrmcp_tpu.serving.engine import EmbeddingEngine, GenerationEngine
from ggrmcp_tpu.serving.tokenizer import ByteTokenizer, load_tokenizer
from ggrmcp_tpu.utils import failpoints, tracing

logger = logging.getLogger("ggrmcp.serving.sidecar")

class Sidecar:
    """Owns the engines and the grpc.aio server."""

    def __init__(self, serving: Optional[ServingConfig] = None, mesh=None):
        self.serving = serving or ServingConfig()
        self.tokenizer = load_tokenizer(self.serving.tokenizer_path)
        self.generation: Optional[GenerationEngine] = None
        self.embedding: Optional[EmbeddingEngine] = None
        self.batcher: Optional[ContinuousBatcher] = None
        params = None
        # The mesh is built HERE, before any weight load, so checkpoint
        # restores can place each parameter shard directly onto its
        # devices (docs/tensor_parallel_serving.md) — never the
        # load-on-host-then-shard round trip that costs a full model of
        # host RAM (llama3-8b bf16 = 16 GB).
        from ggrmcp_tpu.parallel import mesh as mesh_mod

        if mesh is None:
            mesh = mesh_mod.build_mesh(self.serving.mesh)
        hf_path = self.serving.hf_checkpoint_path
        if hf_path and not os.path.isdir(hf_path) and (
            self.serving.hf_checkpoint_optional
        ):
            # Flagship fallback (ROADMAP item 1): weights unobtainable
            # in this environment — serve serving.model (llama3-8b in
            # the ladder config) with random init instead of dying.
            # Loud, and only under the explicit opt-in flag: a
            # production config pointing at absent weights still fails.
            logger.warning(
                "hf checkpoint %s unobtainable; falling back to "
                "random-init %s (hf_checkpoint_optional=true — outputs "
                "are meaningless, geometry/tokenizer are real)",
                hf_path, self.serving.model,
            )
            hf_path = ""
        if hf_path:
            # Real upstream weights: architecture AND params come from
            # the HF checkpoint, each shard device_put straight to its
            # NamedSharding (serving/weights.py).
            from ggrmcp_tpu.serving.weights import load_hf_checkpoint_sharded

            family = "llama"
            model_cfg, params = load_hf_checkpoint_sharded(hf_path, mesh)
        else:
            family, model_cfg = get_model(self.serving.model)
            if self.serving.checkpoint_path:
                params = self._restore_params(model_cfg, family, mesh)
        self.family = family
        if family != "bert":  # every decoder family
            self.generation = GenerationEngine(
                model_cfg, self.serving, mesh=mesh, params=params
            )
            if self.serving.batching.kv_tiers:
                from ggrmcp_tpu.serving.tiered import TieredBatcher

                self.batcher = TieredBatcher(
                    self.generation, self.serving.batching,
                    eos_id=self.tokenizer.eos_id,
                )
            else:
                self.batcher = ContinuousBatcher(
                    self.generation, self.serving.batching,
                    eos_id=self.tokenizer.eos_id,
                )
        else:
            self.embedding = EmbeddingEngine(
                model_cfg, self.serving, mesh=mesh, params=params
            )
        self.server: Optional[grpc.aio.Server] = None
        self.health = HealthService()
        self.port = 0
        self.target = ""  # dialable target string, set by start()
        self._profile_lock = asyncio.Lock()
        # Disaggregated serving (serving.role, docs/routing.md): the
        # declared role rides ServingStats so the gateway's role-aware
        # router can place on it; the kv_transfer_* counters track the
        # sidecar→sidecar page-shipping plane. Mirrors config.validate
        # for sidecars built directly in tests: a non-mixed role
        # without a paged, non-tiered generate batcher can neither
        # export nor import pages — fail at build, not mid-transfer.
        role = getattr(self.serving, "role", "mixed")
        if role not in SERVING_ROLES:
            raise ValueError(
                f"unknown serving.role {role!r}; supported: "
                f"{', '.join(SERVING_ROLES)}"
            )
        if role != "mixed" and (
            not isinstance(self.batcher, ContinuousBatcher)
            or self.serving.batching.paged_kv != "on"
        ):
            raise ValueError(
                f"serving.role={role!r} requires batching.paged_kv=on "
                "and no kv_tiers: KV pages are the transfer format "
                "and page import needs one arena (docs/paged_kv.md)"
            )
        # Unary Generate's own time, [sum of ms, calls that returned a
        # result] (ServingStats rpc_generate_ms_sum/_count).
        self._rpc_generate_ms = [0.0, 0]
        self._transfer_stats = dict.fromkeys(
            (
                "kv_transfers_sent", "kv_transfers_received",
                "kv_transfer_failures", "kv_transfer_pages_sent",
                "kv_transfer_pages_received", "kv_transfer_bytes_sent",
                "kv_transfer_bytes_received",
            ),
            0,
        )
        # Peer sidecar channels for outbound TransferKV, keyed by
        # dialable target — long-lived like the gateway's backend
        # channels (a transfer per long prompt must not pay a dial).
        self._peer_channels: dict[str, grpc.aio.Channel] = {}
        # Schema-constrained decoding (ggrmcp_tpu/grammar): LRU of
        # compiled DFAs keyed by canonical schema hash — a tool whose
        # output schema rides every call compiles once (the compiles/
        # hits counters export through ServingStats).
        self.grammar_cache = GrammarCache(
            self.serving.grammar.cache_entries
        )

    def _restore_params(self, model_cfg, family: str, mesh):
        """Orbax restore placed directly onto the mesh (each leaf's
        target carries its NamedSharding) when the layout is the plain
        family one; pipeline-parallel serving keeps the host restore —
        the engine re-places onto its staged specs either way."""
        from functools import partial

        import jax

        from ggrmcp_tpu.parallel import mesh as mesh_mod
        from ggrmcp_tpu.serving.checkpoint import restore, restore_sharded

        path = self.serving.checkpoint_path
        if mesh_mod.axis_size(mesh, "stage") > 1:
            params = restore(path)
            logger.info("restored params from %s (host-side; PP mesh)", path)
            return params
        if family != "bert":  # every decoder family
            from ggrmcp_tpu.models import family_module

            fam = family_module(model_cfg)
        else:
            from ggrmcp_tpu.models import bert as fam
        abstract = jax.eval_shape(
            partial(fam.init_params, cfg=model_cfg), jax.random.PRNGKey(0)
        )
        params = restore_sharded(
            path, abstract, fam.param_specs(model_cfg), mesh
        )
        logger.info(
            "restored params from %s sharded onto %s",
            path, mesh_mod.mesh_shape_str(mesh),
        )
        return params

    # ------------------------------------------------------------------
    # EmbedService
    # ------------------------------------------------------------------

    async def embed(self, request: serving_pb2.EmbedRequest, context):
        # Registration is family-scoped (start()), so the engine exists.
        assert self.embedding is not None
        t0 = time.perf_counter()
        has_token_ids = (
            request.token_ids.shape
            or request.token_ids.int_values
            or request.token_ids.data
        )
        if has_token_ids:
            ids = tensors.from_proto(request.token_ids).astype(np.int32)
            token_lists = [
                _strip_trailing_pads(row) for row in np.atleast_2d(ids)
            ]
        elif request.texts:
            token_lists = [self.tokenizer.encode(t) for t in request.texts]
        else:
            await context.abort(
                grpc.StatusCode.INVALID_ARGUMENT, "texts or token_ids required"
            )
        token_lists = [t or [self.tokenizer.pad_id] for t in token_lists]
        pooling = request.pooling or "mean"
        if pooling not in ("mean", "cls", "max"):
            await context.abort(
                grpc.StatusCode.INVALID_ARGUMENT,
                f"unknown pooling {pooling!r}",
            )
        loop = asyncio.get_running_loop()
        with tracing.tracer.span(
            "sidecar.embed",
            trace_id=tracing.trace_id_from_metadata(
                context.invocation_metadata()
            ) or None,
            model=self.embedding.cfg.name, batch=len(token_lists),
        ):
            vectors = await loop.run_in_executor(
                None,
                lambda: self.embedding.embed(
                    token_lists, pooling, request.max_length
                ),
            )
        return serving_pb2.EmbedResponse(
            embeddings=tensors.to_proto(vectors),
            model_id=self.embedding.cfg.name,
            compute_ms=(time.perf_counter() - t0) * 1000,
        )

    # ------------------------------------------------------------------
    # GenerateService
    # ------------------------------------------------------------------

    def _prompt_ids(self, request: serving_pb2.GenerateRequest) -> list[int]:
        if request.prompt_ids.shape or request.prompt_ids.int_values:
            return (
                tensors.from_proto(request.prompt_ids)
                .astype(np.int32).reshape(-1).tolist()
            )
        if request.prompt:
            return [self.tokenizer.bos_id] + self.tokenizer.encode(request.prompt)
        return [self.tokenizer.bos_id]

    def _sampling(self, request: serving_pb2.GenerateRequest) -> SamplingConfig:
        s = request.sampling
        return SamplingConfig(
            temperature=s.temperature,
            top_k=s.top_k,
            top_p=s.top_p if 0.0 < s.top_p < 1.0 else 1.0,
        )

    def _retry_after(self, qos_class: str) -> float:
        """The per-QoS-class Retry-After (serving/scheduler.py ladder):
        encoded into RESOURCE_EXHAUSTED details as "retry in Ns" so the
        gateway's 429 carries a class-appropriate backoff — background
        sheds wait geometrically longer than interactive ones, and the
        retry storm cooperates with the scheduler's priority order.
        Falls back to the flat 1 s contract when the batcher carries no
        scheduler config (tiered facade, bare test rigs)."""
        return retry_after_for(
            getattr(self.batcher, "sched_cfg", None), qos_class
        )

    async def _resolve_adapter(self, request, context):
        """GenerateRequest.adapter name → (served LoRA row id, arena
        lease or None). Static (boot-time) mode resolves against the
        engine's fixed name table; the dynamic arena
        (serving.lora.registry) acquires residency through the
        batcher's serialized host-op stream — a first sighting loads
        the factors H2D between ticks, and the returned lease pins the
        row until the request's terminal chunk. Every failure is
        typed: unknown names are the CALLER's error
        (INVALID_ARGUMENT); an all-pinned arena is overload
        (RESOURCE_EXHAUSTED, the PR-2 ladder → HTTP 429); a load
        failure — corrupt file, injected adapter_load_fail chaos,
        device write error — ABORTS loudly so the request sheds or
        retries on a replica holding the adapter, never silently
        serving base weights."""
        from ggrmcp_tpu.serving.adapter_arena import (
            AdapterExhaustedError,
            AdapterLoadError,
            UnknownAdapterError,
        )

        name = request.adapter
        if getattr(self.generation, "adapter_arena", None) is None:
            try:
                return self.generation.resolve_adapter(name), None
            except ValueError as exc:
                await context.abort(
                    grpc.StatusCode.INVALID_ARGUMENT, str(exc)
                )
        if not name:
            return 0, None
        try:
            lease = await self.batcher.acquire_adapter(name)
        except UnknownAdapterError as exc:
            await context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(exc))
        except AdapterExhaustedError as exc:
            await context.abort(
                grpc.StatusCode.RESOURCE_EXHAUSTED,
                f"server overloaded (adapters): {exc}; "
                f"retry in {self._retry_after(''):g}s",
            )
        except AdapterLoadError as exc:
            await context.abort(grpc.StatusCode.ABORTED, str(exc))
        return lease.row, lease

    def _release_adapter(self, lease) -> None:
        """Return a lease whose request never reached the batcher
        (submit-time shed, validation abort). Idempotent host
        bookkeeping; a submitted request's lease is released by
        _record_terminal instead."""
        if lease is not None:
            self.batcher.release_adapter(lease)

    async def _resolve_grammar(
        self, request: serving_pb2.GenerateRequest, context
    ) -> Optional[CompiledGrammar]:
        """GenerateRequest.constraint → compiled DFA (LRU-cached by
        schema hash). Bad schemas are the CALLER's error — unsupported
        dialect, over-budget DFAs, and unresolved tool refs all abort
        INVALID_ARGUMENT; nothing here can 500."""
        spec = request.constraint
        if not (spec.json_schema or spec.tool_output_schema_ref):
            return None
        if not self.serving.grammar.enabled:
            await context.abort(
                grpc.StatusCode.INVALID_ARGUMENT,
                "constrained decoding is disabled (serving.grammar.enabled)",
            )
        if not spec.json_schema:
            # The sidecar has no tool registry; the gateway resolves
            # tool_output_schema_ref into an inline schema before the
            # backend call (gateway.structured_output).
            await context.abort(
                grpc.StatusCode.INVALID_ARGUMENT,
                "constraint.tool_output_schema_ref must be resolved to "
                "an inline json_schema by the gateway",
            )
        if not isinstance(self.tokenizer, ByteTokenizer):
            await context.abort(
                grpc.StatusCode.INVALID_ARGUMENT,
                "constrained decoding requires the byte-level tokenizer "
                "(subword DFA alignment is not implemented)",
            )
        try:
            return self.grammar_cache.get(
                spec.json_schema,
                vocab_size=self.generation.cfg.vocab_size,
                eos_id=self.tokenizer.eos_id,
                max_states=self.serving.grammar.max_states,
                byte_offset=ByteTokenizer.OFFSET,
            )
        except GrammarError as exc:
            await context.abort(
                grpc.StatusCode.INVALID_ARGUMENT,
                f"constraint schema rejected: {exc}",
            )

    @staticmethod
    def _maybe_replica_crash() -> None:
        """Chaos hook (utils/failpoints.py `replica_crash`): a due
        evaluation aborts the WHOLE worker process — `every=N` is
        "this replica dies after N calls", the process-level fault the
        fleet supervisor's heal path must notice and restart
        (serving/fleet.py; tests/test_fleet.py arms it through the
        spawned worker's GGRMCP_FAILPOINTS env). os._exit, not
        sys.exit: a crash must not unwind politely through grpc's
        handlers — that politeness is exactly what a real SIGKILL
        doesn't grant."""
        try:
            failpoints.evaluate("replica_crash")
        except failpoints.FailpointError as exc:
            logger.critical("replica_crash failpoint fired: %s", exc)
            os._exit(86)

    def _tenant_identity(
        self, request: serving_pb2.GenerateRequest, context
    ) -> tuple[str, str]:
        """Tenant & SLO identity for this call (serving.slo,
        serving/slo.py). Explicit GenerateRequest fields win — the
        gateway threads x-tenant-id / x-qos-class into them — otherwise
        derive from the forwarded gRPC metadata with the documented
        fallback chain tenant ← x-adapter-id ← x-session-id ←
        "default", so direct gRPC callers (no gateway in front) are
        attributed too. qos_class passes through unvalidated: the
        batcher's SloAccount degrades unknown names to
        slo.default_class — measurement never rejects a request."""
        md: dict = {}
        for key, val in context.invocation_metadata() or ():
            if isinstance(val, str):
                md.setdefault(key.lower(), val)
        tenant = (
            request.tenant_id
            or md.get("x-tenant-id")
            or request.adapter
            or md.get("x-adapter-id")
            or md.get("x-session-id")
            or "default"
        )
        qos = request.qos_class or md.get("x-qos-class") or ""
        return str(tenant), str(qos)

    async def generate(self, request: serving_pb2.GenerateRequest, context):
        assert self.generation is not None and self.batcher is not None
        self._maybe_replica_crash()
        t0 = time.perf_counter()
        trace_id = tracing.trace_id_from_metadata(
            context.invocation_metadata()
        )
        prompt = self._prompt_ids(request)
        if request.kv_transfer_target:
            # Disaggregated prefill leg: prefill only, ship the pages,
            # return "transferred" — the gateway re-issues the request
            # to the peer, whose admission skips prefill entirely.
            shipped = await self._prefill_and_ship(
                request, context, prompt, trace_id, t0
            )
            self._generate_returned(t0)
            return shipped
        max_new = request.max_new_tokens or 64
        max_new = min(max_new, self.serving.batching.max_decode_steps)
        seed = request.sampling.seed or 0
        token_ids: list[int] = []
        finish = "length"
        sampling = self._sampling(request)
        # Grammar first: its aborts are lease-free; the adapter
        # resolution may pin an arena row that must then be released
        # on every failure path short of a successful submit.
        grammar = await self._resolve_grammar(request, context)
        adapter, lease = await self._resolve_adapter(request, context)
        with tracing.tracer.span(
            "sidecar.generate",
            trace_id=trace_id or None,
            model=self.generation.cfg.name, prompt_tokens=len(prompt),
        ) as span:
            # unary: one terminal chunk — skips per-tick cross-thread
            # emission (batching.py _Request.unary).
            try:
                tenant, qos_class = self._tenant_identity(request, context)
                it = self.batcher.submit(
                    prompt, max_new, sampling, seed, unary=True,
                    adapter=adapter, trace_id=trace_id, grammar=grammar,
                    adapter_key=request.adapter, adapter_lease=lease,
                    tenant=tenant, qos_class=qos_class,
                )
            except OverloadedError as exc:
                # Load shedding, not failure: RESOURCE_EXHAUSTED is
                # the retryable-overload status (the gateway maps
                # it to HTTP 429 + Retry-After). The shed request
                # never reached the batcher — return its arena pin.
                self._release_adapter(lease)
                await context.abort(
                    grpc.StatusCode.RESOURCE_EXHAUSTED,
                    f"server overloaded ({exc.reason}): {exc}; "
                    f"retry in {exc.retry_after_s:g}s",
                )
            except GrammarCapacityError as exc:
                # Too many DISTINCT schemas decoding at once —
                # transient, retryable: same overload contract.
                self._release_adapter(lease)
                await context.abort(
                    grpc.StatusCode.RESOURCE_EXHAUSTED, str(exc)
                )
            async for chunk_ids, reason in it:
                token_ids.extend(chunk_ids)
                if reason:
                    finish = reason
            span.set(completion_tokens=len(token_ids), finish=finish)
            self._attribute_span(span, trace_id)
        if finish == "overloaded":
            # Paged-KV page-pool exhaustion discovered at admission
            # (after submit already queued the request): same typed
            # overload ladder as a submit-time shed — RESOURCE_EXHAUSTED
            # here, HTTP 429 + Retry-After at the gateway.
            await context.abort(
                grpc.StatusCode.RESOURCE_EXHAUSTED,
                f"server overloaded (pages): kv page pool exhausted; "
                f"retry in {self._retry_after(qos_class):g}s",
            )
        if finish == "error":
            await context.abort(
                grpc.StatusCode.INTERNAL, "generation failed on the backend"
            )
        text = self.tokenizer.decode(token_ids)
        text, finish = _apply_stops(text, list(request.stop), finish)
        return serving_pb2.GenerateResponse(
            text=text,
            token_ids=token_ids if request.return_tokens else [],
            finish_reason=finish,
            prompt_tokens=len(prompt),
            completion_tokens=len(token_ids),
            model_id=self.generation.cfg.name,
            compute_ms=self._generate_returned(t0),
        )

    def _generate_returned(self, t0: float) -> float:
        """A unary Generate is about to return a result: its time in
        this handler (ms since `t0`, the stamp taken at entry) joins
        rpc_generate_ms_sum/_count. With the batcher's e2e_ms this is
        the sidecar's own part of what the gateway adds to a call."""
        ms = (time.perf_counter() - t0) * 1000
        self._rpc_generate_ms[0] += ms
        self._rpc_generate_ms[1] += 1
        return ms

    async def generate_stream(self, request: serving_pb2.GenerateRequest, context):
        assert self.generation is not None and self.batcher is not None
        self._maybe_replica_crash()
        trace_id = tracing.trace_id_from_metadata(
            context.invocation_metadata()
        )
        prompt = self._prompt_ids(request)
        if request.kv_transfer_target:
            # Same disaggregated prefill leg as unary Generate; the
            # stream carries exactly one terminal "transferred" chunk.
            await self._prefill_and_ship(
                request, context, prompt, trace_id, time.perf_counter(),
            )
            yield serving_pb2.GenerateChunk(
                finish_reason="transferred", done=True
            )
            return
        max_new = min(
            request.max_new_tokens or 64, self.serving.batching.max_decode_steps
        )
        seed = request.sampling.seed or 0
        # Same ordering rationale as unary Generate: grammar aborts
        # are lease-free, the adapter resolution pins a row.
        grammar = await self._resolve_grammar(request, context)
        adapter, lease = await self._resolve_adapter(request, context)
        emitted = ""
        stops = list(request.stop)
        all_ids: list[int] = []
        # Incremental UTF-8 decode (serving/tokenizer.py): the decoder
        # buffers an incomplete trailing multi-byte sequence across
        # chunk boundaries, so text_delta never carries U+FFFD for text
        # that is merely split mid-rune. Tokenizers without one (HF
        # subword) keep the decode-everything + stable-prefix fallback.
        mk_decoder = getattr(self.tokenizer, "stream_decoder", None)
        decoder = mk_decoder() if mk_decoder is not None else None
        decoded = {"text": ""}

        def delta_for(final: bool) -> tuple[str, str]:
            """(delta, stop_hit): emit only the stable prefix while
            streaming (incomplete multi-byte UTF-8 is held back until
            the sequence completes); flush everything on the final
            chunk."""
            if decoder is not None:
                text = decoded["text"]
                if final:
                    text = decoded["text"] = text + decoder.flush()
                stopped_text, stop_hit = _apply_stops(text, stops, "")
                stable = stopped_text  # complete sequences only, by feed()
            else:
                text = self.tokenizer.decode(all_ids)
                stopped_text, stop_hit = _apply_stops(text, stops, "")
                stable = (
                    stopped_text if final else _stable_prefix(stopped_text)
                )
            if len(stable) < len(emitted):
                return "", stop_hit  # stop cut before emitted point
            return stable[len(emitted):], stop_hit

        try:
            tenant, qos_class = self._tenant_identity(request, context)
            it = self.batcher.submit(
                prompt, max_new, self._sampling(request), seed,
                adapter=adapter, trace_id=trace_id, grammar=grammar,
                adapter_key=request.adapter, adapter_lease=lease,
                tenant=tenant, qos_class=qos_class,
            )
        except OverloadedError as exc:
            # Shed before any chunk is written — same overload contract
            # as unary Generate.
            self._release_adapter(lease)
            await context.abort(
                grpc.StatusCode.RESOURCE_EXHAUSTED,
                f"server overloaded ({exc.reason}): {exc}; "
                        f"retry in {exc.retry_after_s:g}s",
            )
        except GrammarCapacityError as exc:
            self._release_adapter(lease)
            await context.abort(
                grpc.StatusCode.RESOURCE_EXHAUSTED, str(exc)
            )
        async for chunk_ids, reason in it:
            all_ids.extend(chunk_ids)
            if decoder is not None:
                decoded["text"] += decoder.feed(chunk_ids)
            final = reason is not None
            delta, stop_hit = delta_for(final)
            if delta:
                emitted += delta
                yield serving_pb2.GenerateChunk(
                    text_delta=delta,
                    token_ids=chunk_ids if request.return_tokens else [],
                )
            if stop_hit == "stop_string":
                yield serving_pb2.GenerateChunk(
                    finish_reason="stop_string", done=True
                )
                return
            if reason:
                if reason == "overloaded":
                    # Paged admission-time shed: typed overload, same
                    # ladder as a submit-time OverloadedError.
                    await context.abort(
                        grpc.StatusCode.RESOURCE_EXHAUSTED,
                        f"server overloaded (pages): kv page pool "
                        f"exhausted; retry in "
                        f"{self._retry_after(qos_class):g}s",
                    )
                if reason == "error":
                    # Same contract as unary Generate: a backend failure
                    # is an INTERNAL status, not a normal-looking stream.
                    await context.abort(
                        grpc.StatusCode.INTERNAL,
                        "generation failed on the backend",
                    )
                yield serving_pb2.GenerateChunk(finish_reason=reason, done=True)
                return
        yield serving_pb2.GenerateChunk(finish_reason="length", done=True)

    def _attribute_span(self, span, trace_id: str) -> None:
        """Stamp the flight-recorder lifecycle onto this call's span —
        ttft_ms plus the tick-seq range — so one trace id walks span →
        request record → tick records (/debug/traces → /debug/requests
        → /debug/ticks)."""
        if not trace_id:
            return
        rec = self.batcher.request_record(trace_id)
        if rec is None:
            return
        span.set(
            ttft_ms=round(rec.ttft_ms, 3),
            queue_ms=round(rec.queue_ms, 3),
            first_tick=rec.first_tick,
            last_tick=rec.last_tick,
        )

    # ------------------------------------------------------------------
    # KVTransferService — sidecar→sidecar page shipping (serving.role)
    # ------------------------------------------------------------------

    # Target payload bytes per TransferKV chunk: comfortably under the
    # default 4 MB gRPC message cap with proto overhead included, while
    # big enough that a 4k-token llama3-8b prompt ships in a handful of
    # calls. Long prompts stream as several in-order chunks, each
    # self-contained (prompt + start_page), so a failed transfer leaves
    # only a VALID shorter prefix behind — warmth, never corruption.
    TRANSFER_CHUNK_BYTES = 2 << 20

    def _transfer_call(self, target: str):
        """Cached unary stub for a peer sidecar's TransferKV."""
        channel = self._peer_channels.get(target)
        if channel is None:
            channel = grpc.aio.insecure_channel(target)
            self._peer_channels[target] = channel
        return channel.unary_unary(
            "/ggrmcp.tpu.KVTransferService/TransferKV",
            request_serializer=(
                serving_pb2.KVTransferRequest.SerializeToString
            ),
            response_deserializer=(
                serving_pb2.KVTransferResponse.FromString
            ),
        )

    async def _ship_kv(
        self, target: str, prompt: list[int], export: dict,
        adapter: str = "",
    ) -> tuple[int, int]:
        """Stream one exported prompt's pages to a peer sidecar as
        in-order TransferKV chunks. Returns (pages, wire bytes); any
        failure propagates to _prefill_and_ship's typed ABORTED."""
        n = export["pages"]
        arrays = [
            a for a in export.values() if isinstance(a, np.ndarray)
        ]
        per_page = max(1, sum(a.nbytes for a in arrays) // n)
        per_chunk = max(1, self.TRANSFER_CHUNK_BYTES // per_page)
        call = self._transfer_call(target)
        quantized = "k_scale" in export
        sent_bytes = 0
        for start in range(0, n, per_chunk):
            end = min(n, start + per_chunk)
            # The SHARED page-content codec (serving/tensors.py —
            # also the host tier's storage format): one pack, two
            # consumers, zero format drift.
            payload = tensors.kv_pages_to_payload(
                export["k"][:, start:end],
                export["v"][:, start:end],
                export["k_scale"][:, start:end] if quantized else None,
                export["v_scale"][:, start:end] if quantized else None,
            )
            chunk = serving_pb2.KVTransferRequest(
                prompt_ids=prompt,
                page_size=export["page_size"],
                start_page=start,
                total_pages=n,
                k_pages=payload.k,
                v_pages=payload.v,
                kv_dtype=self.serving.kv_cache_dtype,
                model_id=self.generation.cfg.name,
                done=end == n,
                adapter=adapter,
            )
            if quantized:
                chunk.k_scales.CopyFrom(payload.k_scales)
                chunk.v_scales.CopyFrom(payload.v_scales)
            sent_bytes += chunk.ByteSize()
            await call(chunk, timeout=30.0)
        return n, sent_bytes

    async def _prefill_and_ship(
        self, request, context, prompt: list[int], trace_id: str,
        t0: float,
    ):
        """The prefill-role leg of a disaggregated call: admit the
        prompt for ONE token (the admission prefill computes and
        indexes the prompt's page chain; the sampled token is
        discarded — the decode replica samples every output token
        itself, which is what keeps greedy outputs bit-identical to a
        one-replica run), export the chain, ship it to `target`.
        Every failure is TYPED — gRPC ABORTED with a "kv transfer
        failed" detail — so the gateway retries the whole request on a
        mixed replica; a transfer failure is never silently recomputed
        into a normal-looking success here."""
        target = request.kv_transfer_target
        # Clamp with the REQUEST's max_new (fit_request keeps the
        # tail): the exported chain must be the one the decode
        # replica's identically clamped admission will look up. The
        # constraint flag rides along for the same reason — a grammar
        # widens the decode replica's jump-window reserve, so a
        # constrained near-limit prompt clamps shorter there.
        max_new = min(
            request.max_new_tokens or 64,
            self.serving.batching.max_decode_steps,
        )
        spec = request.constraint
        constrained = bool(
            spec.json_schema or spec.tool_output_schema_ref
        )
        clamp = getattr(self.batcher, "clamp_prompt", None)
        if clamp is not None:
            prompt = clamp(prompt, max_new, constrained=constrained)
        try:
            # Chaos hook (utils/failpoints.py kv_transfer_fail): an
            # injected fault IS a failed transfer — same typed path.
            failpoints.evaluate("kv_transfer_fail")
        except failpoints.FailpointError as exc:
            self._transfer_stats["kv_transfer_failures"] += 1
            await context.abort(
                grpc.StatusCode.ABORTED,
                f"kv transfer failed (injected): {exc}",
            )
        # The prefill leg runs under the request's ADAPTER (its pages
        # are keyed in that adapter's chain domain since ISSUE 15 — a
        # base-model prefill would compute, and ship, the wrong KV).
        adapter, lease = await self._resolve_adapter(request, context)
        finish = "error"
        try:
            tenant, qos_class = self._tenant_identity(request, context)
            it = self.batcher.submit(
                prompt, 1, SamplingConfig(temperature=0.0), 0,
                unary=True, trace_id=trace_id, adapter=adapter,
                adapter_key=request.adapter, adapter_lease=lease,
                tenant=tenant, qos_class=qos_class,
            )
        except OverloadedError as exc:
            self._release_adapter(lease)
            await context.abort(
                grpc.StatusCode.RESOURCE_EXHAUSTED,
                f"server overloaded ({exc.reason}): {exc}; "
                        f"retry in {exc.retry_after_s:g}s",
            )
        async for _ids, reason in it:
            if reason:
                finish = reason
        if finish not in ("stop", "length", "grammar_complete"):
            self._transfer_stats["kv_transfer_failures"] += 1
            await context.abort(
                grpc.StatusCode.ABORTED,
                f"kv transfer failed: prefill finished {finish!r}",
            )
        try:
            export = await self.batcher.run_host_op(
                lambda: self.batcher.export_prompt_kv(
                    prompt, adapter=request.adapter
                )
            )
            pages, wire_bytes = await self._ship_kv(
                target, prompt, export, adapter=request.adapter
            )
        except asyncio.CancelledError:
            raise  # client disconnect must cancel, not "error"
        except Exception as exc:  # noqa: BLE001 — typed ABORTED below
            self._transfer_stats["kv_transfer_failures"] += 1
            logger.warning("kv transfer to %s failed: %s", target, exc)
            await context.abort(
                grpc.StatusCode.ABORTED, f"kv transfer failed: {exc}"
            )
        self._transfer_stats["kv_transfers_sent"] += 1
        self._transfer_stats["kv_transfer_pages_sent"] += pages
        self._transfer_stats["kv_transfer_bytes_sent"] += wire_bytes
        logger.info(
            "kv transfer: %d pages (%d bytes) of a %d-token prompt "
            "shipped to %s", pages, wire_bytes, len(prompt), target,
        )
        return serving_pb2.GenerateResponse(
            finish_reason="transferred",
            prompt_tokens=len(prompt),
            model_id=self.generation.cfg.name,
            compute_ms=(time.perf_counter() - t0) * 1000,
        )

    async def transfer_kv(
        self, request: serving_pb2.KVTransferRequest, context
    ):
        """Receive one KV-page chunk into this replica's arena. The
        import is refcount-safe by construction: pages land at
        refcount 0 in the prefix index (evictable, exactly like a
        finished local request's pages) and the device write runs in
        the batcher's serialized executor stream, so no tick or
        admission can observe a half-written page."""
        batcher = self.batcher
        if not isinstance(batcher, ContinuousBatcher) or not getattr(
            batcher, "_paged", False
        ):
            await context.abort(
                grpc.StatusCode.FAILED_PRECONDITION,
                "kv import requires a paged, non-tiered batcher "
                "(batching.paged_kv=on)",
            )
        if (request.kv_dtype or "") != (self.serving.kv_cache_dtype or ""):
            await context.abort(
                grpc.StatusCode.INVALID_ARGUMENT,
                f"kv dtype mismatch: sender {request.kv_dtype!r} vs "
                f"receiver {self.serving.kv_cache_dtype!r}",
            )
        if request.page_size != batcher._page_size:
            await context.abort(
                grpc.StatusCode.INVALID_ARGUMENT,
                f"page size mismatch: sender {request.page_size} vs "
                f"receiver {batcher._page_size}",
            )
        payload = serving_pb2.KVPagePayload(
            k=request.k_pages, v=request.v_pages
        )
        if request.HasField("k_scales"):
            payload.k_scales.CopyFrom(request.k_scales)
            payload.v_scales.CopyFrom(request.v_scales)
        k, v, k_scale, v_scale = tensors.kv_pages_from_payload(payload)
        prompt = list(request.prompt_ids)
        start = int(request.start_page)
        try:
            imported, present = await batcher.run_host_op(
                lambda: batcher.import_prompt_kv(
                    prompt, start, k, v, k_scale, v_scale,
                    adapter=request.adapter,
                )
            )
        except PageExhaustedError as exc:
            # The receiving arena is full even after eviction — the
            # same typed overload ladder as an admission shed.
            await context.abort(
                grpc.StatusCode.RESOURCE_EXHAUSTED, str(exc)
            )
        except (KVTransferError, ValueError) as exc:
            await context.abort(
                grpc.StatusCode.INVALID_ARGUMENT, str(exc)
            )
        self._transfer_stats["kv_transfer_pages_received"] += imported
        self._transfer_stats["kv_transfer_bytes_received"] += (
            request.ByteSize()
        )
        if request.done:
            self._transfer_stats["kv_transfers_received"] += 1
        return serving_pb2.KVTransferResponse(
            pages_imported=imported, pages_present=present
        )

    # ------------------------------------------------------------------
    # ModelInfoService
    # ------------------------------------------------------------------

    async def get_serving_stats(self, request, context):
        """Live batching/cache counters (serving_pb2.ServingStatsResponse);
        zeros for an embed-only sidecar (no batcher). The kwargs
        construction fails loudly if stats() keys drift from the proto."""
        stats = dict(self.batcher.stats()) if self.batcher is not None else {}
        # Disaggregated-serving identity + transfer-plane counters: the
        # role string rides info-style (like mesh_shape) so the
        # gateway's role-aware router reads it from the same snapshot
        # it scores load from.
        stats["role"] = getattr(self.serving, "role", "mixed")
        stats.update(self._transfer_stats)
        stats["rpc_generate_ms_sum"], stats["rpc_generate_ms_count"] = (
            self._rpc_generate_ms
        )
        # Compile watcher (serving/compile_watcher.py): process-level
        # XLA compile counters — count/wall/cache outcomes and the
        # steady-state post-warmup recompiles (fields 101-105,
        # gateway_backend_compile_*). Exported here, not per batcher:
        # jax's hooks are process-global, exactly like the watcher.
        from ggrmcp_tpu.ops.attention import dispatch_stats
        from ggrmcp_tpu.serving.compile_watcher import watcher

        stats.update(watcher.stats())
        # Which attention implementation the traced programs took —
        # trace-time and process-wide, like the compile counters.
        stats.update(dispatch_stats())
        if self.batcher is None and self.embedding is not None:
            # Embed-only sidecar: no batcher stats, but the weights
            # component is still real — exported from the embed
            # engine's ledger so /metrics never claims an empty HBM.
            mem = self.embedding.ledger.component_bytes()
            stats["memory_weights_bytes"] = mem.get(("", "weights"), 0)
        if self.batcher is not None:
            # Sidecar-owned grammar compile cache (the batcher/tiers
            # contribute grammar_masked_tokens / grammar_states_in_use).
            stats["grammar_compiles"] = self.grammar_cache.compiles
            stats["grammar_cache_hits"] = self.grammar_cache.hits
        return serving_pb2.ServingStatsResponse(**stats)

    async def get_model_info(self, request, context):
        engine = self.generation or self.embedding
        info = engine.model_info()
        return serving_pb2.ModelInfoResponse(
            model_id=info["model_id"],
            family=info["family"],
            num_params_million=info["num_params_million"],
            max_seq_len=info["max_seq_len"],
            dtype=info["dtype"],
            mesh=info["mesh"],
            num_devices=info["num_devices"],
            platform=info["platform"],
            device_kind=info["device_kind"],
        )

    # ------------------------------------------------------------------
    # DebugService — on-demand JAX profiler capture (SURVEY.md §5.1)
    # ------------------------------------------------------------------

    async def profile(self, request: serving_pb2.ProfileRequest, context):
        # The client names the dump, it does not place it: output_dir is
        # reduced to a [A-Za-z0-9._-] label under the server-side base
        # dir, so remote callers can never write outside it.
        duration_ms = (
            1000.0 if not request.duration_ms
            else float(min(max(request.duration_ms, 10), 60_000))
        )
        label = re.sub(r"[^A-Za-z0-9._-]", "_", os.path.basename(
            request.output_dir or ""
        ))
        if not label.strip("."):  # "", "." and ".." all escape the base dir
            label = f"capture-{int(time.time())}"
        out = os.path.join(
            tempfile.gettempdir(), "ggrmcp-profiles", label
        )
        if self._profile_lock.locked():
            await context.abort(
                grpc.StatusCode.FAILED_PRECONDITION,
                "a profile capture is already running",
            )
        async with self._profile_lock:
            os.makedirs(out, exist_ok=True)
            loop = asyncio.get_running_loop()
            try:
                path = await loop.run_in_executor(
                    None, lambda: tracing.profile_capture(duration_ms, out)
                )
            except asyncio.CancelledError:
                raise  # a cancelled RPC must not abort() a dead context
            except Exception as exc:
                logger.exception("profile capture failed")
                await context.abort(
                    grpc.StatusCode.INTERNAL, f"profile capture failed: {exc}"
                )
        logger.info("profiler capture (%.0f ms) written to %s", duration_ms, path)
        return serving_pb2.ProfileResponse(
            output_path=path, duration_ms=duration_ms
        )

    async def get_flight_record(
        self, request: serving_pb2.FlightRecordRequest, context
    ):
        """Flight-recorder rings: per-tick and per-request lifecycle
        records, optionally filtered to one trace id — the postmortem
        RPC behind the gateway's /debug/ticks and /debug/requests.
        Snapshot reads of host state; no device work, no locks held
        across the engine."""
        max_ticks = request.max_ticks or 128
        max_requests = request.max_requests or 128
        ticks: list = []
        requests: list = []
        admissions: list = []
        handoffs: list = []
        enabled = False
        if self.batcher is not None:
            enabled = any(
                t.recorder.enabled
                for t in getattr(self.batcher, "tiers", [self.batcher])
            )
            ticks, requests = self.batcher.flight_snapshot(
                max_ticks, max_requests, request.trace_id, request.tenant
            )
            admissions, handoffs = self.batcher.loop_snapshot(
                max_ticks, request.trace_id
            )
        from ggrmcp_tpu.serving.compile_watcher import watcher

        return serving_pb2.FlightRecordResponse(
            # Compile events ride the flight record so the unified
            # timeline can render each as an instant on the same axis
            # as the tick phases (process-global ring, newest last).
            compiles=[
                serving_pb2.CompileRecord(
                    fn_name=c.fn_name, t_wall=c.t_wall,
                    duration_ms=c.duration_ms, post_warmup=c.post_warmup,
                )
                for c in watcher.snapshot(max_ticks)
            ],
            ticks=[
                serving_pb2.TickRecord(
                    seq=t.seq, t_wall=t.t_wall, t_mono=t.t_mono,
                    duration_ms=t.duration_ms, active_slots=t.active_slots,
                    admitted=t.admitted, finished=t.finished,
                    interleaved_rows=t.interleaved_rows,
                    shed_total=t.shed_total, replayed_total=t.replayed_total,
                    timed_out_total=t.timed_out_total,
                    trace_ids=t.trace_ids, source=t.source,
                    kv_pages_in_use=t.kv_pages_in_use,
                    phase_admit_ms=t.phase_admit_ms,
                    phase_sync_ms=t.phase_sync_ms,
                    phase_dispatch_ms=t.phase_dispatch_ms,
                    phase_wait_ms=t.phase_wait_ms,
                    phase_host_ms=t.phase_host_ms,
                    phase_marks=[p for p, _ in t.marks],
                    phase_mark_start_ms=[ms for _, ms in t.marks],
                    jump_tokens=t.jump_tokens, jump_runs=t.jump_runs,
                    steps=t.steps,
                    memory_components=list(t.memory),
                    memory_component_bytes=[
                        int(b) for b in t.memory.values()
                    ],
                )
                for t in ticks
            ],
            requests=[
                serving_pb2.RequestRecord(
                    trace_id=r.trace_id, t_submit=r.t_submit,
                    queue_ms=r.queue_ms, pending_ms=r.pending_ms,
                    prefill_ms=r.prefill_ms,
                    ttft_ms=r.ttft_ms, e2e_ms=r.e2e_ms,
                    prompt_tokens=r.prompt_tokens, tokens=r.tokens,
                    finish_reason=r.finish_reason, decode_tps=r.decode_tps,
                    first_tick=r.first_tick, last_tick=r.last_tick,
                    source=r.source, constrained=r.constrained,
                    tenant=r.tenant, qos_class=r.qos_class,
                    slo_violated=r.slo_violated,
                )
                for r in requests
            ],
            admissions=[
                serving_pb2.AdmissionRecord(
                    seq=a.seq, t_wall=a.t_wall, t_mono=a.t_mono,
                    duration_ms=a.duration_ms, family=a.family,
                    rows=a.rows, prompt_tokens=a.prompt_tokens,
                    reused_tokens=a.reused_tokens,
                    trace_ids=a.trace_ids, tick_seq=a.tick_seq,
                    source=a.source, host_ms=a.host_ms,
                    tick_wait_ms=a.tick_wait_ms, device_ms=a.device_ms,
                    programs=a.programs, dispatch_ms=a.dispatch_ms, deferred=a.deferred,
                )
                for a in admissions
            ],
            handoffs=[
                serving_pb2.HandoffRecord(
                    seq=h.seq, kind=h.kind, t_wall=h.t_wall,
                    t_mono=h.t_mono, host_ms=h.host_ms,
                    exec_wait_ms=h.exec_wait_ms, work_ms=h.work_ms,
                    lag_ms=h.lag_ms, tick_seq=h.tick_seq,
                    source=h.source,
                )
                for h in handoffs
            ],
            enabled=enabled,
        )

    async def get_memory(
        self, request: serving_pb2.MemoryRequest, context
    ):
        """Device-memory ledger detail (serving/memory_ledger.py): the
        full per-(scope, component) accounting behind the ServingStats
        memory_* scalars, the closure reconciliation against JAX
        live-buffer totals, and the compile watcher's counters + ring —
        the gateway's GET /debug/memory body. Host-side walks only
        (array metadata, never contents); run in the executor so a
        large live-array census never blocks the event loop."""
        from ggrmcp_tpu.serving.compile_watcher import watcher

        engine = self.generation or self.embedding
        ledger = getattr(engine, "ledger", None)
        components: list = []
        total = 0
        live = unattr_bytes = unattr_arrays = 0
        if ledger is not None and ledger.enabled:
            loop = asyncio.get_running_loop()
            if request.reconcile:
                rec = await loop.run_in_executor(None, ledger.reconcile)
                live = rec["live_bytes"]
                unattr_bytes = rec["unattributed_bytes"]
                unattr_arrays = len(rec["unattributed_arrays"])
                per = {}
                for name, b in rec["components"].items():
                    scope, _, comp = name.rpartition("/")
                    per[(scope, comp)] = b
            else:
                per = await loop.run_in_executor(
                    None, ledger.component_bytes
                )
            for (scope, comp), b in sorted(per.items()):
                components.append(serving_pb2.MemoryComponent(
                    component=comp, scope=scope, bytes=int(b)
                ))
                total += int(b)
        # Host-tier components (ledger.register_host — the host-RAM
        # complement of the device closure above; exact by
        # construction, no reconcile pass): the GET /debug/memory
        # `host` section.
        host_components: list = []
        host_total = 0
        if ledger is not None and ledger.enabled:
            for (scope, comp), info in sorted(
                ledger.host_components().items()
            ):
                host_components.append(serving_pb2.HostMemoryComponent(
                    component=comp, scope=scope,
                    bytes=int(info.get("bytes", 0)),
                    entries=int(info.get("entries", 0)),
                    budget_bytes=int(info.get("budget_bytes", 0)),
                    file_path=str(info.get("file_path", "")),
                    file_bytes=int(info.get("file_bytes", 0)),
                    file_entries=int(info.get("file_entries", 0)),
                ))
                host_total += int(info.get("bytes", 0))
        cstats = watcher.stats()
        # The allocator's own per-chip figures, now and at its high-water
        # mark (None on backends that report no memory stats, e.g. CPU).
        device_stats = [
            ms for ms in (d.memory_stats() for d in engine.mesh.devices.flat)
            if ms is not None
        ]
        return serving_pb2.MemoryResponse(
            device_bytes_in_use=[
                int(ms["bytes_in_use"]) for ms in device_stats
            ],
            device_peak_bytes_in_use=[
                int(ms["peak_bytes_in_use"]) for ms in device_stats
                if "peak_bytes_in_use" in ms
            ],
            components=components,
            total_bytes=total,
            host=host_components,
            host_total_bytes=host_total,
            live_bytes=live,
            unattributed_bytes=unattr_bytes,
            unattributed_arrays=unattr_arrays,
            enabled=ledger is not None and ledger.enabled,
            compile_count=cstats["compile_count"],
            compile_ms=cstats["compile_ms"],
            compile_cache_hits=cstats["compile_cache_hits"],
            compile_cache_misses=cstats["compile_cache_misses"],
            compile_post_warmup=cstats["compile_post_warmup"],
            compiles=[
                serving_pb2.CompileRecord(
                    fn_name=c.fn_name, t_wall=c.t_wall,
                    duration_ms=c.duration_ms,
                    post_warmup=c.post_warmup,
                )
                for c in watcher.snapshot()
            ],
        )

    # ------------------------------------------------------------------
    # Server lifecycle
    # ------------------------------------------------------------------

    async def start(self, port: Optional[int] = None) -> int:
        self.server = grpc.aio.server()
        # Register only the services this model family actually serves —
        # a gateway pooling an embed sidecar and a generate sidecar must
        # not see colliding tool names (discovery is name-keyed).
        services = ["ggrmcp.tpu.ModelInfoService"]
        if self.embedding is not None:
            services.append("ggrmcp.tpu.EmbedService")
            add_service(
                self.server, "ggrmcp.tpu.EmbedService",
                {"Embed": MethodDef(
                    self.embed,
                    serving_pb2.EmbedRequest, serving_pb2.EmbedResponse,
                )},
            )
        if self.generation is not None:
            services.append("ggrmcp.tpu.GenerateService")
            add_service(
                self.server, "ggrmcp.tpu.GenerateService",
                {
                    "Generate": MethodDef(
                        self.generate,
                        serving_pb2.GenerateRequest,
                        serving_pb2.GenerateResponse,
                    ),
                    "GenerateStream": MethodDef(
                        self.generate_stream,
                        serving_pb2.GenerateRequest, serving_pb2.GenerateChunk,
                        server_streaming=True,
                    ),
                },
            )
            # Sidecar→sidecar KV-page transfer (serving.role): every
            # generate sidecar serves the receiving half — a mixed
            # replica must accept pages too, or a decode-role drain
            # would leave in-flight transfers nowhere to land.
            services.append("ggrmcp.tpu.KVTransferService")
            add_service(
                self.server, "ggrmcp.tpu.KVTransferService",
                {"TransferKV": MethodDef(
                    self.transfer_kv,
                    serving_pb2.KVTransferRequest,
                    serving_pb2.KVTransferResponse,
                )},
            )
        add_service(
            self.server, "ggrmcp.tpu.ModelInfoService",
            {
                "GetModelInfo": MethodDef(
                    self.get_model_info,
                    serving_pb2.ModelInfoRequest,
                    serving_pb2.ModelInfoResponse,
                ),
                "GetServingStats": MethodDef(
                    self.get_serving_stats,
                    serving_pb2.ServingStatsRequest,
                    serving_pb2.ServingStatsResponse,
                ),
            },
        )
        services.append("ggrmcp.tpu.DebugService")
        add_service(
            self.server, "ggrmcp.tpu.DebugService",
            {
                "Profile": MethodDef(
                    self.profile,
                    serving_pb2.ProfileRequest, serving_pb2.ProfileResponse,
                ),
                "GetFlightRecord": MethodDef(
                    self.get_flight_record,
                    serving_pb2.FlightRecordRequest,
                    serving_pb2.FlightRecordResponse,
                ),
                "GetMemory": MethodDef(
                    self.get_memory,
                    serving_pb2.MemoryRequest,
                    serving_pb2.MemoryResponse,
                ),
            },
        )
        ReflectionService(services).attach(self.server)
        self.health.attach(self.server)
        if self.serving.uds_path:
            # UDS listen (co-launch default): no TCP socket at all —
            # the gateway dials `self.target`. gRPC returns 1 for a
            # successful unix bind, so `port` stays 0 in this mode.
            if self.server.add_insecure_port(f"unix:{self.serving.uds_path}") == 0:
                raise OSError(
                    f"failed to bind unix:{self.serving.uds_path}"
                )
            self.port = 0
            self.target = f"unix:{self.serving.uds_path}"
        else:
            bind = port if port is not None else self.serving.port
            self.port = self.server.add_insecure_port(f"0.0.0.0:{bind}")
            self.target = f"localhost:{self.port}"
        if self.batcher is not None:
            # Compile decode/admission programs before accepting traffic
            # (device-bound → executor, not the event loop).
            await asyncio.get_running_loop().run_in_executor(
                None, self.batcher.warmup
            )
            self.batcher.start()
        # Warmup is over: from here every XLA compile is a steady-state
        # recompile — counted, WARNING-logged, and a timeline instant
        # (serving/compile_watcher.py; compile_post_warmup == 0 is the
        # serving-time contract `make test-mem` pins).
        from ggrmcp_tpu.serving.compile_watcher import watcher

        watcher.mark_warm()
        await self.server.start()
        engine = self.generation or self.embedding
        mesh_label = (
            self.generation.mesh_stats()["mesh_shape"]
            if self.generation is not None
            else (engine.cfg.name if engine else "?")
        )
        logger.info(
            "sidecar serving %s (%s) on %s — mesh %s, tokenizer %s",
            self.serving.model, self.family, self.target, mesh_label,
            type(self.tokenizer).__name__,
        )
        return self.port

    async def stop(self) -> None:
        for channel in self._peer_channels.values():
            try:
                await channel.close()
            except asyncio.CancelledError:
                raise  # a cancelled shutdown must not swallow itself
            except Exception:  # noqa: BLE001 — peer may already be gone
                pass
        self._peer_channels.clear()
        if self.batcher is not None:
            await self.batcher.stop()
        if self.server is not None:
            await self.server.stop(grace=2.0)
        if self.serving.uds_path:
            try:
                os.unlink(self.serving.uds_path)
            except OSError:
                pass


def _strip_trailing_pads(row: "np.ndarray") -> list[int]:
    """Strip only TRAILING zeros (padding); interior zeros are real ids."""
    nonzero = np.nonzero(row)[0]
    if len(nonzero) == 0:
        return []
    return row[: nonzero[-1] + 1].tolist()


def _stable_prefix(text: str) -> str:
    """Hold back a trailing replacement char: it usually marks a
    partially-decoded multi-byte UTF-8 sequence that later tokens will
    complete — emitting it would corrupt the stream irreversibly."""
    return text.rstrip("�")


def _apply_stops(text: str, stops: list[str], finish: str) -> tuple[str, str]:
    """Truncate at the earliest stop string, if any."""
    cut = -1
    for stop in stops:
        if not stop:
            continue
        idx = text.find(stop)
        if idx >= 0 and (cut < 0 or idx < cut):
            cut = idx
    if cut >= 0:
        return text[:cut], "stop_string"
    return text, finish


def run(cfg: Config) -> None:
    from ggrmcp_tpu.gateway.app import setup_logging
    from ggrmcp_tpu.utils.jaxenv import init_runtime

    setup_logging(cfg)
    init_runtime("sidecar")

    async def main():
        sidecar = Sidecar(cfg.serving)
        await sidecar.start()
        await sidecar.server.wait_for_termination()

    asyncio.run(main())
