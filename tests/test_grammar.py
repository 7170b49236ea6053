"""Schema-constrained decoding net (ISSUE 4, marker `grammar`).

Covers, bottom-up:
- compiler: the schema suite (object/required/enum/number/array/nested,
  strings with escapes + UTF-8, $ref) accepts exactly its canonical
  JSON; typed errors for unsupported dialect and over-budget DFAs
- arena: state-0 reservation, refcounted residency, LRU eviction of
  idle grammars, capacity shed, offset relocation
- batcher end-to-end: constrained greedy output PARSES and VALIDATES
  against every suite schema while the same model unconstrained emits
  invalid JSON (the grammar demonstrably does the work); mixed
  constrained/unconstrained batches share ONE compiled tick and leave
  unconstrained rows bit-identical; grammar state survives chunked
  prefill and tick-interleaved admission; `grammar_complete` fires at
  the DFA's accepting sink
- chaos (also marker `chaos`): constrained greedy output bit-identical
  across injected tick failures — replay re-derives DFA state from the
  emitted prefix
- sidecar gRPC: GenerateRequest.constraint round-trip, INVALID_ARGUMENT
  for bad schemas / unresolved refs, stats fields flowing
- gateway: a real MCP tools/call with a constraint returns schema-valid
  JSON; gateway.structured_output resolves a tool's output schema into
  the backend call
"""

import asyncio
import contextlib
import json

import grpc
import grpc.aio
import pytest

from ggrmcp_tpu.core.config import (
    BatchingConfig,
    MeshConfig,
    ServingConfig,
)
from ggrmcp_tpu.grammar import (
    GrammarArena,
    GrammarCache,
    GrammarCapacityError,
    GrammarError,
    SchemaTooComplexError,
    SchemaUnsupportedError,
    compile_schema,
)
from ggrmcp_tpu.models import llama
from ggrmcp_tpu.ops.sampling import SamplingConfig
from ggrmcp_tpu.rpc.pb import serving_pb2
from ggrmcp_tpu.serving.batching import ContinuousBatcher
from ggrmcp_tpu.serving.engine import GenerationEngine
from ggrmcp_tpu.serving.sidecar import Sidecar
from ggrmcp_tpu.serving.tokenizer import ByteTokenizer
from ggrmcp_tpu.utils import failpoints

pytestmark = pytest.mark.grammar

GREEDY = SamplingConfig(temperature=0.0)
TOK = ByteTokenizer()
VOCAB = llama.CONFIGS["tiny-llama"].vocab_size

# The acceptance-suite schemas: every value type is BOUNDED (maxLength/
# maxItems; digit runs are compiler-bounded) so any model — including a
# random-weight one — must reach the accepting sink within max_new.
SUITE = {
    "object_required": {
        "type": "object",
        "properties": {
            "ok": {"type": "boolean"},
            "label": {"type": "string", "maxLength": 4},
        },
        "required": ["ok", "label"],
    },
    "enum": {"enum": ["alpha", "beta", 3, None]},
    "number": {
        "type": "object",
        "properties": {"value": {"type": "number"}},
        "required": ["value"],
    },
    "array": {
        "type": "array",
        "items": {"type": "integer"},
        "minItems": 1,
        "maxItems": 3,
    },
    "nested": {
        "type": "object",
        "properties": {
            "kind": {"enum": ["a", "b"]},
            "inner": {
                "type": "object",
                "properties": {
                    "flags": {
                        "type": "array",
                        "items": {"type": "boolean"},
                        "maxItems": 2,
                    },
                },
                "required": ["flags"],
            },
        },
        "required": ["kind", "inner"],
    },
}


def validate(value, schema, root=None):
    """Minimal JSON-schema validator for the compilable dialect — the
    test's independent oracle (no jsonschema on the image)."""
    root = root if root is not None else schema
    if "$ref" in schema:
        name = schema["$ref"].split("/")[-1]
        return validate(value, root["definitions"][name], root)
    if "const" in schema:
        return value == schema["const"]
    if "enum" in schema:
        return value in schema["enum"]
    for key in ("oneOf", "anyOf"):
        if key in schema:
            return any(validate(value, s, root) for s in schema[key])
    t = schema.get("type")
    if isinstance(t, list):
        return any(
            validate(value, {**schema, "type": x}, root) for x in t
        )
    if t == "object" or (t is None and "properties" in schema):
        if not isinstance(value, dict):
            return False
        props = schema.get("properties", {})
        if any(k not in value for k in schema.get("required", [])):
            return False
        return all(
            validate(v, props[k], root) for k, v in value.items()
            if k in props
        )
    if t == "array":
        if not isinstance(value, list):
            return False
        if len(value) < schema.get("minItems", 0):
            return False
        if "maxItems" in schema and len(value) > schema["maxItems"]:
            return False
        return all(validate(v, schema["items"], root) for v in value)
    if t == "string":
        return isinstance(value, str) and (
            schema.get("minLength", 0) <= len(value)
            <= schema.get("maxLength", 1 << 30)
        )
    if t == "integer":
        return isinstance(value, int) and not isinstance(value, bool)
    if t == "number":
        return isinstance(value, (int, float)) and not isinstance(value, bool)
    if t == "boolean":
        return isinstance(value, bool)
    if t == "null":
        return value is None
    return False


# ---------------------------------------------------------------------------
# Compiler (pure host)
# ---------------------------------------------------------------------------


class TestCompiler:
    def _g(self, schema, **kw):
        kw.setdefault("vocab_size", VOCAB)
        return compile_schema(schema, **kw)

    @pytest.mark.parametrize("name", sorted(SUITE))
    def test_suite_accepts_canonical_json(self, name):
        g = self._g(SUITE[name])
        samples = {
            "object_required": ['{"ok":true,"label":"ab"}',
                                '{"ok":false,"label":""}'],
            "enum": ['"alpha"', '"beta"', "3", "null"],
            "number": ['{"value":-12.5e3}', '{"value":0}'],
            "array": ["[1]", "[1,-2,3]"],
            "nested": ['{"kind":"a","inner":{"flags":[true,false]}}',
                       '{"kind":"b","inner":{"flags":[]}}'],
        }[name]
        for text in samples:
            assert g.matches(text), (name, text)
            assert validate(json.loads(text), SUITE[name]), (name, text)

    @pytest.mark.parametrize("name", sorted(SUITE))
    def test_suite_rejects_invalid_json(self, name):
        g = self._g(SUITE[name])
        bad = {
            "object_required": ['{"label":"ab","ok":true}',  # wrong order
                                '{"ok":1,"label":"ab"}', "{}"],
            "enum": ['"gamma"', "4", "true"],
            "number": ['{"value":"x"}', '{"value":01}'],
            "array": ["[]", "[1,2,3,4]", '["x"]'],
            "nested": ['{"kind":"c","inner":{"flags":[]}}',
                       '{"kind":"a","inner":{}}'],
        }[name]
        for text in bad:
            assert not g.matches(text), (name, text)

    def test_string_escapes_and_utf8(self):
        g = self._g({"type": "string"})
        for text in ['""', '"héllo"', '"tab\\t"', '"\\u00e9"', '"日本語"']:
            assert g.matches(text), text
        assert not g.matches('"raw"quote"')
        assert not g.matches('"dangling\\"')
        # a split multi-byte sequence is not accepted
        assert not g.matches('"x'.encode() + b"\xc3")

    def test_ref_resolution(self):
        schema = {
            "type": "object",
            "properties": {"p": {"$ref": "#/definitions/Point"}},
            "required": ["p"],
            "definitions": {
                "Point": {
                    "type": "object",
                    "properties": {"x": {"type": "integer"}},
                    "required": ["x"],
                },
            },
        }
        g = self._g(schema)
        assert g.matches('{"p":{"x":7}}')
        assert not g.matches('{"p":{"x":true}}')

    def test_recursive_ref_is_typed_error(self):
        schema = {
            "$ref": "#/definitions/Node",
            "definitions": {
                "Node": {
                    "type": "object",
                    "properties": {"next": {"$ref": "#/definitions/Node"}},
                    "required": ["next"],
                },
            },
        }
        with pytest.raises(SchemaTooComplexError):
            self._g(schema)

    def test_state_budget_is_typed_error(self):
        with pytest.raises(SchemaTooComplexError):
            self._g(SUITE["nested"], max_states=8)

    @pytest.mark.parametrize("schema", [
        {"type": "array"},                       # no items
        {"type": "string", "pattern": "a+"},     # regex pattern
        {"type": "frobnicate"},                  # unknown type
        {"enum": []},                            # empty enum
        {},                                      # unconstrained
    ])
    def test_unsupported_dialect_is_typed_error(self, schema):
        with pytest.raises(SchemaUnsupportedError):
            self._g(schema)

    def test_invalid_json_schema_text(self):
        with pytest.raises(GrammarError):
            self._g("{not json")

    def test_eos_only_in_accepting_states(self):
        g = self._g({"type": "boolean"})
        for s in range(g.n_states):
            assert bool(g.allow[s, g.eos_id]) == bool(g.accept[s])
        # and byte tokens outside the DFA edge set are disallowed
        assert not g.allow[g.start, TOK.pad_id]
        assert not g.allow[g.start, TOK.bos_id]

    def test_sink_reached_exactly_at_completion(self):
        g = self._g(SUITE["object_required"])
        tokens = TOK.encode('{"ok":true,"label":"ab"}')
        s = g.start
        for i, t in enumerate(tokens):
            assert not g.sink[s], f"sink before the end at {i}"
            s = g.step(s, t)
        assert g.sink[s] and g.accept[s]

    def test_fingerprint_is_canonical(self):
        from ggrmcp_tpu.grammar import schema_fingerprint

        a = schema_fingerprint('{"type": "boolean"}')
        b = schema_fingerprint({"type": "boolean"})
        assert a == b

    def test_vocab_too_small_rejected(self):
        with pytest.raises(GrammarError):
            compile_schema({"type": "boolean"}, vocab_size=100)


class TestCache:
    def test_compile_once_then_hit(self):
        cache = GrammarCache(max_entries=4)
        g1 = cache.get({"type": "boolean"}, vocab_size=VOCAB)
        g2 = cache.get('{"type":"boolean"}', vocab_size=VOCAB)
        assert g1 is g2
        assert cache.compiles == 1 and cache.hits == 1

    def test_lru_eviction(self):
        cache = GrammarCache(max_entries=2)
        cache.get({"type": "boolean"}, vocab_size=VOCAB)
        cache.get({"type": "null"}, vocab_size=VOCAB)
        cache.get({"type": "integer"}, vocab_size=VOCAB)  # evicts boolean
        cache.get({"type": "boolean"}, vocab_size=VOCAB)
        assert cache.compiles == 4 and cache.hits == 0


class TestArena:
    def test_state0_reserved_and_relocation(self):
        g = compile_schema(SUITE["enum"], vocab_size=VOCAB)
        arena = GrammarArena(256, VOCAB)
        handle = arena.acquire(g)
        assert handle.base >= 1
        assert bool(arena.allow[0].all())  # accept-all survives
        # relocated walk matches the local walk
        tokens = TOK.encode('"beta"')
        s_abs, s_loc = handle.start, g.start
        for t in tokens:
            s_abs = arena.step(s_abs, t)
            s_loc = g.step(s_loc, t)
        assert s_abs == s_loc + handle.base
        assert arena.is_sink(s_abs) == bool(g.sink[s_loc])

    def test_refcount_and_idle_eviction(self):
        # Layout: null (5 states, LIVE) at base 1, boolean (10 states,
        # idle) at base 6. The string grammar (71 states) fits the
        # 80-row arena only in the [6, 80) gap the boolean eviction
        # opens — the live null must survive.
        small = GrammarArena(80, VOCAB)
        g_live = compile_schema({"type": "null"}, vocab_size=VOCAB)
        g_idle = compile_schema({"type": "boolean"}, vocab_size=VOCAB)
        h_live = small.acquire(g_live)
        h_idle = small.acquire(g_idle)
        used = small.states_in_use()
        small.release(h_idle)  # idle but still resident (warm)
        assert small.states_in_use() == used
        big = compile_schema(
            {"type": "string", "maxLength": 4}, vocab_size=VOCAB
        )
        small.acquire(big)
        assert g_idle.schema_hash not in small._entries
        assert g_live.schema_hash in small._entries
        small.release(h_live)

    def test_capacity_error_when_live(self):
        # boolean (10 states) at base 1 leaves a 1-row tail in a
        # 12-row arena: nothing else fits while its ref is live.
        tiny = GrammarArena(12, VOCAB)
        g = compile_schema({"type": "boolean"}, vocab_size=VOCAB)
        tiny.acquire(g)  # live ref held
        other = compile_schema({"type": "null"}, vocab_size=VOCAB)
        with pytest.raises(GrammarCapacityError):
            tiny.acquire(other)


# ---------------------------------------------------------------------------
# Batcher end-to-end (virtual 8-device CPU mesh)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def engine():
    return GenerationEngine(
        llama.CONFIGS["tiny-llama"],
        ServingConfig(mesh=MeshConfig(tensor=2, data=0)),
    )


async def _drain(batcher, prompt, max_new, sampling=GREEDY, **kw):
    out, reason = [], None
    async for ids, reason in batcher.submit(
        prompt, max_new, sampling, **kw
    ):
        out.extend(ids)
    return out, reason


@contextlib.asynccontextmanager
async def _batcher(engine, **cfg_kw):
    cfg_kw.setdefault("max_batch_size", 4)
    cfg_kw.setdefault("kv_cache_max_seq", 512)
    batcher = ContinuousBatcher(engine, BatchingConfig(**cfg_kw))
    batcher.start()
    try:
        yield batcher
    finally:
        await batcher.stop()


class TestConstrainedDecode:
    @pytest.mark.parametrize("name", sorted(SUITE))
    async def test_suite_end_to_end_valid_json(self, engine, name):
        """THE acceptance property: constrained greedy output parses
        AND validates against the schema, for every suite schema."""
        schema = SUITE[name]
        g = compile_schema(schema, vocab_size=VOCAB)
        async with _batcher(engine) as batcher:
            out, reason = await _drain(
                batcher, [3, 1, 4, 1], 256, grammar=g
            )
            text = TOK.decode(out)
            assert reason in ("grammar_complete", "stop"), (name, text)
            value = json.loads(text)  # parses
            assert validate(value, schema), (name, text)
            assert g.matches(text), (name, text)

    async def test_unconstrained_same_model_is_invalid(self, engine):
        """The grammar demonstrably does the work: the SAME model and
        prompt without the constraint does not produce valid JSON."""
        async with _batcher(engine) as batcher:
            out, _ = await _drain(batcher, [3, 1, 4, 1], 64)
            with pytest.raises(json.JSONDecodeError):
                json.loads(TOK.decode(out))

    async def test_sampled_constrained_output_also_valid(self, engine):
        schema = SUITE["nested"]
        g = compile_schema(schema, vocab_size=VOCAB)
        async with _batcher(engine) as batcher:
            out, reason = await _drain(
                batcher, [7, 7, 7], 256, grammar=g,
                sampling=SamplingConfig(temperature=1.0, top_p=0.9),
                seed=11,
            )
            value = json.loads(TOK.decode(out))
            assert validate(value, schema)
            assert reason in ("grammar_complete", "stop")

    async def test_mixed_batch_shares_one_compiled_tick(self, engine):
        """Mixed constrained/unconstrained batches: the unconstrained
        row is BIT-identical to its solo run, and running constrained
        traffic (including a SECOND distinct schema) adds zero tick
        compiles — table contents change, shapes never do."""
        g1 = compile_schema(SUITE["object_required"], vocab_size=VOCAB)
        g2 = compile_schema(SUITE["array"], vocab_size=VOCAB)
        async with _batcher(engine) as batcher:
            solo, _ = await _drain(batcher, [3, 1, 4, 1], 8)
            compiles_before = batcher._tick._cache_size()
            plain, c1 = await asyncio.gather(
                _drain(batcher, [3, 1, 4, 1], 8),
                _drain(batcher, [5, 5, 5], 256, grammar=g1),
            )
            c2, _ = await asyncio.gather(
                _drain(batcher, [9, 2], 256, grammar=g2),
                _drain(batcher, [1, 2, 3], 8),
            )
            assert plain[0] == solo
            assert validate(
                json.loads(TOK.decode(c1[0])), SUITE["object_required"]
            )
            assert validate(json.loads(TOK.decode(c2[0])), SUITE["array"])
            # compile-count stability across constrained ticks + a new
            # schema (the fixed-shape arena contract).
            assert batcher._tick._cache_size() == compiles_before

    async def test_same_schema_reuses_arena_entry(self, engine):
        g = compile_schema(SUITE["enum"], vocab_size=VOCAB)
        async with _batcher(engine) as batcher:
            await _drain(batcher, [3], 64, grammar=g)
            states = batcher.arena.states_in_use()
            out1, _ = await _drain(batcher, [3], 64, grammar=g)
            assert batcher.arena.states_in_use() == states
            # deterministic: same prompt, same grammar → same bytes
            out2, _ = await _drain(batcher, [3], 64, grammar=g)
            assert out1 == out2

    async def test_grammar_state_survives_chunked_prefill(self, engine):
        """A prompt longer than prefill_chunk takes the chunked
        admission path; the first-token sample must still be masked
        from the grammar's start state."""
        schema = SUITE["object_required"]
        g = compile_schema(schema, vocab_size=VOCAB)
        prompt = list(range(3, 3 + 90))
        async with _batcher(engine, prefill_chunk=32) as batcher:
            out, reason = await _drain(batcher, prompt, 256, grammar=g)
            assert validate(json.loads(TOK.decode(out)), schema)
            assert reason in ("grammar_complete", "stop")

    async def test_grammar_survives_interleaved_admission(self, engine):
        """A constrained long prompt admitted mid-decode through the
        tick-interleaved path produces output bit-identical to its
        solo (serialized) run — PR 1's numerics guarantee must hold
        under the grammar mask too."""
        schema = SUITE["nested"]
        g = compile_schema(schema, vocab_size=VOCAB)
        prompt = list(range(5, 5 + 90))
        async with _batcher(engine, prefill_chunk=32) as batcher:
            solo, _ = await _drain(batcher, prompt, 256, grammar=g)
        async with _batcher(
            engine, prefill_chunk=32, prefill_interleave="on",
            prefill_interleave_rows=2,
        ) as batcher:
            bg = asyncio.create_task(
                _drain(batcher, [8, 8, 8], 200, seed=1)
            )
            await asyncio.sleep(0.05)  # bg decode occupies the pool
            out, reason = await _drain(batcher, prompt, 256, grammar=g)
            await bg
            assert batcher.interleaved_admissions >= 1
            assert out == solo
            assert validate(json.loads(TOK.decode(out)), schema)

    async def test_stats_and_flight_record_flow(self, engine):
        g = compile_schema(SUITE["number"], vocab_size=VOCAB)
        async with _batcher(engine) as batcher:
            out, _ = await _drain(
                batcher, [4, 2], 256, grammar=g, trace_id="trace-g"
            )
            stats = batcher.stats()
            assert stats["grammar_masked_tokens"] >= len(out)
            assert stats["grammar_states_in_use"] > 1
            record = batcher.request_record("trace-g")
            assert record is not None and record.constrained
            # arena reference returned at terminal
            entry = batcher.arena._entries[g.schema_hash]
            assert entry["refs"] == 0

    async def test_capacity_shed_is_eager_and_typed(self, engine):
        """A schema the arena cannot host sheds AT SUBMIT — typed,
        before any queue slot or device work is spent."""
        batcher = ContinuousBatcher(
            engine, BatchingConfig(max_batch_size=2, kv_cache_max_seq=128)
        )
        # Shrink the arena post-hoc (the constructor sizes it from
        # engine.serving.grammar; the module engine uses the default).
        batcher.arena = GrammarArena(40, VOCAB)
        g_big = compile_schema(SUITE["nested"], vocab_size=VOCAB)
        with pytest.raises(GrammarCapacityError):
            batcher.submit([1, 2], 8, GREEDY, grammar=g_big)


class TestGrammarChaos:
    """Grammar × robustness (also in the chaos net)."""

    pytestmark = [pytest.mark.grammar, pytest.mark.chaos]

    @pytest.fixture(autouse=True)
    def clean_failpoints(self):
        failpoints.registry.disarm()
        yield
        failpoints.registry.disarm()

    async def test_constrained_bit_identical_under_tick_faults(
        self, engine
    ):
        """THE chaos acceptance property: with tick_fail injected,
        constrained greedy output is BIT-identical to the fault-free
        run — the replayed rows re-derive DFA state by replaying their
        emitted tokens through the transition table."""
        schema = SUITE["nested"]
        g = compile_schema(schema, vocab_size=VOCAB)
        prompts = [[3, 1, 4, 1], [2, 7, 1], [5, 5, 5, 5]]

        async def run_all(**cfg_kw):
            async with _batcher(
                engine, max_batch_size=4, kv_cache_max_seq=256, **cfg_kw
            ) as batcher:
                results = await asyncio.gather(*(
                    _drain(batcher, p, 256, grammar=g, seed=i)
                    for i, p in enumerate(prompts)
                ))
                return results, batcher.replayed

        baseline, replayed0 = await run_all()
        failpoints.registry.arm("tick_fail", every=4)
        faulted, replayed = await run_all(tick_retry_limit=32)
        failpoints.registry.disarm()
        assert replayed0 == 0 and replayed > 0
        assert faulted == baseline
        for out, reason in baseline:
            assert validate(json.loads(TOK.decode(out)), schema)
            assert reason in ("grammar_complete", "stop")


# ---------------------------------------------------------------------------
# Sidecar over real gRPC
# ---------------------------------------------------------------------------


def _unary(channel, path, req_cls, resp_cls):
    return channel.unary_unary(
        path,
        request_serializer=req_cls.SerializeToString,
        response_deserializer=resp_cls.FromString,
    )


@contextlib.asynccontextmanager
async def _sidecar():
    side = Sidecar(ServingConfig(
        mesh=MeshConfig(tensor=2, data=0),
        batching=BatchingConfig(max_batch_size=4, kv_cache_max_seq=512),
    ))
    port = await side.start(0)
    channel = grpc.aio.insecure_channel(f"localhost:{port}")
    try:
        yield side, channel
    finally:
        await channel.close()
        await side.stop()


class TestSamplerGateCounters:
    """sampler_order_ticks / sampler_mask_ticks (PR 40): how many of
    `ticks` carried a live row that makes the sampler sort, or read the
    grammar tables. Stamped at dispatch from what the tick is given."""

    async def test_zero_of_ticks_after_greedy_unconstrained_traffic(
        self, engine
    ):
        async with _batcher(engine) as batcher:
            await asyncio.gather(
                _drain(batcher, [3, 1, 4, 1], 8),
                _drain(batcher, [5, 5], 8),
                # temperature alone asks for no order: CDF inversion
                # over everything, no sort.
                _drain(batcher, [9, 2], 8,
                       sampling=SamplingConfig(temperature=0.9), seed=4),
            )
            stats = batcher.stats()
            assert stats["ticks"] > 0
            assert stats["sampler_order_ticks"] == 0
            assert stats["sampler_mask_ticks"] == 0

    @pytest.mark.parametrize("sampling", [
        SamplingConfig(temperature=0.8, top_p=0.9),
        SamplingConfig(temperature=0.8, top_k=5),
    ], ids=["top_p", "top_k"])
    async def test_order_ticks_count_a_sampling_row_under_top_k_or_top_p(
        self, engine, sampling
    ):
        async with _batcher(engine) as batcher:
            await _drain(batcher, [3, 1, 4, 1], 8, sampling=sampling, seed=7)
            stats = batcher.stats()
            assert 0 < stats["sampler_order_ticks"] <= stats["ticks"]
            assert stats["sampler_mask_ticks"] == 0
            # A greedy row that sets top_p is an argmax all the same.
            await _drain(batcher, [3, 1, 4, 1], 8,
                         sampling=SamplingConfig(temperature=0.0, top_p=0.5))
            after = batcher.stats()
            assert after["ticks"] > stats["ticks"]
            assert after["sampler_order_ticks"] == stats["sampler_order_ticks"]

    async def test_mask_ticks_count_a_live_grammar_and_stop_once_it_parks(
        self, engine
    ):
        g = compile_schema(SUITE["object_required"], vocab_size=VOCAB)
        async with _batcher(engine) as batcher:
            await _drain(batcher, [3], 256, grammar=g)
            stats = batcher.stats()
            assert 0 < stats["sampler_mask_ticks"] <= stats["ticks"]
            assert stats["sampler_order_ticks"] == 0
            # The request is over and its slot parked (the device twin of
            # its state is stale, not live): unconstrained traffic on the
            # same batcher adds ticks and no mask tick.
            plain, _ = await _drain(batcher, [3, 1, 4, 1], 8)
            after = batcher.stats()
            assert after["ticks"] > stats["ticks"]
            assert after["sampler_mask_ticks"] == stats["sampler_mask_ticks"]
            # ... and reads what a batcher that never saw a grammar reads.
            async with _batcher(engine) as fresh:
                solo, _ = await _drain(fresh, [3, 1, 4, 1], 8)
            assert plain == solo


class TestSidecarConstraint:
    async def test_generate_with_constraint_returns_valid_json(self):
        schema = SUITE["object_required"]
        async with _sidecar() as (side, channel):
            gen = _unary(
                channel, "/ggrmcp.tpu.GenerateService/Generate",
                serving_pb2.GenerateRequest, serving_pb2.GenerateResponse,
            )
            resp = await gen(serving_pb2.GenerateRequest(
                prompt="hi", max_new_tokens=256,
                constraint=serving_pb2.ConstraintSpec(
                    json_schema=json.dumps(schema)
                ),
            ))
            assert resp.finish_reason in ("grammar_complete", "stop")
            assert validate(json.loads(resp.text), schema)
            # stats flow: compiles/masked tokens visible over the RPC
            stats = await _unary(
                channel, "/ggrmcp.tpu.ModelInfoService/GetServingStats",
                serving_pb2.ServingStatsRequest,
                serving_pb2.ServingStatsResponse,
            )(serving_pb2.ServingStatsRequest())
            assert stats.grammar_compiles == 1
            assert stats.grammar_masked_tokens > 0
            assert stats.grammar_states_in_use > 1
            assert 0 < stats.sampler_mask_ticks <= stats.ticks
            assert stats.sampler_order_ticks == 0
            # second call with the SAME schema hits the compile cache
            await gen(serving_pb2.GenerateRequest(
                prompt="yo", max_new_tokens=256,
                constraint=serving_pb2.ConstraintSpec(
                    json_schema=json.dumps(schema)
                ),
            ))
            stats = await _unary(
                channel, "/ggrmcp.tpu.ModelInfoService/GetServingStats",
                serving_pb2.ServingStatsRequest,
                serving_pb2.ServingStatsResponse,
            )(serving_pb2.ServingStatsRequest())
            assert stats.grammar_compiles == 1
            assert stats.grammar_cache_hits >= 1

    async def test_stream_with_constraint(self):
        schema = SUITE["array"]
        async with _sidecar() as (_side, channel):
            stream = channel.unary_stream(
                "/ggrmcp.tpu.GenerateService/GenerateStream",
                request_serializer=(
                    serving_pb2.GenerateRequest.SerializeToString
                ),
                response_deserializer=serving_pb2.GenerateChunk.FromString,
            )
            text, finish = "", ""
            async for chunk in stream(serving_pb2.GenerateRequest(
                prompt="s", max_new_tokens=256,
                constraint=serving_pb2.ConstraintSpec(
                    json_schema=json.dumps(schema)
                ),
            )):
                text += chunk.text_delta
                if chunk.done:
                    finish = chunk.finish_reason
            assert finish in ("grammar_complete", "stop")
            assert validate(json.loads(text), schema)

    async def test_bad_schema_is_invalid_argument(self):
        async with _sidecar() as (_side, channel):
            gen = _unary(
                channel, "/ggrmcp.tpu.GenerateService/Generate",
                serving_pb2.GenerateRequest, serving_pb2.GenerateResponse,
            )
            for bad in (
                '{"type":"string","pattern":"a+"}',  # unsupported
                "{not json",                          # unparsable
            ):
                with pytest.raises(grpc.aio.AioRpcError) as err:
                    await gen(serving_pb2.GenerateRequest(
                        prompt="x", max_new_tokens=4,
                        constraint=serving_pb2.ConstraintSpec(
                            json_schema=bad
                        ),
                    ))
                assert err.value.code() == (
                    grpc.StatusCode.INVALID_ARGUMENT
                )

    async def test_unresolved_ref_is_invalid_argument(self):
        async with _sidecar() as (_side, channel):
            gen = _unary(
                channel, "/ggrmcp.tpu.GenerateService/Generate",
                serving_pb2.GenerateRequest, serving_pb2.GenerateResponse,
            )
            with pytest.raises(grpc.aio.AioRpcError) as err:
                await gen(serving_pb2.GenerateRequest(
                    prompt="x", max_new_tokens=4,
                    constraint=serving_pb2.ConstraintSpec(
                        tool_output_schema_ref="some_tool"
                    ),
                ))
            assert err.value.code() == grpc.StatusCode.INVALID_ARGUMENT


# ---------------------------------------------------------------------------
# Gateway: MCP tools/call with structured output
# ---------------------------------------------------------------------------


class TestGatewayStructuredOutput:
    async def test_tool_call_with_inline_constraint(self):
        """End-to-end MCP: tools/call → gateway → sidecar, with the
        caller's constraint enforced by DFA masking — the returned
        completion text parses and validates."""
        import aiohttp

        from ggrmcp_tpu.core import config as cfgmod
        from ggrmcp_tpu.gateway.app import Gateway

        schema = SUITE["nested"]
        side = Sidecar(ServingConfig(
            mesh=MeshConfig(tensor=2, data=0),
            batching=BatchingConfig(max_batch_size=4, kv_cache_max_seq=512),
        ))
        port = await side.start(0)
        cfg = cfgmod.default()
        cfg.server.host = "127.0.0.1"
        cfg.server.port = 0
        cfg.grpc.reconnect.enabled = False
        gw = Gateway(cfg, targets=[f"localhost:{port}"])
        await gw.start()
        try:
            async with aiohttp.ClientSession(
                base_url=f"http://127.0.0.1:{gw.port}"
            ) as client:
                resp = await client.post("/", json={
                    "jsonrpc": "2.0", "method": "tools/call", "id": 1,
                    "params": {
                        "name": "ggrmcp_tpu_generateservice_generate",
                        "arguments": {
                            "prompt": "go", "maxNewTokens": 256,
                            "constraint": {
                                "jsonSchema": json.dumps(schema)
                            },
                        },
                    },
                })
                data = await resp.json()
                assert "error" not in data, data
                payload = json.loads(data["result"]["content"][0]["text"])
                assert payload["finishReason"] in (
                    "grammar_complete", "stop"
                )
                assert validate(json.loads(payload["text"]), schema)

                # /metrics carries the grammar gauges
                metrics = await (await client.get("/metrics")).text()
                assert "gateway_backend_grammar_masked_tokens" in metrics
                assert "gateway_backend_grammar_compiles" in metrics

                # the structured_output resolver: opting the generate
                # tool in (schema source = itself) injects the tool's
                # own output schema into the backend arguments.
                tool_name = "ggrmcp_tpu_generateservice_generate"
                handler = gw.handler
                handler.cfg.gateway.structured_output = {tool_name: "self"}
                args = handler._apply_structured_output(
                    tool_name, {"prompt": "x"}
                )
                injected = json.loads(args["constraint"]["jsonSchema"])
                tools = handler._handle_tools_list()["tools"]
                tool = next(
                    t for t in tools if t["name"] == tool_name
                )
                assert injected == tool["outputSchema"]

                # per-call ref resolution does the same
                args2 = handler._apply_structured_output(
                    tool_name,
                    {"prompt": "x",
                     "constraint": {"toolOutputSchemaRef": tool_name}},
                )
                assert json.loads(
                    args2["constraint"]["jsonSchema"]
                ) == tool["outputSchema"]
        finally:
            await gw.stop()
            await side.stop()
