"""Jump-ahead constrained decoding net (ISSUE 16, marker
`grammar_jump`).

Covers, bottom-up:
- compiler: forced-run tables — single-token forced states, multi-token
  chains, truncation at jump_cap (with the chain continuing from the
  landing state), no forced run at branching or accepting states, and
  the walk-consistency invariant (jump_states IS the transition walk
  over jump_tokens)
- batcher: greedy constrained output BIT-identical jump-on vs jump-off
  on every admission path — fused, chunked prefill, tick-interleaved
  admission and paged KV — with jump_runs > 0 on
  the on side (the fast path demonstrably engaged)
- compile stability: a mixed batch over distinct schemas adds zero
  compiles to the plain AND jump tick programs post-warmup (the
  fixed-shape forced-run window contract)
- chaos (also marker `chaos`): grammar_jump_fail degrades one slot
  typed to one-token constrained decoding with bit-identical output;
  tick_fail replay mid-stream preserves bit-identity while jumps fire
"""

import asyncio
import contextlib
import json

import numpy as np
import pytest

from ggrmcp_tpu.core.config import (
    BatchingConfig,
    MeshConfig,
    ServingConfig,
)
from ggrmcp_tpu.grammar import compile_schema
from ggrmcp_tpu.grammar.compiler import JUMP_CAP, compute_jump_tables
from ggrmcp_tpu.models import llama
from ggrmcp_tpu.ops.sampling import SamplingConfig
from ggrmcp_tpu.serving.batching import ContinuousBatcher
from ggrmcp_tpu.serving.engine import GenerationEngine
from ggrmcp_tpu.serving.tokenizer import ByteTokenizer
from ggrmcp_tpu.utils import failpoints

pytestmark = pytest.mark.grammar_jump

GREEDY = SamplingConfig(temperature=0.0)
TOK = ByteTokenizer()
VOCAB = llama.CONFIGS["tiny-llama"].vocab_size

# Enum/const-rich schemas: long literal spans force multi-token runs,
# which is the workload the jump tick exists for.
SCHEMAS = {
    "const_obj": {
        "type": "object",
        "properties": {
            "kind": {"const": "structured"},
            "ok": {"type": "boolean"},
        },
        "required": ["kind", "ok"],
    },
    "enum_obj": {
        "type": "object",
        "properties": {
            "mode": {"enum": ["alpha", "beta"]},
            "flag": {"type": "boolean"},
        },
        "required": ["mode", "flag"],
    },
    "nested": {
        "type": "object",
        "properties": {
            "label": {"const": "jump-ahead"},
            "inner": {
                "type": "object",
                "properties": {"on": {"type": "boolean"}},
                "required": ["on"],
            },
        },
        "required": ["label", "inner"],
    },
}


# ---------------------------------------------------------------------------
# Compiler forced-run tables
# ---------------------------------------------------------------------------


class TestJumpTables:
    def test_const_forces_full_literal(self):
        """`{"const": true}` admits exactly one byte per state until the
        accepting sink: the start state's forced run is the whole
        literal, and the landing state accepts (run is empty there —
        a jump can never skip a legal stop point)."""
        g = compile_schema({"const": True}, vocab_size=VOCAB)
        run = g.forced_run(g.start)
        assert TOK.decode(run) == "true"
        landing = int(g.jump_states[g.start, len(run) - 1])
        assert g.forced_run(landing) == []
        assert g.state_after(run) == landing

    def test_multi_token_chain_long_literal(self):
        g = compile_schema({"const": "alphabet"}, vocab_size=VOCAB)
        run = g.forced_run(g.start)
        assert TOK.decode(run) == '"alphabet"'
        assert len(run) == 10

    def test_truncation_at_jump_cap_chains_from_landing(self):
        """A run longer than jump_cap truncates; the landing state's
        OWN run continues the literal — two windowed jumps cover what
        one uncapped jump would."""
        g = compile_schema(
            {"const": "alphabet"}, vocab_size=VOCAB, jump_cap=3
        )
        first = g.forced_run(g.start)
        assert len(first) == 3 and TOK.decode(first) == '"al'
        landing = int(g.jump_states[g.start, 2])
        second = g.forced_run(landing)
        assert TOK.decode(second) == "pha"
        full = compile_schema({"const": "alphabet"}, vocab_size=VOCAB)
        assert len(full.forced_run(full.start)) == 10 <= JUMP_CAP

    def test_branching_state_has_no_forced_run(self):
        """enum ["alpha", "beta"]: the opening quote is forced, then
        the next byte branches — the post-quote state must not force."""
        g = compile_schema({"enum": ["alpha", "beta"]}, vocab_size=VOCAB)
        run = g.forced_run(g.start)
        assert TOK.decode(run) == '"'
        landing = int(g.jump_states[g.start, 0])
        assert g.forced_run(landing) == []

    def test_accepting_states_never_forced(self):
        """Every state that admits EOS has run length 0 by definition
        (forced = exactly one admissible token AND it is not EOS)."""
        g = compile_schema(SCHEMAS["const_obj"], vocab_size=VOCAB)
        accepting = np.where(g.allow[:, g.eos_id])[0]
        assert len(accepting) >= 1
        assert (g.jump_len[accepting] == 0).all()

    def test_tables_consistent_with_transition_walk(self):
        """jump_states[s, :L] IS the trans walk over jump_tokens[s, :L],
        and every intermediate state on the chain is itself forced —
        the invariant the device gather relies on."""
        g = compile_schema(SCHEMAS["nested"], vocab_size=VOCAB)
        assert int(g.jump_len.max()) > 1  # the schema actually jumps
        for s in range(g.n_states):
            length = int(g.jump_len[s])
            cur = s
            for k in range(length):
                tok = int(g.jump_tokens[s, k])
                row = g.allow[cur]
                assert row.sum() == 1 and row[tok] and tok != g.eos_id
                cur = int(g.trans[cur, tok])
                assert cur == int(g.jump_states[s, k])

    def test_zero_cap_disables(self):
        jl, jt, js = compute_jump_tables(
            compile_schema({"const": True}, vocab_size=VOCAB).allow,
            compile_schema({"const": True}, vocab_size=VOCAB).trans,
            eos_id=2, jump_cap=0,
        )
        assert (jl == 0).all() and jt.shape[1] == 0


# ---------------------------------------------------------------------------
# Batcher end-to-end (virtual 8-device CPU mesh)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def engine():
    return GenerationEngine(
        llama.CONFIGS["tiny-llama"],
        ServingConfig(mesh=MeshConfig(tensor=2, data=0)),
    )


@pytest.fixture(autouse=True)
def clean_failpoints():
    failpoints.registry.disarm()
    yield
    failpoints.registry.disarm()


async def _drain(batcher, prompt, max_new, sampling=GREEDY, **kw):
    out, reason = [], None
    async for ids, reason in batcher.submit(prompt, max_new, sampling, **kw):
        out.extend(ids)
    return out, reason


@contextlib.asynccontextmanager
async def _batcher(engine, jump=True, **cfg_kw):
    """Batcher with jump-ahead on (the config default) or forced off —
    the constructor reads serving.grammar.jump_max, so the off side
    flips it for the construction window only."""
    cfg_kw.setdefault("max_batch_size", 4)
    cfg_kw.setdefault("kv_cache_max_seq", 512)
    saved = engine.serving.grammar.jump_max
    engine.serving.grammar.jump_max = saved if jump else 0
    try:
        batcher = ContinuousBatcher(engine, BatchingConfig(**cfg_kw))
    finally:
        engine.serving.grammar.jump_max = saved
    batcher.start()
    try:
        yield batcher
    finally:
        await batcher.stop()


def _jump_stats(batcher) -> dict:
    s = batcher.counter_stats()
    return {k: s[k] for k in (
        "grammar_jump_tokens", "grammar_jump_runs",
        "grammar_jump_fallbacks",
    )}


class TestJumpBitIdentity:
    """THE acceptance property: greedy constrained output is
    bit-identical jump-on vs jump-off on every admission path, and the
    on side demonstrably jumps (jump_runs > 0)."""

    @pytest.mark.parametrize("name", sorted(SCHEMAS))
    async def test_fused(self, engine, name):
        schema = SCHEMAS[name]
        g = compile_schema(schema, vocab_size=VOCAB)
        async with _batcher(engine, jump=False) as batcher:
            off, reason_off = await _drain(batcher, [3, 1, 4, 1], 256,
                                           grammar=g)
            assert _jump_stats(batcher)["grammar_jump_runs"] == 0
        async with _batcher(engine, jump=True) as batcher:
            on, reason_on = await _drain(batcher, [3, 1, 4, 1], 256,
                                         grammar=g)
            stats = _jump_stats(batcher)
        assert on == off and reason_on == reason_off
        assert stats["grammar_jump_runs"] > 0
        assert stats["grammar_jump_tokens"] >= stats["grammar_jump_runs"]
        assert stats["grammar_jump_fallbacks"] == 0
        json.loads(TOK.decode(on))

    async def test_chunked_prefill(self, engine):
        g = compile_schema(SCHEMAS["const_obj"], vocab_size=VOCAB)
        prompt = list(range(3, 3 + 90))
        async with _batcher(engine, jump=False, prefill_chunk=32) as b:
            off, _ = await _drain(b, prompt, 256, grammar=g)
        async with _batcher(engine, jump=True, prefill_chunk=32) as b:
            on, _ = await _drain(b, prompt, 256, grammar=g)
            assert _jump_stats(b)["grammar_jump_runs"] > 0
        assert on == off

    async def test_interleaved_admission(self, engine):
        """A constrained prompt admitted mid-decode through the
        tick-interleaved path: the jump+chunk fused program carries the
        prefill rows while live slots jump."""
        g = compile_schema(SCHEMAS["enum_obj"], vocab_size=VOCAB)
        prompt = list(range(5, 5 + 90))
        async with _batcher(engine, jump=False, prefill_chunk=32) as b:
            off, _ = await _drain(b, prompt, 256, grammar=g)
        async with _batcher(
            engine, jump=True, prefill_chunk=32,
            prefill_interleave="on", prefill_interleave_rows=2,
        ) as b:
            bg = asyncio.create_task(_drain(b, [8, 8, 8], 200, seed=1))
            await asyncio.sleep(0.05)  # bg decode occupies the pool
            on, _ = await _drain(b, prompt, 256, grammar=g)
            await bg
            assert b.interleaved_admissions >= 1
            assert _jump_stats(b)["grammar_jump_runs"] > 0
        assert on == off

    async def test_paged_kv(self, engine):
        """Jump ticks over the paged arena: the admission-time reserve
        already covers the 1 + jump_max window, so the block-table walk
        absorbs multi-token KV writes with no mid-run extension."""
        g = compile_schema(SCHEMAS["nested"], vocab_size=VOCAB)
        async with _batcher(engine, jump=False, paged_kv="on") as b:
            off, _ = await _drain(b, [3, 1, 4, 1], 256, grammar=g)
        async with _batcher(engine, jump=True, paged_kv="on") as b:
            on, _ = await _drain(b, [3, 1, 4, 1], 256, grammar=g)
            assert _jump_stats(b)["grammar_jump_runs"] > 0
        assert on == off
        json.loads(TOK.decode(on))


class TestJumpCompileStability:
    async def test_mixed_schema_batch_zero_recompiles(self, engine):
        """Distinct schemas decoding concurrently add ZERO compiles to
        the plain and jump tick programs after warmup — the forced-run
        window is jump_max wide regardless of schema mix."""
        gs = [compile_schema(SCHEMAS[n], vocab_size=VOCAB)
              for n in sorted(SCHEMAS)]
        async with _batcher(engine, jump=True) as batcher:
            # Warm BOTH program families (a pure-constrained drain only
            # compiles the jump tick; the unconstrained one compiles
            # the plain tick) before snapshotting the compile counts.
            await _drain(batcher, [2, 2], 256, grammar=gs[0])
            await _drain(batcher, [6, 6], 8)
            plain_before = batcher._tick._cache_size()
            jump_before = batcher._tick_jump._cache_size()
            results = await asyncio.gather(
                *(_drain(batcher, [3 + i], 256, grammar=g)
                  for i, g in enumerate(gs)),
                _drain(batcher, [9, 9], 8),  # unconstrained rider
            )
            for (out, reason), name in zip(results[:-1], sorted(SCHEMAS)):
                assert reason in ("grammar_complete", "stop")
                json.loads(TOK.decode(out))
            assert batcher._tick._cache_size() == plain_before
            assert batcher._tick_jump._cache_size() == jump_before
            assert _jump_stats(batcher)["grammar_jump_runs"] > 0


class TestJumpChaos:
    pytestmark = [pytest.mark.grammar_jump, pytest.mark.chaos]

    async def test_jump_fail_degrades_typed_and_bit_identical(
        self, engine
    ):
        """grammar_jump_fail: the refused run degrades that slot to
        one-token constrained decoding — counted, never silent, output
        bit-identical and still schema-valid."""
        g = compile_schema(SCHEMAS["const_obj"], vocab_size=VOCAB)
        async with _batcher(engine, jump=True,
                            tick_retry_limit=8) as batcher:
            clean, reason_clean = await _drain(
                batcher, [3, 1, 4, 1], 256, grammar=g
            )
            assert _jump_stats(batcher)["grammar_jump_fallbacks"] == 0
        failpoints.registry.arm("grammar_jump_fail", times=1)
        async with _batcher(engine, jump=True,
                            tick_retry_limit=8) as batcher:
            out, reason = await _drain(
                batcher, [3, 1, 4, 1], 256, grammar=g
            )
            stats = _jump_stats(batcher)
        failpoints.registry.disarm()
        assert stats["grammar_jump_fallbacks"] == 1
        assert out == clean and reason == reason_clean
        assert json.loads(TOK.decode(out))["kind"] == "structured"

    async def test_tick_replay_bit_identical_with_jumps_midstream(
        self, engine
    ):
        """tick_fail while jumps fire: replayed rows re-derive DFA
        state from the emitted prefix and re-admit onto the jump path —
        output stays bit-identical to the fault-free run."""
        g = compile_schema(SCHEMAS["nested"], vocab_size=VOCAB)
        prompts = [[3, 1, 4, 1], [2, 7, 1], [5, 5, 5, 5]]

        async def run_all(**cfg_kw):
            async with _batcher(
                engine, jump=True, max_batch_size=4,
                kv_cache_max_seq=256, **cfg_kw
            ) as batcher:
                results = await asyncio.gather(*(
                    _drain(batcher, p, 256, grammar=g, seed=i)
                    for i, p in enumerate(prompts)
                ))
                return results, batcher.replayed, _jump_stats(batcher)

        baseline, replayed0, stats0 = await run_all()
        failpoints.registry.arm("tick_fail", every=4)
        faulted, replayed, _ = await run_all(tick_retry_limit=32)
        failpoints.registry.disarm()
        assert replayed0 == 0 and replayed > 0
        assert stats0["grammar_jump_runs"] > 0
        assert faulted == baseline
        for out, reason in baseline:
            json.loads(TOK.decode(out))
            assert reason in ("grammar_complete", "stop")
