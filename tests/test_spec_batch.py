"""Speculative decoding inside the continuous batcher (ISSUE 5,
marker `spec_batch`): the fixed-shape draft/verify tick a configured
`serving.speculative_draft` turns on.

The load-bearing guarantees:

  * Greedy bitwise identity — the output of an engine with a draft
    is BYTE-identical to that of the same engine without one across
    every admission path (fused single/burst, chunked,
    tick-interleaved; the paged path in tests/test_paged_kv.py) and under
    injected tick faults (chaos replay). Exact-match acceptance makes
    this hold REGARDLESS of draft quality.
  * Sampled losslessness — emitted tokens are distributed exactly as
    plain target sampling over the per-row temp→top-k→top-p FILTERED
    distribution, pinned by TV-distance against the exact
    conditional.
  * Fixed shapes — mixed greedy/sampled/top-k/constrained batches
    share ONE compiled spec tick (compile-count stability).

Deliberately NOT slow-marked: tier-1 always runs the spec tick;
`make test-spec-batch` selects it alone.
"""

import asyncio
import functools
import json

import jax
import numpy as np
import pytest

from ggrmcp_tpu.core.config import BatchingConfig, MeshConfig, ServingConfig
from ggrmcp_tpu.grammar import compile_schema
from ggrmcp_tpu.models import llama
from ggrmcp_tpu.ops.sampling import SamplingConfig
from ggrmcp_tpu.serving.batching import ContinuousBatcher
from ggrmcp_tpu.serving.engine import GenerationEngine
from ggrmcp_tpu.serving.tokenizer import ByteTokenizer
from ggrmcp_tpu.utils import failpoints

pytestmark = pytest.mark.spec_batch

GREEDY = SamplingConfig(temperature=0.0)
TOK = ByteTokenizer()
VOCAB = llama.CONFIGS["tiny-llama"].vocab_size


def spec_cfg(**kw) -> ServingConfig:
    kw.setdefault("model", "tiny-llama")
    kw.setdefault("mesh", MeshConfig(tensor=2, data=0))
    kw.setdefault("speculative_draft", "tiny-llama")
    return ServingConfig(**kw)


@pytest.fixture(scope="module")
def engine():
    # Draft = same architecture, DIFFERENT random params (seed offset
    # in _init_speculative): realistic imperfect-draft acceptance.
    return GenerationEngine(llama.CONFIGS["tiny-llama"], spec_cfg())


@pytest.fixture(autouse=True)
def clean_failpoints():
    failpoints.registry.disarm()
    yield
    failpoints.registry.disarm()


@functools.cache
def _plain_engine() -> GenerationEngine:
    """The same target (same seed, same weights) with no draft: the
    spec-off side of every comparison."""
    return GenerationEngine(
        llama.CONFIGS["tiny-llama"], spec_cfg(speculative_draft="")
    )


def _batcher(engine, spec: bool, **cfg_kw) -> ContinuousBatcher:
    """A configured draft IS the switch: spec=False builds the batcher
    over the draft-less twin of `engine`."""
    cfg_kw.setdefault("max_batch_size", 4)
    cfg_kw.setdefault("kv_cache_max_seq", 256)
    return ContinuousBatcher(
        engine if spec else _plain_engine(), BatchingConfig(**cfg_kw)
    )


async def _drain(batcher, prompt, max_new, sampling=GREEDY, seed=0,
                 grammar=None):
    out, reason = [], None
    async for ids, reason in batcher.submit(
        prompt, max_new, sampling, seed=seed, grammar=grammar
    ):
        out.extend(ids)
    return out, reason


async def _run_all(engine, prompts, max_new, spec, seeds=None, **cfg_kw):
    """Drain `prompts` concurrently through one batcher; returns
    ([(tokens, reason)], batcher)."""
    batcher = _batcher(engine, spec, **cfg_kw)
    batcher.start()
    try:
        results = await asyncio.gather(*(
            _drain(batcher, p, max_new,
                   seed=(seeds[i] if seeds else i))
            for i, p in enumerate(prompts)
        ))
        return results, batcher
    finally:
        await batcher.stop()


LONG = [(i * 7) % 200 + 3 for i in range(90)]  # > prefill_chunk=32


class TestGreedyBitwiseIdentity:
    """THE acceptance property: spec-on greedy output is byte-identical
    to spec-off on every admission path."""

    async def test_fused_burst_and_trickle(self, engine):
        prompts = [[3, 1, 4, 1], [2, 7, 1], [5, 5, 5, 5, 5], [9, 9]]
        off, _ = await _run_all(engine, prompts, 10, spec=False)
        on, b = await _run_all(engine, prompts, 10, spec=True)
        assert on == off
        assert b.spec_ticks > 0 and b.spec_drafted > 0
        # Trickle (single-row admission program) too.
        off1, _ = await _run_all(engine, [[8, 6, 7]], 9, spec=False)
        on1, _ = await _run_all(engine, [[8, 6, 7]], 9, spec=True)
        assert on1 == off1

    async def test_chunked_admission(self, engine):
        off, _ = await _run_all(
            engine, [LONG], 8, spec=False, prefill_chunk=32
        )
        on, _ = await _run_all(
            engine, [LONG], 8, spec=True, prefill_chunk=32
        )
        assert on == off

    async def test_interleaved_admission(self, engine):
        """A long prompt landing while another slot decodes takes the
        tick-interleaved chunk path (spec tick fused with the chunk);
        output must still match spec-off exactly."""
        outs = {}
        for spec in (False, True):
            batcher = _batcher(
                engine, spec, prefill_chunk=32, prefill_interleave="on",
                prefill_interleave_rows=2,
            )
            batcher.start()
            try:
                bg = asyncio.ensure_future(
                    _drain(batcher, [4, 2, 4], 48, seed=1)
                )
                await asyncio.sleep(0.05)  # bg decodes before LONG lands
                long_res = await _drain(batcher, LONG, 8, seed=2)
                bg_res = await bg
                outs[spec] = (bg_res, long_res)
                if spec:
                    assert batcher.interleaved_admissions > 0, (
                        "interleave path not exercised"
                    )
            finally:
                await batcher.stop()
        assert outs[True] == outs[False]

    async def test_chaos_replay_bit_identity(self, engine):
        """Injected tick faults: victims replay with their emitted
        prefix, the draft cache re-prefills at re-admission, and greedy
        spec-on output stays byte-identical to the fault-free run."""
        prompts = [[3, 1, 4, 1], [2, 7, 1], [5, 5, 5, 5, 5], [9, 9]]
        baseline, _ = await _run_all(engine, prompts, 8, spec=True)
        failpoints.registry.arm("tick_fail", every=3)
        faulted, chaos_b = await _run_all(
            engine, prompts, 8, spec=True, tick_retry_limit=32
        )
        failpoints.registry.disarm()
        assert chaos_b.replayed > 0, "no fault was actually injected"
        assert chaos_b.replay_exhausted == 0
        assert faulted == baseline


class TestConstrainedRows:
    """Grammar-constrained rows verify against the DFA mask inside the
    spec tick (states advanced along the proposal path)."""

    SCHEMA = {
        "type": "object",
        "properties": {
            "ok": {"type": "boolean"},
            "label": {"type": "string", "maxLength": 4},
        },
        "required": ["ok", "label"],
    }

    async def test_constrained_greedy_matches_spec_off(self, engine):
        g = compile_schema(self.SCHEMA, vocab_size=VOCAB)
        outs = {}
        for spec in (False, True):
            batcher = _batcher(engine, spec)
            batcher.start()
            try:
                outs[spec] = await _drain(
                    batcher, [3, 1, 4, 1], 256, grammar=g
                )
            finally:
                await batcher.stop()
        assert outs[True] == outs[False]
        out, reason = outs[True]
        assert reason in ("grammar_complete", "stop")
        text = TOK.decode(out)
        value = json.loads(text)
        assert value.get("ok") in (True, False)
        assert g.matches(text)

    async def test_mixed_batch_compile_count_stable(self, engine):
        """Mixed greedy / sampled / top-k/top-p / constrained rows all
        ride ONE compiled spec tick — running them adds zero compiles
        after warmup (the fixed-shape contract)."""
        g = compile_schema(self.SCHEMA, vocab_size=VOCAB)
        batcher = _batcher(engine, spec=True)
        batcher.start()
        try:
            await _drain(batcher, [3, 1, 4], 8)  # warm the spec tick
            before = batcher._tick_spec._cache_size()
            results = await asyncio.gather(
                _drain(batcher, [3, 1, 4], 8),
                _drain(batcher, [5, 5, 5], 8,
                       sampling=SamplingConfig(temperature=0.9), seed=7),
                _drain(batcher, [2, 7], 8,
                       sampling=SamplingConfig(
                           temperature=0.8, top_k=5, top_p=0.9
                       ), seed=11),
                _drain(batcher, [9, 2], 256, grammar=g),
            )
            for out, reason in results:
                assert len(out) >= 1
                assert reason in (
                    "stop", "length", "grammar_complete"
                )
            assert batcher._tick_spec._cache_size() == before
        finally:
            await batcher.stop()


NANO = llama.LlamaConfig(
    name="nano-llama-sb", vocab_size=8, hidden_dim=32, num_layers=2,
    num_heads=2, num_kv_heads=2, head_dim=16, ffn_dim=64,
    max_seq_len=64, dtype="float32",
)


@pytest.fixture(scope="module")
def nano_engine():
    """Tiny-vocab (8) engine + imperfect draft: small enough that an
    empirical output histogram can be compared against the exact model
    distribution (same construction as tests/test_speculative.py)."""
    llama.CONFIGS["nano-llama-sb"] = NANO
    try:
        yield GenerationEngine(
            NANO, spec_cfg(model="nano-llama-sb",
                           speculative_draft="nano-llama-sb"),
        )
    finally:
        del llama.CONFIGS["nano-llama-sb"]


async def _second_token_pairs(engine, sampling, waves, rows, eos=2):
    """(t0, t1) pairs from max_new=2 spec-batched generations with
    distinct per-row seeds; stripped EOS reconstructed (the batcher
    consumes the terminal EOS as finish_reason 'stop')."""
    batcher = _batcher(engine, spec=True, max_batch_size=rows)
    batcher.start()
    pairs = []
    try:
        for wave in range(waves):
            results = await asyncio.gather(*(
                _drain(batcher, [3, 1, 4], 2, sampling=sampling,
                       seed=wave * rows + i)
                for i in range(rows)
            ))
            for ids, reason in results:
                if len(ids) == 2:
                    pairs.append((ids[0], ids[1]))
                elif len(ids) == 1 and reason == "stop":
                    pairs.append((ids[0], eos))
    finally:
        await batcher.stop()
    return pairs


def _exact_conditional(engine, prompt, filt=None):
    """Exact second-token conditional: target softmax after prompt,
    optionally restricted to `filt(probs) -> mask` support."""
    import jax.numpy as jnp

    logits, _ = llama.forward(
        dict(engine.params), NANO, jnp.asarray([prompt], jnp.int32)
    )
    exact = np.asarray(
        jax.nn.softmax(np.asarray(logits)[0, -1].astype(np.float64))
    )
    if filt is not None:
        mask = filt(exact)
        exact = np.where(mask, exact, 0.0)
        exact /= exact.sum()
    return exact


class TestSampledLossless:
    """The TV-distance net: the spec TICK's rejection sampler (accept + residual against an
    imperfect draft) must emit second tokens distributed exactly as
    plain target sampling — and, with top-k set, as the top-k FILTERED
    target distribution (the lossless extension this issue adds)."""

    def _check(self, engine, pairs, filt=None, bound=0.15):
        firsts = [p[0] for p in pairs]
        assert firsts, "all rows stopped at zero tokens"
        modal = max(set(firsts), key=firsts.count)
        seconds = [p[1] for p in pairs if p[0] == modal]
        assert len(seconds) >= 150, "not enough conditional samples"
        emp = np.bincount(
            seconds, minlength=NANO.vocab_size
        ).astype(float)
        emp /= emp.sum()
        exact = _exact_conditional(engine, [3, 1, 4, modal], filt)
        tv = 0.5 * np.abs(emp - exact).sum()
        assert tv < bound, (
            f"spec-batched second-token TV distance {tv:.3f} "
            f"(emp {np.round(emp, 3)}, exact {np.round(exact, 3)})"
        )

    async def test_plain_temperature_distribution(self, nano_engine):
        # 28 waves: under this install's (jax 0.9.0) seeded init EOS is
        # the modal FIRST token at p = 0.60, so six rows in ten stop
        # with no pair to count; 14 waves left 87 conditional samples
        # of the 150 _check asks for.
        pairs = await _second_token_pairs(
            nano_engine, SamplingConfig(temperature=1.0),
            waves=28, rows=64,
        )
        self._check(nano_engine, pairs)

    async def test_top_k_filtered_distribution(self, nano_engine):
        """top-k rows rejection-sample over the FILTERED p and q: the
        emitted distribution must match the top-3-renormalized target
        conditional — and never leave the top-3 support."""
        k = 3
        pairs = await _second_token_pairs(
            nano_engine, SamplingConfig(temperature=1.0, top_k=k),
            waves=28, rows=64,
        )

        def topk_mask(probs):
            kth = np.sort(probs)[-k]
            return probs >= kth

        self._check(nano_engine, pairs, filt=topk_mask)
        # Support check is exact, not statistical: conditioned on ANY
        # first token, every second token lies in that prefix's top-k.
        by_first = {}
        for t0, t1 in pairs:
            by_first.setdefault(t0, set()).add(t1)
        for t0, seconds in by_first.items():
            exact = _exact_conditional(nano_engine, [3, 1, 4, t0])
            allowed = set(np.argsort(exact)[-k:].tolist())
            assert seconds <= allowed, (t0, seconds, allowed)


class TestStatsAndSidecar:
    async def test_spec_counters_flow_to_proto(self, engine):
        from ggrmcp_tpu.rpc.pb import serving_pb2

        _, b = await _run_all(engine, [[3, 1, 4]], 8, spec=True)
        stats = b.stats()
        assert stats["spec_ticks"] == b.spec_ticks > 0
        assert stats["spec_drafted"] >= stats["spec_accepted"] >= 0
        # Loud-drift contract: every stats key is a proto field.
        resp = serving_pb2.ServingStatsResponse(**stats)
        assert resp.spec_ticks == b.spec_ticks
        # Per-tick acceptance reaches the flight recorder ring.
        ticks, _ = b.flight_snapshot(max_ticks=64)
        assert any(t.spec_drafted > 0 for t in ticks)
        assert all(
            0 <= t.spec_accepted <= t.spec_drafted for t in ticks
        )

    def test_no_draft_means_plain_tick(self, engine):
        """No draft configured: the plain tick, no draft cache."""
        b = _batcher(engine, spec=False)
        assert b._spec is False and b.dcache is None

    def test_config_rejects_bad_values(self):
        from ggrmcp_tpu.core import config as cfgmod

        cfg = cfgmod.default()
        cfg.serving.speculative_gamma = 0
        with pytest.raises(ValueError, match="speculative_gamma"):
            cfg.validate()
        cfg.serving.speculative_gamma = 4
        cfg.serving.speculative_draft = "tiny-llama"
        cfg.serving.model = "tiny-mistral"
        cfg.serving.kv_ring = True
        with pytest.raises(ValueError, match="kv_ring"):
            cfg.validate()


class TestValidation:
    """What the engine refuses at construction when a draft is named."""

    def test_embedding_draft_rejected(self):
        with pytest.raises(ValueError, match="decoder"):
            GenerationEngine(
                llama.CONFIGS["tiny-llama"],
                spec_cfg(speculative_draft="bert-tiny"),
            )

    def test_vocab_mismatch_rejected(self):
        with pytest.raises(ValueError, match="vocab"):
            GenerationEngine(
                llama.CONFIGS["tiny-llama"],
                spec_cfg(speculative_draft="llama-1b"),
            )

    def test_moe_target_rejected(self):
        from ggrmcp_tpu.models import moe

        with pytest.raises(
            ValueError,
            match=r"speculative decoding \(speculative_draft\) is not "
                  r"supported for the moe family",
        ):
            GenerationEngine(
                moe.CONFIGS["tiny-moe"],
                spec_cfg(model="tiny-moe"),
            )
