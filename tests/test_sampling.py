"""Static↔dynamic sampling parity (ISSUE 4 satellite).

`sample` (static jit-arg config) and `sample_dynamic` (traced per-row
config — the continuous-batching path) implement the same sampling
policy with different machinery: explicit masking vs one sorted-
threshold pass. The property held here: for equal configs the two
paths keep IDENTICAL token sets — the support of the sampling
distribution — across every temperature / top-k / top-p combination,
including the boundary cases (k and p both active, where top-p must be
computed over the top-k-renormalized distribution, and temperature,
which scales BEFORE the nucleus test). The grammar mask
(masked_sample_dynamic) composes with exactly these semantics, so this
net also guards constrained sampling's boundary behavior.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ggrmcp_tpu.ops.sampling import (
    SamplingConfig,
    _mask_top_k,
    _mask_top_p,
    dynamic_support_mask,
    masked_sample_dynamic,
    sample,
    sample_dynamic,
)

pytestmark = pytest.mark.grammar


def _static_support(logits: jnp.ndarray, cfg: SamplingConfig) -> np.ndarray:
    """The token set sample() can draw: replicate its exact masking
    pipeline (temperature scale → top-k → top-p) and read the finite
    entries."""
    masked = logits.astype(jnp.float32) / max(cfg.temperature, 1e-9)
    if cfg.top_k > 0:
        masked = _mask_top_k(masked, cfg.top_k)
    if cfg.top_p < 1.0:
        masked = _mask_top_p(masked, cfg.top_p)
    return np.asarray(jnp.isfinite(masked))


class TestStaticDynamicParity:
    @pytest.mark.parametrize("temperature", [0.5, 1.0, 2.3])
    @pytest.mark.parametrize("top_k", [0, 1, 3, 64])
    @pytest.mark.parametrize("top_p", [0.3, 0.6, 0.95, 1.0])
    def test_support_sets_identical(self, temperature, top_k, top_p):
        """THE parity property: equal configs → equal sampleable token
        sets, for every (t, k, p) combination."""
        logits = jax.random.normal(jax.random.PRNGKey(42), (6, 64)) * 3.0
        cfg = SamplingConfig(
            temperature=temperature, top_k=top_k, top_p=top_p
        )
        static = _static_support(logits, cfg)
        b = logits.shape[0]
        dynamic = np.asarray(dynamic_support_mask(
            logits,
            jnp.full((b,), temperature, jnp.float32),
            jnp.full((b,), top_k, jnp.int32),
            jnp.full((b,), top_p, jnp.float32),
        ))
        np.testing.assert_array_equal(
            static, dynamic,
            err_msg=f"support mismatch at t={temperature} k={top_k} "
                    f"p={top_p}",
        )

    def test_combined_k_and_p_renormalizes_within_top_k(self):
        """The boundary case the property exists for: with both active,
        top-p must act on the top-k-RENORMALIZED distribution (static
        path order). probs [0.5, 0.3, 0.2], k=2, p=0.6: renormalized
        top-2 is [0.625, 0.375], mass before token 1 is 0.625 > 0.6 →
        only token 0 survives. (Computed over the FULL distribution the
        mass before token 1 is 0.5 < 0.6 and token 1 would leak in.)"""
        probs = np.array([[0.5, 0.3, 0.2]])
        logits = jnp.asarray(np.log(probs))
        support = np.asarray(dynamic_support_mask(
            logits, jnp.ones((1,)), jnp.array([2], jnp.int32),
            jnp.array([0.6], jnp.float32),
        ))
        assert support.tolist() == [[True, False, False]]
        assert _static_support(
            logits, SamplingConfig(temperature=1.0, top_k=2, top_p=0.6)
        ).tolist() == [[True, False, False]]

    def test_sampled_tokens_land_in_static_support(self):
        """End-to-end: every token sample_dynamic actually draws lies
        in the static path's support."""
        logits = jax.random.normal(jax.random.PRNGKey(7), (4, 32)) * 2.0
        cfg = SamplingConfig(temperature=0.8, top_k=5, top_p=0.7)
        static = _static_support(logits, cfg)
        b = logits.shape[0]
        for step in range(24):
            toks = np.asarray(sample_dynamic(
                logits, jnp.arange(b, dtype=jnp.uint32), jnp.int32(step),
                jnp.full((b,), cfg.temperature),
                jnp.full((b,), cfg.top_k, jnp.int32),
                jnp.full((b,), cfg.top_p),
            ))
            for row, tok in enumerate(toks):
                assert static[row, tok], (step, row, int(tok))

    def test_greedy_matches_static(self):
        logits = jax.random.normal(jax.random.PRNGKey(3), (4, 100))
        static = sample(logits, jax.random.PRNGKey(0), SamplingConfig())
        dynamic = sample_dynamic(
            logits, jnp.zeros(4, jnp.uint32), jnp.int32(0),
            jnp.zeros(4), jnp.zeros(4, jnp.int32), jnp.ones(4),
        )
        assert static.tolist() == dynamic.tolist()


class TestMaskedSampling:
    def _tables(self, v=16):
        # Two states: state 0 accept-all, state 1 allows only tokens
        # {3, 5} (3 → state 1 self-ish advance to 0, 5 → stays 1).
        allow = np.zeros((2, v), bool)
        allow[0, :] = True
        allow[1, [3, 5]] = True
        trans = np.tile(np.arange(2, dtype=np.int32)[:, None], (1, v))
        trans[1, 3] = 0
        return jnp.asarray(allow), jnp.asarray(trans)

    def test_state0_is_numerically_transparent(self):
        """Unconstrained rows (state 0) must produce BIT-identical
        tokens to plain sample_dynamic — the mixed-batch contract."""
        allow, trans = self._tables()
        logits = jax.random.normal(jax.random.PRNGKey(5), (3, 16))
        seeds = jnp.arange(3, dtype=jnp.uint32)
        args = (seeds, jnp.int32(4), jnp.full((3,), 0.9),
                jnp.zeros(3, jnp.int32), jnp.full((3,), 0.8))
        plain = sample_dynamic(logits, *args)
        masked, nxt = masked_sample_dynamic(
            logits, *args, jnp.zeros(3, jnp.int32), allow, trans
        )
        assert plain.tolist() == masked.tolist()
        assert nxt.tolist() == [0, 0, 0]

    def test_constrained_rows_only_draw_allowed_tokens(self):
        allow, trans = self._tables()
        logits = jax.random.normal(jax.random.PRNGKey(6), (2, 16)) * 4
        for step in range(16):
            toks, nxt = masked_sample_dynamic(
                logits, jnp.arange(2, dtype=jnp.uint32), jnp.int32(step),
                jnp.full((2,), 1.0), jnp.zeros(2, jnp.int32),
                jnp.ones((2,)),
                jnp.array([1, 1], jnp.int32), allow, trans,
            )
            for tok, s in zip(toks.tolist(), nxt.tolist()):
                assert tok in (3, 5)
                assert s == (0 if tok == 3 else 1)

    def test_greedy_respects_mask(self):
        """Greedy (temperature 0) must argmax over the ALLOWED set even
        when the global argmax is disallowed."""
        allow, trans = self._tables()
        logits = np.full((1, 16), -1.0, np.float32)
        logits[0, 7] = 10.0   # global argmax, disallowed in state 1
        logits[0, 5] = 1.0
        toks, _ = masked_sample_dynamic(
            jnp.asarray(logits), jnp.zeros(1, jnp.uint32), jnp.int32(0),
            jnp.zeros((1,)), jnp.zeros(1, jnp.int32), jnp.ones((1,)),
            jnp.array([1], jnp.int32), allow, trans,
        )
        assert toks.tolist() == [5]


# ---------------------------------------------------------------------------
# The gated sampler (PR 40) against the ungated formulas, bit for bit
# ---------------------------------------------------------------------------


def _ref_support(logits, temperature, top_k, top_p):
    """dynamic_support_mask as it stood before the gates: the sort, the
    softmax and the cumsum for every row of every call."""
    logits = logits.astype(jnp.float32)
    v = logits.shape[-1]
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    sorted_logits = jnp.sort(scaled, axis=-1)[..., ::-1]
    rank = jnp.arange(v)[None, :]
    k = jnp.where(top_k[:, None] > 0, top_k[:, None], v)
    keep_k = rank < k
    probs = jax.nn.softmax(
        jnp.where(keep_k, sorted_logits, -jnp.inf), axis=-1
    )
    cumulative = jnp.cumsum(probs, axis=-1)
    keep_p = (
        (cumulative - probs) < jnp.minimum(top_p, 1.0)[:, None]
    ) | (top_p[:, None] >= 1.0)
    keep = (keep_k & keep_p).at[:, 0].set(True)
    kept_count = keep.sum(axis=-1, keepdims=True)
    threshold = jnp.take_along_axis(sorted_logits, kept_count - 1, axis=-1)
    return scaled >= threshold


def _ref_sample(logits, seeds, step, temperature, top_k, top_p):
    logits = logits.astype(jnp.float32)
    support = _ref_support(logits, temperature, top_k, top_p)
    scaled = jnp.where(
        support, logits / jnp.maximum(temperature, 1e-6)[:, None], -jnp.inf
    )
    u = jax.vmap(lambda seed: jax.random.uniform(
        jax.random.fold_in(jax.random.PRNGKey(seed), step), ()
    ))(seeds)
    cdf = jnp.cumsum(jax.nn.softmax(scaled, axis=-1), axis=-1)
    sampled = jnp.sum(cdf < u[:, None] * cdf[:, -1:], axis=-1)
    greedy = jnp.argmax(logits, axis=-1)
    return jnp.where(temperature <= 0.0, greedy, sampled).astype(jnp.int32)


def _ref_masked(logits, seeds, step, temperature, top_k, top_p,
                state, allow, trans):
    masked = jnp.where(allow[state], logits.astype(jnp.float32), -jnp.inf)
    tokens = _ref_sample(masked, seeds, step, temperature, top_k, top_p)
    nxt = jnp.take_along_axis(trans[state], tokens[:, None], axis=-1)[:, 0]
    return tokens, nxt


GATE_B, GATE_V, GATE_S = 4, 192, 5
SAMPLING = np.array([0.7, 1.0, 1.3, 0.9], np.float32)
NO_K, NO_P = np.zeros(GATE_B, np.int32), np.ones(GATE_B, np.float32)
# name -> (temperature, top_k, top_p, state, live): the rows' own
# parameters, which is all the gates read.
GATE_CASES = {
    "all_greedy": (np.zeros(GATE_B, np.float32), NO_K, NO_P, None, None),
    "greedy_rows_that_set_top_k_and_top_p": (
        np.zeros(GATE_B, np.float32), np.array([5, 0, 3, 0], np.int32),
        np.array([0.5, 1.0, 0.9, 0.3], np.float32), None, None),
    "all_sampling_without_top_k_or_top_p": (SAMPLING, NO_K, NO_P, None, None),
    "top_k_only": (SAMPLING, np.array([5, 0, 3, 8], np.int32), NO_P,
                   None, None),
    "top_p_only": (SAMPLING, NO_K,
                   np.array([0.5, 0.9, 1.0, 0.3], np.float32), None, None),
    "one_sampling_row_among_greedy": (
        np.array([0.0, 0.0, 0.8, 0.0], np.float32),
        np.array([0, 0, 4, 0], np.int32),
        np.array([1.0, 1.0, 0.7, 1.0], np.float32), None, None),
    "one_plain_sampling_row_among_greedy": (
        np.array([0.0, 1.1, 0.0, 0.0], np.float32), NO_K, NO_P, None, None),
    "all_state_0": (
        np.array([0.0, 0.8, 0.0, 1.2], np.float32),
        np.array([0, 4, 0, 0], np.int32), NO_P,
        np.zeros(GATE_B, np.int32), None),
    "all_state_0_all_greedy": (
        np.zeros(GATE_B, np.float32), NO_K, NO_P,
        np.zeros(GATE_B, np.int32), None),
    "one_constrained_row_among_state_0": (
        np.array([0.0, 0.8, 0.0, 1.2], np.float32),
        np.array([0, 4, 0, 0], np.int32), NO_P,
        np.array([0, 2, 0, 0], np.int32), None),
    "one_constrained_greedy_row_among_state_0": (
        np.zeros(GATE_B, np.float32), NO_K, NO_P,
        np.array([0, 0, 3, 0], np.int32), None),
    "every_row_constrained": (
        SAMPLING, NO_K, np.array([0.9, 1.0, 0.6, 1.0], np.float32),
        np.array([1, 2, 3, 4], np.int32), None),
    "parked_row_with_a_stale_state_among_live_state_0": (
        np.array([0.0, 0.8, 0.0, 0.0], np.float32), NO_K, NO_P,
        np.array([0, 0, 3, 0], np.int32),
        np.array([True, True, False, True])),
    "parked_stale_row_beside_a_live_constrained_one": (
        np.zeros(GATE_B, np.float32), NO_K, NO_P,
        np.array([0, 2, 3, 0], np.int32),
        np.array([True, True, False, True])),
}


def _gate_tables():
    """State 0 as grammar/runtime.py lays it out (allow all, go to 0);
    every other state allows a random third of the vocabulary and moves
    to a random state."""
    rng = np.random.default_rng(40)
    allow = rng.random((GATE_S, GATE_V)) < 0.33
    allow[:, 7] = True  # never an empty row
    allow[0] = True
    trans = rng.integers(0, GATE_S, (GATE_S, GATE_V)).astype(np.int32)
    trans[0] = 0
    return jnp.asarray(allow), jnp.asarray(trans)


def _vocab_sharded(logits):
    """The logits as tensor-parallel serving hands them to the sampler:
    columns split over a 2-device tensor mesh (tests/test_tp.py)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(jax.devices()[:2]), ("tensor",))
    return jax.device_put(
        logits, NamedSharding(mesh, PartitionSpec(None, "tensor"))
    )


class TestGatedSamplerIsTheUngatedOne:
    @pytest.mark.parametrize("sharded", [False, True],
                             ids=["one_device", "tensor_mesh"])
    @pytest.mark.parametrize("case", sorted(GATE_CASES))
    def test_bit_for_bit(self, case, sharded):
        """Tokens and next states of the gated sampler are the ungated
        formulas', whatever branch its rows' parameters select, on one
        device and with the logits vocabulary-sharded."""
        temperature, top_k, top_p, state, live = GATE_CASES[case]
        allow, trans = _gate_tables()
        seeds = jnp.arange(11, 11 + GATE_B, dtype=jnp.uint32)
        params = tuple(map(jnp.asarray, (temperature, top_k, top_p)))
        plain = jax.jit(sample_dynamic)
        masked = jax.jit(masked_sample_dynamic)
        for step in range(6):
            logits = jax.random.normal(
                jax.random.PRNGKey(100 + step), (GATE_B, GATE_V)) * 3.0
            given = _vocab_sharded(logits) if sharded else logits
            if state is None:
                np.testing.assert_array_equal(
                    np.asarray(plain(given, seeds, step, *params)),
                    np.asarray(_ref_sample(logits, seeds, step, *params)),
                )
                continue
            state_j = jnp.asarray(state)
            want_tok, want_nxt = map(np.asarray, _ref_masked(
                logits, seeds, step, *params, state_j, allow, trans))
            tok, nxt = map(np.asarray, masked(
                given, seeds, step, *params, state_j, allow, trans,
                live=None if live is None else jnp.asarray(live)))
            rows = np.ones(GATE_B, bool) if live is None else live
            np.testing.assert_array_equal(tok[rows], want_tok[rows])
            np.testing.assert_array_equal(nxt[rows], want_nxt[rows])
            if live is not None and not (state != 0)[live].any():
                # No live row holds a state: the parked row kept its
                # stale one (its token is junk the host drops).
                np.testing.assert_array_equal(nxt[~live], state[~live])

    def test_support_of_sampling_rows_is_the_ungated_support(self):
        """dynamic_support_mask as its callers see it: every
        SAMPLING row's support is the sorted threshold's, gate open or
        shut. A greedy row's is not compared: nothing reads it."""
        logits = jax.random.normal(jax.random.PRNGKey(9), (GATE_B, GATE_V))
        for case, (temperature, top_k, top_p, _, _) in GATE_CASES.items():
            params = tuple(map(jnp.asarray, (temperature, top_k, top_p)))
            rows = temperature > 0
            np.testing.assert_array_equal(
                np.asarray(dynamic_support_mask(logits, *params))[rows],
                np.asarray(_ref_support(logits, *params))[rows],
                err_msg=case,
            )


# ---------------------------------------------------------------------------
# Structure: the vocabulary-wide work sits inside conditionals
# ---------------------------------------------------------------------------


def _primitives(jaxpr, into_cond, table_shape=None):
    """Names of the primitives of `jaxpr` and of every jaxpr nested in
    it (jit, scan, while, custom calls), descending into a `cond`'s
    branches only when `into_cond`. A gather whose operand has
    `table_shape` is reported as "table_gather"."""
    found = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "gather" and table_shape is not None and (
            eqn.invars[0].aval.shape == table_shape
        ):
            name = "table_gather"
        found.append(name)
        if name == "cond" and not into_cond:
            continue
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else (param,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    found += _primitives(inner, into_cond, table_shape)
    return found


VOCABULARY_WIDE = {"sort", "cumsum", "table_gather"}


class TestVocabularyWideWorkIsConditional:
    def _args(self):
        allow, trans = _gate_tables()
        return (
            jnp.zeros((GATE_B, GATE_V)), jnp.zeros(GATE_B, jnp.uint32),
            jnp.int32(0), jnp.zeros(GATE_B), jnp.zeros(GATE_B, jnp.int32),
            jnp.ones(GATE_B),
        ), (jnp.zeros(GATE_B, jnp.int32), allow, trans)

    def test_sample_dynamic(self):
        args, _ = self._args()
        jaxpr = jax.make_jaxpr(sample_dynamic)(*args).jaxpr
        assert not VOCABULARY_WIDE & set(_primitives(jaxpr, False))
        # ... and the walker does see them once it enters the branches.
        assert {"sort", "cumsum"} <= set(_primitives(jaxpr, True))

    def test_masked_sample_dynamic(self):
        args, grammar = self._args()
        shape = (GATE_S, GATE_V)
        jaxpr = jax.make_jaxpr(masked_sample_dynamic)(*args, *grammar).jaxpr
        assert not VOCABULARY_WIDE & set(_primitives(jaxpr, False, shape))
        inside = _primitives(jaxpr, True, shape)
        assert VOCABULARY_WIDE <= set(inside)
        assert inside.count("table_gather") == 2  # allow[state], trans[state]

    def test_the_ticks_scan_body(self):
        """The batcher's tick as it is jitted: nothing vocabulary-wide
        outside a conditional anywhere in the program, the scan's body
        included, so a later edit cannot put it back unseen."""
        from ggrmcp_tpu.core.config import BatchingConfig, ServingConfig
        from ggrmcp_tpu.models import llama
        from ggrmcp_tpu.serving.batching import ContinuousBatcher
        from ggrmcp_tpu.serving.engine import GenerationEngine

        engine = GenerationEngine(llama.CONFIGS["tiny-llama"], ServingConfig())
        b = 4
        batcher = ContinuousBatcher(engine, BatchingConfig(
            max_batch_size=b, kv_cache_max_seq=64, decode_steps_per_tick=2,
        ))
        allow, trans = batcher._grammar_tables()
        jaxpr = jax.make_jaxpr(batcher._tick_impl)(
            engine.params, jnp.zeros((b,), jnp.int32), batcher.cache,
            jnp.asarray(batcher.seeds), jnp.int32(0),
            jnp.asarray(batcher.temps), jnp.asarray(batcher.top_ks),
            jnp.asarray(batcher.top_ps), jnp.zeros((b,), bool),
            jnp.zeros((b,), jnp.int32), jnp.zeros((b,), jnp.int32),
            allow, trans,
        ).jaxpr
        assert "scan" in _primitives(jaxpr, False)
        outside = _primitives(jaxpr, False, trans.shape)
        assert not VOCABULARY_WIDE & set(outside)
        assert VOCABULARY_WIDE <= set(_primitives(jaxpr, True, trans.shape))
