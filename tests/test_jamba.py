"""The jamba family (models/jamba.py: runs of Mamba-1 state-space layers
around attention layers without rotary; a row's recurrent state in a
pool beside the pages, snapshots on page chain keys) at its tiny preset
(one whole period: Mamba, Mamba, attention, Mamba; float32), against the
benchmark's plain reference (benchmark/reference_jamba.py, which shares
no code with it). Comparisons are of logits unless the batcher is in
the way, where greedy tokens are compared with the engine's own
uncached generate."""

import asyncio
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ggrmcp_tpu.core.config import (
    BatchingConfig,
    LoraConfig,
    MeshConfig,
    SchedulerConfig,
    ServingConfig,
    SloConfig,
)
from ggrmcp_tpu.models import family_module, family_name, get_model, llama
from ggrmcp_tpu.models import jamba as J
from ggrmcp_tpu.ops import ssm
from ggrmcp_tpu.ops.sampling import SamplingConfig
from ggrmcp_tpu.serving.batching import ContinuousBatcher
from ggrmcp_tpu.serving.engine import _UNSUPPORTED, GenerationEngine
from ggrmcp_tpu.utils import failpoints

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmark import reference_jamba as R  # noqa: E402

CFG = J.CONFIGS["tiny-jamba"]
with open(os.path.join(
        ROOT, "tests", "benchmark", "rehearsal_jamba", "benchmark",
        "configs", "tiny-jamba-cpu.json")) as f:
    REF_MODEL = json.load(f)

# float32 on both sides, the same operations in another order (the
# program scans a chunk in blocks and walks the keys in blocks; the
# reference scans a token at a time and takes one softmax): logits of
# magnitude ~1 agree to ~2e-6.
ATOL = 2e-4
GREEDY = SamplingConfig(temperature=0.0)


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: J.init_params(k, CFG))(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def ref():
    return R.to_host(jax, REF_MODEL, R.family_init_weights(jax, REF_MODEL))


@pytest.fixture(scope="module")
def engine():
    return GenerationEngine(
        CFG, ServingConfig(mesh=MeshConfig(tensor=1, data=1)))


@pytest.fixture(autouse=True)
def clean_failpoints():
    failpoints.registry.disarm()
    yield
    failpoints.registry.disarm()


def ids_of(n, salt=0):
    rng = np.random.RandomState(salt)
    return [int(t) for t in rng.randint(3, CFG.vocab_size, n)]


def ref_logits(ref, ids):
    return np.asarray(R.logits_of(jax, REF_MODEL, ref, ids))


def paged_of(mini, rows: int, s_max: int, page: int = 16):
    """`mini`'s K/V as pages and its state as the first entries of a
    pool: what a decode tick sees."""
    n_pages = rows * s_max // page
    paged = llama.PagedKVCache.create(CFG, rows, s_max, n_pages, page)
    return paged._replace(
        k=mini.k.reshape(paged.k.shape), v=mini.v.reshape(paged.v.shape),
        table=jnp.arange(n_pages, dtype=jnp.int32).reshape(rows, -1),
        length=mini.length,
        state=tuple(pool.at[:, :rows].set(leaf)
                    for pool, leaf in zip(paged.state, mini.state)))


# ---------------------------------------------------------------------------
# The family and its forward
# ---------------------------------------------------------------------------


def test_registry_layer_order_and_the_published_count():
    name, cfg = get_model("tiny-jamba")
    assert name == "jamba" and family_module(cfg) is J
    assert family_name(cfg) == "jamba"
    # a LlamaConfig of a test is still the dense family's
    assert family_module(get_model("tiny-mistral")[1]) is llama
    assert family_name(get_model("tiny-keye")[1]) == "keye"
    assert cfg.segments == (("mamba", 0, 2), ("attn", 0), ("mamba", 2, 3))
    big = J.CONFIGS["jamba2-3b"]
    assert big.attn_layers == (7, 21) and big.mamba_layers == 26
    assert big.segments == (
        ("mamba", 0, 7), ("attn", 0), ("mamba", 7, 20), ("attn", 1),
        ("mamba", 20, 26))
    assert big.cache_layers == 2 and big.d_inner == 5120
    # ISSUE 49's count: 3,029.3M parameters, 6.06 GB in bf16
    assert abs(J.num_params(big) / 1e6 - 3029.3) < 0.1
    # a row's state: 26 x (3 x 5,120 bf16 + 16 x 5,120 float32) = 9.32 MB
    per_row = sum(
        int(np.prod(shape)) * jnp.dtype(dt).itemsize
        for shape, dt in big.row_state) * big.mamba_layers
    assert per_row == 26 * 358_400 == 9_318_400


def test_the_reference_and_the_program_share_one_recipe():
    mine = [(".".join(path), shape, scale, dtype)
            for path, shape, scale, dtype in J.leaf_recipe(CFG)]
    assert mine == [tuple(x) for x in R.leaf_recipe(REF_MODEL)]
    with open(os.path.join(
            ROOT, "benchmark", "configs", "jamba2-3b-bf16-1chip.json")) as f:
        served = json.load(f)
    big = J.CONFIGS[served["registry_model"]]
    assert [(".".join(p), s, sc, d) for p, s, sc, d in J.leaf_recipe(big)] == [
        tuple(x) for x in R.leaf_recipe(served)]


def test_forward_equals_the_reference(params, ref):
    ids = ids_of(90, salt=1)
    logits, _ = J.forward(params, CFG, jnp.asarray([ids]))
    np.testing.assert_allclose(
        np.asarray(logits[0]), ref_logits(ref, ids), atol=ATOL)


def test_chunked_prefill_then_decode_through_pool_and_pages(params, ref):
    """Three chunks of 32 into a contiguous mini cache (the state
    enters and leaves each), then 12 decode steps over the same K/V as
    pages and the same state as a pool entry: the reference's one full
    forward."""
    ids = ids_of(108, salt=2)
    cache = llama.KVCache.create(CFG, 1, 128)
    got = []
    for lo in range(0, 96, 32):
        logits, cache = J.forward(
            params, CFG, jnp.asarray([ids[lo:lo + 32]]), cache)
        got.append(np.asarray(logits[0]))
    paged = paged_of(cache, 1, 128)
    for i in range(96, 108):
        logits, paged = J.forward(params, CFG, jnp.asarray([[ids[i]]]), paged)
        got.append(np.asarray(logits[0]))
    np.testing.assert_allclose(
        np.concatenate(got), ref_logits(ref, ids), atol=ATOL)
    assert int(paged.length[0]) == 108


@pytest.mark.parametrize("tail", [1, 7, 16, 31])
def test_padding_in_a_last_chunk_leaves_the_state_where_it_was(params, tail):
    """A last chunk of `tail` real tokens padded to 32: the state that
    leaves is the state the last REAL token left (the same tokens
    unpadded), for the convolution's window and for h; and decoding on
    from it equals decoding on from the unpadded one."""
    ids = ids_of(32 + tail + 1, salt=3)

    def after(chunks, valids):
        cache = llama.KVCache.create(CFG, 1, 96)
        for chunk, valid in zip(chunks, valids):
            _, cache = J.forward(
                params, CFG, jnp.asarray([chunk]), cache,
                valid=None if valid is None else jnp.asarray([valid]))
        return cache

    exact = after([ids[:32], ids[32:32 + tail]], [None, None])
    padded = after(
        [ids[:32], ids[32:32 + tail] + [0] * (32 - tail)],
        [None, [i < tail for i in range(32)]])
    for a, b in zip(exact.state, padded.state):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    padded = padded._replace(length=exact.length)
    one, _ = J.forward(params, CFG, jnp.asarray([[ids[-1]]]), exact)
    two, _ = J.forward(params, CFG, jnp.asarray([[ids[-1]]]), padded)
    np.testing.assert_allclose(np.asarray(one), np.asarray(two), atol=1e-5)


def test_rows_of_different_starts_and_lengths_in_one_step(params, ref):
    """One [3, 32] step whose rows start at 0, 16 and 48 and hold 32, 9
    and 20 real tokens: each row's logits are the reference's at its
    own positions, whatever its neighbours do."""
    seqs = [ids_of(32, salt=10), ids_of(25, salt=11), ids_of(68, salt=12)]
    starts, reals = [0, 16, 48], [32, 9, 20]
    cache = llama.KVCache.create(CFG, 3, 96)
    # bring each row to its start, alone
    for row, (seq, start) in enumerate(zip(seqs, starts)):
        if start:
            one = llama.KVCache.create(CFG, 1, 96)
            _, one = J.forward(params, CFG, jnp.asarray([seq[:start]]), one)
            cache = cache._replace(
                k=cache.k.at[:, row].set(one.k[:, 0]),
                v=cache.v.at[:, row].set(one.v[:, 0]),
                length=cache.length.at[row].set(start),
                state=tuple(pool.at[:, row].set(leaf[:, 0])
                            for pool, leaf in zip(cache.state, one.state)))
    step = np.zeros((3, 32), np.int32)
    for row, (seq, start, real) in enumerate(zip(seqs, starts, reals)):
        step[row, :real] = seq[start:start + real]
    valid = np.arange(32)[None, :] < np.asarray(reals)[:, None]
    logits, _ = J.forward(
        params, CFG, jnp.asarray(step), cache, valid=jnp.asarray(valid))
    for row, (seq, start, real) in enumerate(zip(seqs, starts, reals)):
        want = ref_logits(ref, seq[:start + real])[start:]
        np.testing.assert_allclose(
            np.asarray(logits[row, :real]), want, atol=ATOL)


def test_a_captured_state_restored_equals_the_cold_run(params):
    """The state the scan passes at a page boundary, copied into a pool
    entry (`capture`), then restored into another row's entry
    (`restore_rows`): a suffix computed from it gives the logits of the
    same tokens computed cold. A position the step does not pass, or
    one off a block boundary, captures nothing."""
    ids = ids_of(64, salt=4)
    pool = llama.zero_state(CFG, 6)
    cache = llama.KVCache.create(CFG, 1, 96)._replace(
        state=pool, state_rows=jnp.asarray([0]))
    capture = (jnp.asarray([[32, 40, 96]]), jnp.asarray([[3, 4, 5]]))
    cold, cache = J.forward(
        params, CFG, jnp.asarray([ids[:48]]), cache, capture=capture)
    taken = [bool(np.abs(np.asarray(cache.state[1][:, e])).sum() > 0)
             for e in (3, 4, 5)]
    assert taken == [True, False, False]
    # K/V of the first 32 positions from the cold run, the state from
    # the snapshot: the suffix 32..64
    state = J.restore_rows(cache.state, jnp.asarray([1]), jnp.asarray([3]))
    warm = llama.KVCache.create(CFG, 1, 96)._replace(
        k=cache.k, v=cache.v, length=jnp.asarray([32]), state=state,
        state_rows=jnp.asarray([1]))
    got, _ = J.forward(params, CFG, jnp.asarray([ids[32:64]]), warm)
    whole, _ = J.forward(params, CFG, jnp.asarray([ids]))
    np.testing.assert_allclose(
        np.asarray(got[0]), np.asarray(whole[0, 32:]), atol=ATOL)
    # a source of -1 is a zero state: the cold start
    zeroed = J.restore_rows(cache.state, jnp.asarray([0]), jnp.asarray([-1]))
    assert all(float(np.abs(np.asarray(p[:, 0])).sum()) == 0 for p in zeroed)
    assert float(np.abs(np.asarray(zeroed[1][:, 3])).sum()) > 0


def test_the_scan_and_the_step_agree_and_dt_zero_moves_nothing():
    rng = np.random.RandomState(0)
    b, s, c, n = 2, 40, 8, 4
    u, dt = rng.randn(b, s, c), np.abs(rng.randn(b, s, c)) * 0.1
    b_m, c_m = rng.randn(b, s, n), rng.randn(b, s, n)
    a = -np.abs(rng.randn(n, c))
    h0 = rng.randn(b, n, c).astype(np.float32)
    dt[:, 33:] = 0.0  # padding
    y, h, hs = ssm.ssm_scan(jnp.asarray(h0), *map(jnp.asarray, (u, dt)),
                            jnp.asarray(a, jnp.float32),
                            *map(jnp.asarray, (b_m, c_m)))
    hh, ys = jnp.asarray(h0), []
    for t in range(s):
        y_t, hh = ssm.ssm_step(
            hh, *(jnp.asarray(x[:, t]) for x in (u, dt)),
            jnp.asarray(a, jnp.float32), jnp.asarray(b_m[:, t]),
            jnp.asarray(c_m[:, t]))
        ys.append(y_t)
        if t == 32:
            at_33 = hh
    np.testing.assert_allclose(np.asarray(y), np.stack(ys, 1), atol=1e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hh), atol=1e-6)
    np.testing.assert_allclose(np.asarray(h), np.asarray(at_33), atol=1e-6)
    assert hs.shape == (3, b, n, c)  # 40 positions: three blocks of 16
    np.testing.assert_allclose(np.asarray(hs[-1]), np.asarray(h), atol=1e-6)


# ---------------------------------------------------------------------------
# Through the batcher: pool, pages and snapshots
# ---------------------------------------------------------------------------


async def _collect(batcher, prompt, max_new, seed=0, **kw):
    out = []
    async for ids, _ in batcher.submit(prompt, max_new, GREEDY, seed=seed, **kw):
        out.extend(ids)
    return out


def _batcher(engine, **kw):
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("kv_cache_max_seq", 256)
    kw.setdefault("paged_kv", "on")
    kw.setdefault("paged_kv_page_size", 16)
    kw.setdefault("prefill_chunk", 32)
    return ContinuousBatcher(engine, BatchingConfig(**kw))


@pytest.mark.parametrize("model, short", [
    ("tiny-jamba", 0.0),
    # h in bfloat16: 16 x 256 x 2 B of an entry's 3 x (3 + 16) x 256 x 4
    ("tiny-jamba-bf16-state", 3 * 16 * 256 * 2 / (3 * 19 * 256 * 4)),
])
def test_the_batcher_says_what_an_entry_of_its_pool_holds(
        model, short, caplog):
    """The start-up line the benchmark's check reads (`row states: ..`)
    is summed over the pool's device arrays, and against the bytes the
    configuration's widths give at its stated precisions
    (`roofline_jamba.state_bytes_per_row`) the served preset is short
    of nothing, the `-bf16-state` control of `h`'s lower half."""
    from benchmark import plugins

    check = plugins.load(
        "checks", "logit_margin_jamba", [os.path.join(ROOT, "benchmark")])
    eng = GenerationEngine(
        J.CONFIGS[model], ServingConfig(mesh=MeshConfig(tensor=1, data=1)))
    with caplog.at_level("INFO", logger="ggrmcp.serving.batching"):
        batcher = _batcher(eng)
    said = [r.getMessage() for r in caplog.records
            if r.getMessage().startswith("row states: ")]
    entries = batcher.cache.state[0].shape[1]
    per_entry = sum(x.nbytes for x in batcher.cache.state) // entries
    assert said == [m for m in said if f"{entries} entries x {per_entry} B" in m]
    assert len(said) == 1 and entries == 20
    got = check.state_bytes_short_share("\n".join(said), REF_MODEL)
    assert got == pytest.approx(short)


async def test_every_admission_path_carries_the_state(engine):
    """Short and long cold prompts, a burst (the full-pool program), a
    trickle (the single-row one), a chunked group and prefix reuse with
    a restored snapshot, twice over: greedy outputs equal the engine's
    own uncached generate, and the allocator's books balance."""
    head = ids_of(70, salt=7)
    prompts = [head + ids_of(9, salt=20 + s) for s in range(3)]
    prompts += [ids_of(150, salt=8), ids_of(10, salt=9)]
    expected, _ = engine.generate(prompts, max_new_tokens=6, seed=0)
    batcher = _batcher(engine)
    leaves = jax.tree_util.tree_leaves(batcher.cache)
    assert [x.shape for x in leaves] == [
        (1, 64, 16, 1, 32), (1, 64, 16, 1, 32), (4, 16), (4,),
        (3, 20, 3, 256), (3, 20, 16, 256)]
    batcher.start()
    try:
        waves = []
        for _ in range(2):
            waves.append(await asyncio.gather(*(
                _collect(batcher, p, 6, i) for i, p in enumerate(prompts))))
            batcher.pages.check_invariants()
        alone = await _collect(batcher, prompts[3], 6)
    finally:
        await batcher.stop()
    assert waves[0] == expected and waves[1] == expected
    assert alone == expected[3]
    stats = batcher.counter_stats()
    assert stats["state_snapshots_taken"] >= 5
    assert stats["state_snapshot_hits"] >= 6
    assert stats["state_pool_total"] == 16
    assert stats["prefill_chunk_tokens_run"] > 0
    assert batcher.cache_bytes() >= sum(x.nbytes for x in leaves[:2])


@pytest.mark.parametrize("first_len, recomputed", [(48, 16), (50, 0)])
async def test_a_second_turn_through_a_snapshot_equals_a_cold_admission(
        engine, first_len, recomputed):
    """Turn 2 sends turn 1's prompt, its output and new tokens. Turn 1
    left a snapshot at its deepest page boundary under the reuse cap
    ((len - 1) // 16 x 16) and indexed its len // 16 full pages: where
    the prompt ends on a page boundary the last full page has no
    state, and its 16 tokens are recomputed; else nothing is."""
    first = ids_of(first_len, salt=30)
    batcher = _batcher(engine)
    batcher.start()
    try:
        out1 = await _collect(batcher, first, 5)
        turn2 = first + out1 + ids_of(21, salt=31)
        got = await _collect(batcher, turn2, 6)
        stats = batcher.counter_stats()
        batcher.pages.check_invariants()
    finally:
        await batcher.stop()
    cold, _ = engine.generate([turn2], max_new_tokens=6, seed=0)
    assert got == cold[0]
    assert stats["state_snapshot_lookups"] == 2  # turn 1 matched nothing
    assert stats["state_snapshot_hits"] == 1
    assert stats["state_tokens_matched"] == first_len // 16 * 16
    assert stats["state_tokens_recomputed"] == recomputed
    assert stats["prefill_tokens_reused"] == (first_len - 1) // 16 * 16


async def test_a_shared_prompt_is_one_snapshot_under_four_sessions(engine):
    """Four sessions open on one 64-token system prompt at once: the
    first admission is cold and captures the state at 32 and 64 (the
    multiples of prefill_chunk), the other three restore the one at 64
    in the same round, in device order, and every later turn of every
    session finds its own."""
    system = ids_of(64, salt=40)
    firsts = [system + ids_of(7 + s, salt=41 + s) for s in range(4)]
    batcher = _batcher(engine)
    batcher.start()
    try:
        outs = await asyncio.gather(*(
            _collect(batcher, p, 5, i) for i, p in enumerate(firsts)))
        stats1 = batcher.counter_stats()
        seconds = [p + o + ids_of(9, salt=50 + i)
                   for i, (p, o) in enumerate(zip(firsts, outs))]
        outs2 = await asyncio.gather(*(
            _collect(batcher, p, 5, i) for i, p in enumerate(seconds)))
        stats2 = batcher.counter_stats()
        batcher.pages.check_invariants()
    finally:
        await batcher.stop()
    want1, _ = engine.generate(firsts, max_new_tokens=5, seed=0)
    want2, _ = engine.generate(seconds, max_new_tokens=5, seed=0)
    assert outs == want1 and outs2 == want2
    # 32 and 64 once, whoever else passes them; no first turn reaches a
    # further boundary (the longest is 74 tokens: (74 - 1) // 16 x 16 = 64)
    assert stats1["state_snapshots_taken"] == 2
    assert stats1["state_snapshot_hits"] == 3
    assert stats1["prefill_tokens_reused"] == 3 * 64
    # every second turn restores its session's own state or the shared one
    assert stats2["state_snapshot_hits"] == 3 + 4
    assert stats2["state_tokens_recomputed"] == 0


@pytest.mark.parametrize("rows_a_call", [1, None])
async def test_a_padding_row_never_touches_the_first_snapshots_entry(
        engine, monkeypatch, rows_a_call):
    """Four sessions on one system prompt at once: one cold row, three
    that restore. As served a row goes alone (`admission_rows` 1: no
    bucket, no padding row). Grouped (None: the whole pool a call), the
    three fill a bucket of four, whose padding row carries slot index
    B: for the pool that is entry B, the first snapshot's, which its
    zeroed restore must not reach (`_pool_rows`). Entry B is marked
    with ones beforehand (the allocator hands it out last)."""
    monkeypatch.setattr(J, "admission_rows", lambda cfg: rows_a_call)
    system = ids_of(64, salt=60)
    firsts = [system + ids_of(7 + s, salt=61 + s) for s in range(4)]
    batcher = _batcher(engine)
    b = len(batcher.slots)
    assert batcher._mini_rows == (rows_a_call or b) and b == 4
    batcher.cache = batcher.cache._replace(state=tuple(
        pool.at[:, b].set(1) for pool in batcher.cache.state))
    batcher.start()
    try:
        outs = await asyncio.gather(*(
            _collect(batcher, p, 5, i) for i, p in enumerate(firsts)))
        batcher.pages.check_invariants()
    finally:
        await batcher.stop()
    want, _ = engine.generate(firsts, max_new_tokens=5, seed=0)
    assert outs == want
    assert batcher.counter_stats()["state_snapshot_hits"] == 3
    for pool in batcher.cache.state:
        assert (np.asarray(pool[:, b], np.float32) == 1).all()


async def test_an_evicted_snapshot_is_a_miss_that_recomputes_and_is_right(
        engine):
    """One slot, so four snapshot entries: six distinct prompts of two
    captures each evict the first prompt's, least recently used first.
    Asked again, its pages still match but no state hangs on them: the
    tokens run again, and the answer is the cold one."""
    prompts = [ids_of(70, salt=60 + s) for s in range(6)]
    batcher = _batcher(engine, max_batch_size=1, paged_kv_pages=64)
    assert batcher.pages.state_entries == 4
    batcher.start()
    try:
        outs = [await _collect(batcher, p, 4) for p in prompts]
        before = batcher.counter_stats()
        again = await _collect(batcher, prompts[0], 4)
        after = batcher.counter_stats()
        batcher.pages.check_invariants()
    finally:
        await batcher.stop()
    want, _ = engine.generate(prompts, max_new_tokens=4, seed=0)
    assert outs == want and again == want[0]
    assert before["state_snapshots_taken"] == 12
    assert before["state_snapshot_evictions"] == 8
    assert before["state_pool_in_use"] == 4
    assert after["state_snapshot_lookups"] == before["state_snapshot_lookups"] + 1
    assert after["state_snapshot_hits"] == before["state_snapshot_hits"]
    assert (after["state_tokens_recomputed"]
            - before["state_tokens_recomputed"]) == 64
    assert after["prefill_tokens_reused"] == before["prefill_tokens_reused"]


async def test_the_no_snapshot_fault_leaves_the_state_zero_and_shows(engine):
    """The benchmark's second control: a restore that leaves the slot's
    state zero gives other tokens than the cold admission."""
    first = ids_of(115, salt=70)
    batcher = _batcher(engine)
    batcher.start()
    try:
        out1 = await _collect(batcher, first, 5)
        turn2 = first + out1 + ids_of(2, salt=71)
        failpoints.registry.arm("state_restore_zero", every=1)
        got = await _collect(batcher, turn2, 16)
    finally:
        await batcher.stop()
    cold, _ = engine.generate([turn2], max_new_tokens=16, seed=0)
    assert got != cold[0]


async def test_a_failed_tick_is_replayed_from_what_the_chain_still_has(engine):
    """A tick fails once mid-decode: the arena and the pool are rebuilt
    from zeros, the allocator forgets every page and snapshot, and the
    live rows are replayed (prompt + what they had emitted) through a
    cold admission. The tokens are those of a run without the fault."""
    prompts = [ids_of(40, salt=90), ids_of(70, salt=91)]
    want, _ = engine.generate(prompts, max_new_tokens=12, seed=0)
    batcher = _batcher(engine)
    batcher.start()
    try:
        failpoints.registry.arm("tick_fail", every=4, times=1)
        got = await asyncio.gather(*(
            _collect(batcher, p, 12, i) for i, p in enumerate(prompts)))
        stats = batcher.counter_stats()
        batcher.pages.check_invariants()
    finally:
        await batcher.stop()
    assert got == want
    assert stats["replayed_requests"] >= 1


async def test_preempt_and_resume_rebuild_the_state(engine):
    """A background request is preempted mid-decode for an interactive
    one and resumed: its pages were parked with it, its state is
    restored from the deepest snapshot its chain still has and the rest
    recomputed; the tokens are those of a run never preempted."""
    import dataclasses
    import time

    serving = dataclasses.replace(
        engine.serving,
        slo=SloConfig(
            default_class="background",
            classes={
                "interactive": {"ttft_p99_ms": 0.01, "tpot_p99_ms": 1e9},
                "background": {"ttft_p99_ms": 1e9, "tpot_p99_ms": 1e9}},
            burn_windows_s=[60.0, 3600.0]),
        scheduler=SchedulerConfig(enabled=True))

    class _Shim:
        def __getattr__(self, name):
            return getattr(engine, name)

    shim = _Shim()
    shim.__dict__["serving"] = serving
    victim, urgent = ids_of(40, salt=80), ids_of(20, salt=81)
    batcher = _batcher(shim, max_batch_size=1, kv_cache_max_seq=128)
    batcher.start()
    try:
        started = asyncio.get_running_loop().create_future()

        async def long_one():
            out = []
            async for ids, _ in batcher.submit(
                    victim, 40, GREEDY, qos_class="background"):
                out.extend(ids)
                if len(out) >= 3 and not started.done():
                    started.set_result(None)
            return out

        task = asyncio.create_task(long_one())
        await started
        fast = await _collect(batcher, urgent, 4, qos_class="interactive")
        slow = await task
        deadline = time.monotonic() + 30
        while batcher.sched.resumes < 1 and time.monotonic() < deadline:
            await asyncio.sleep(0.01)
        stats = batcher.counter_stats()
        batcher.pages.check_invariants()
    finally:
        await batcher.stop()
    want, _ = engine.generate([victim, urgent], max_new_tokens=40, seed=0)
    assert slow == want[0] and fast == want[1][:4]
    assert stats["sched_preemptions"] >= 1 and stats["sched_resumes"] >= 1
    assert stats["state_snapshot_hits"] >= 1


# ---------------------------------------------------------------------------
# What the family refuses, and what the others keep
# ---------------------------------------------------------------------------


def _serving(**kw):
    kw.setdefault("mesh", MeshConfig(tensor=1, data=1))
    return ServingConfig(**kw)


@pytest.mark.parametrize("serving, feature", [
    (_serving(lora=LoraConfig(adapters=["a"])), "lora"),
    (_serving(mesh=MeshConfig(tensor=1, data=1, stage=2)),
     "pipeline-parallel serving"),
    (_serving(kv_ring=True), "kv_ring"),
    (_serving(batching=BatchingConfig(kv_tiers=[[64, 2], [128, 2]])),
     "batching.kv_tiers"),
    (_serving(batching=BatchingConfig(paged_kv_host_bytes=1 << 20)),
     "batching.paged_kv_host_bytes"),
    (_serving(role="prefill"), "a non-mixed serving.role"),
    (_serving(quantize="int8"), "quantize"),
    (_serving(kv_cache_dtype="int8"), "kv_cache_dtype"),
    (_serving(kv_cache_dtype="fp8"), "kv_cache_dtype"),
    (_serving(batching=BatchingConfig(prefill_interleave="on")),
     "batching.prefill_interleave"),
    (_serving(mesh=MeshConfig(tensor=2, data=1)),
     "a mesh of more than one device"),
])
def test_what_cannot_carry_a_state_is_refused_by_name(serving, feature):
    with pytest.raises(ValueError) as err:
        GenerationEngine(CFG, serving)
    text = str(err.value)
    assert feature in text and "jamba family" in text and "tiny-jamba" in text
    reasons = [why for what, why in _UNSUPPORTED["jamba"].items()
               if what.startswith(feature)]
    assert len(reasons) == 1 and reasons[0] in text


@pytest.mark.parametrize("model", [
    "tiny-mistral", "tiny-mla-moe", "tiny-dsv32", "tiny-keye"])
def test_the_other_families_caches_keep_their_leaves(model):
    """`state` is empty and `state_rows` None for a family without a
    row state: its caches flatten to the leaves they always had, its
    cache-layer count is its layer count, and positional construction
    still works."""
    _, cfg = get_model(model)
    assert cfg.row_state == () and cfg.cache_layers == cfg.num_layers
    cache = llama.KVCache.create(cfg, 2, 32)
    paged = llama.PagedKVCache.create(cfg, 2, 32, 4, 8)
    assert cache.state == () and paged.state == ()
    assert cache.state_rows is None and paged.state_rows is None
    planes = len(cfg.kv_planes)
    assert len(jax.tree_util.tree_leaves(cache)) == planes + 1
    assert len(jax.tree_util.tree_leaves(paged)) == planes + 2
    assert jax.tree_util.tree_leaves(cache)[0].shape[0] == cfg.num_layers
    assert llama.KVCache(cache.k, cache.v, cache.length, cache.extra) == cache


def test_the_dense_tick_takes_the_operands_it_always_took():
    """One lowered tick of tiny-mistral: the program's operands are the
    weights, the per-slot vectors and the four leaves of the paged
    cache (K, V, table, lengths), and nothing of a state pool. The
    parent's text cannot be lowered beside it here (the fields cannot
    be taken off a NamedTuple at run time): what is held is that the
    new fields add no operand and the batcher builds no state plan."""
    _, cfg = get_model("tiny-mistral")
    eng = GenerationEngine(cfg, _serving())
    batcher = ContinuousBatcher(eng, BatchingConfig(
        max_batch_size=2, kv_cache_max_seq=64, paged_kv="on",
        paged_kv_page_size=8))
    assert not batcher._row_state and batcher.pages.state_entries == 0
    assert batcher._state_io([], 2) is None
    b = 2
    g_allow, g_trans = batcher._grammar_tables()
    args = (
        eng.params, jnp.zeros((b,), jnp.int32), batcher.cache,
        jnp.zeros((b,), jnp.uint32), jnp.int32(0), jnp.zeros((b,)),
        jnp.zeros((b,), jnp.int32), jnp.ones((b,)), jnp.zeros((b,), bool),
        jnp.zeros((b,), jnp.int32), jnp.zeros((b,), jnp.int32),
        g_allow, g_trans)
    lowered = batcher._tick.lower(*args)
    n_in = len(jax.tree_util.tree_leaves(args))
    assert n_in == len(jax.tree_util.tree_leaves(eng.params)) + 4 + 11
    assert len(jax.tree_util.tree_leaves(lowered.args_info)) == n_in
    assert "ssm" not in lowered.as_text()
