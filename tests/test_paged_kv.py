"""Paged KV cache tests (batching.paged_kv, docs/paged_kv.md).

The contract under test, in order of importance:

1. BIT-IDENTITY — greedy outputs with paged_kv=on are byte-equal to
   the contiguous path (and to the engine's uncached generate) across
   every admission path (fused / chunked / paged-prefix / interleaved),
   under injected tick faults (chaos replay), and with grammar rows
   in the batch. The contiguous path stays the off-mode
   precisely so this is provable.
2. SHARING — same-preamble admissions reference the SAME physical
   pages (refcounts, kv_pages_shared), divergent pages copy-on-write,
   and a working set that outgrows refcounts survives via LRU reuse of
   refcount-0 pages (test_paged_holds_hit_rate_at_3x_working_set).
3. SAFETY — page-pool exhaustion sheds typed ("overloaded" →
   RESOURCE_EXHAUSTED → 429, the PR-2 ladder) and never corrupts
   resident block tables; compile counts stay stable for mixed
   shared/unshared batches; the host allocator's bookkeeping is exact.

Marker `paged` (tier-1, `make test-paged`).
"""

import asyncio
import contextlib

import numpy as np
import pytest

from ggrmcp_tpu.core.config import (
    BatchingConfig,
    Config,
    MeshConfig,
    ServingConfig,
)
from ggrmcp_tpu.grammar import compile_schema
from ggrmcp_tpu.models import llama
from ggrmcp_tpu.ops.sampling import SamplingConfig
from ggrmcp_tpu.serving.batching import ContinuousBatcher
from ggrmcp_tpu.serving.engine import GenerationEngine
from ggrmcp_tpu.serving.pages import (
    PageAdmission,
    PageAllocator,
    PageExhaustedError,
    WindowPages,
    window_pages_per_slot,
)
from ggrmcp_tpu.serving.tiered import TieredBatcher
from ggrmcp_tpu.utils import failpoints

pytestmark = pytest.mark.paged


@pytest.fixture(scope="module")
def engine():
    return GenerationEngine(
        llama.CONFIGS["tiny-llama"],
        ServingConfig(mesh=MeshConfig(tensor=2, data=0)),
    )


def paged_cfg(**kw) -> BatchingConfig:
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("kv_cache_max_seq", 256)
    kw.setdefault("paged_kv", "on")
    kw.setdefault("paged_kv_page_size", 8)
    return BatchingConfig(**kw)


def flat_cfg(**kw) -> BatchingConfig:
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("kv_cache_max_seq", 256)
    return BatchingConfig(**kw)


def prompt_of(n: int, salt: int = 0) -> list[int]:
    return [(i * 13 + salt * 71 + 5) % 500 + 1 for i in range(n)]


async def collect(batcher, prompt, max_new, seed=0, sampling=None,
                  grammar=None):
    out: list[int] = []
    reason = None
    async for ids, r in batcher.submit(
        prompt, max_new, sampling or SamplingConfig(temperature=0.0),
        seed=seed, grammar=grammar,
    ):
        out.extend(ids)
        reason = r
    return out, reason


async def run_wave(engine, cfg, prompts, max_new=5):
    """(outputs, batcher-after-stop) for a concurrent greedy wave."""
    batcher = ContinuousBatcher(engine, cfg)
    batcher.start()
    try:
        results = await asyncio.gather(*(
            collect(batcher, p, max_new, seed=i)
            for i, p in enumerate(prompts)
        ))
    finally:
        await batcher.stop()
    for out, reason in results:
        assert reason in ("stop", "length") and len(out) >= 1
    return [out for out, _ in results], batcher


# ---------------------------------------------------------------------------
# Host allocator (no device)
# ---------------------------------------------------------------------------


class TestPageAllocator:
    def test_cold_admit_allocates_exclusive_pages(self):
        alloc = PageAllocator(16, 4, slots=2, table_width=8)
        adm = alloc.admit(0, list(range(10)), need_len=14)
        assert isinstance(adm, PageAdmission)
        assert adm.merge_start == 0 and adm.scan_start == 0
        assert alloc.in_use() == 4  # ceil(14 / 4)
        assert (alloc.tables[0][:4] != alloc.sentinel).all()
        assert (alloc.tables[0][4:] == alloc.sentinel).all()
        assert alloc.misses == 1 and alloc.hits == 0

    def test_register_then_share_refcounts(self):
        alloc = PageAllocator(16, 4, slots=3, table_width=8)
        prompt = list(range(11))  # 2 full pages (8 tokens) + tail
        alloc.admit(0, prompt, need_len=12)
        alloc.register(0, prompt)
        adm = alloc.admit(1, prompt, need_len=12)
        # Both full pages shared, refcount 2; the tail page is private.
        assert adm.merge_start == 8 and adm.pages_shared == 2
        assert alloc.shared() == 2
        assert (alloc.tables[0][:2] == alloc.tables[1][:2]).all()
        assert alloc.tables[0][2] != alloc.tables[1][2]
        assert alloc.hits == 1

    def test_cow_on_divergent_page(self):
        alloc = PageAllocator(16, 4, slots=2, table_width=8)
        a = [1, 2, 3, 4, 5, 6, 7, 8, 9]
        alloc.admit(0, a, need_len=10)
        alloc.register(0, a)  # indexes pages [1..4] and [5..8]
        # Diverges inside the SECOND page: shares page 0, CoW page 1.
        b = [1, 2, 3, 4, 5, 6, 99, 98, 97]
        adm = alloc.admit(1, b, need_len=10)
        assert adm.merge_start == 4  # one full shared page
        assert adm.scan_start == 6  # + 2 CoW-overlap tokens [5, 6]
        assert adm.gather_row[1] == alloc.tables[0][1]  # source page
        assert adm.gather_row[1] != alloc.tables[1][1]  # != own page
        assert alloc.cow_copies == 1

    def test_free_keeps_indexed_pages_evictable_then_lru_evicts(self):
        alloc = PageAllocator(4, 4, slots=2, table_width=4)
        p1 = list(range(9))
        alloc.admit(0, p1, need_len=9)  # 3 pages
        alloc.register(0, p1)
        alloc.free_slot(0)
        assert alloc.in_use() == 2  # 2 indexed pages stay resident
        # A re-admission still hits the cached pages...
        adm = alloc.admit(0, p1, need_len=9)
        assert adm.pages_shared == 2
        alloc.free_slot(0)
        # ...until allocation pressure LRU-evicts them.
        alloc.admit(1, list(range(100, 116)), need_len=16)  # all 4 pages
        assert alloc.in_use() == 4
        alloc.free_slot(1)
        assert alloc.admit(0, p1, need_len=9).pages_shared == 0

    def test_exhaustion_is_all_or_nothing(self):
        alloc = PageAllocator(4, 4, slots=2, table_width=8)
        alloc.admit(0, list(range(10)), need_len=12)  # 3 of 4 pages
        before = alloc.tables.copy()
        with pytest.raises(PageExhaustedError):
            alloc.admit(1, list(range(50, 60)), need_len=12)  # needs 3
        assert (alloc.tables == before).all()  # nothing mutated
        assert alloc.in_use() == 3

    def test_reset_forgets_everything(self):
        alloc = PageAllocator(8, 4, slots=2, table_width=4)
        p = list(range(9))
        alloc.admit(0, p, need_len=9)
        alloc.register(0, p)
        alloc.reset()
        assert alloc.in_use() == 0
        assert (alloc.tables == alloc.sentinel).all()
        assert alloc.admit(0, p, need_len=9).pages_shared == 0

    def test_share_false_consults_nothing(self):
        alloc = PageAllocator(16, 4, slots=2, table_width=8)
        p = list(range(11))
        alloc.admit(0, p, need_len=12)
        alloc.register(0, p)
        adm = alloc.admit(1, p, need_len=12, share=False)
        assert adm.merge_start == 0 and adm.scan_start == 0
        assert alloc.shared() == 0


class TestStateSnapshots:
    """State beside pages (docs/paged_kv.md): snapshots of a row's
    recurrent state hung on page chain keys, host side only. 2 slots,
    so entries 2..5 of the pool are the snapshots'."""

    def _alloc(self, **kw):
        kw.setdefault("state_entries", 4)
        return PageAllocator(32, 4, slots=2, table_width=16, **kw)

    def test_no_state_entries_means_no_snapshot_work(self):
        alloc = PageAllocator(16, 4, slots=2, table_width=8)
        p = list(range(11))
        adm = alloc.admit(0, p, need_len=12)
        assert adm.state_src == -1
        assert alloc.plan_snapshots(0, p, 0, every=8) == []
        alloc.register(0, p)
        assert alloc.stats()["state_pool_total"] == 0
        assert alloc.stats()["state_snapshot_lookups"] == 0
        alloc.check_invariants()

    @pytest.mark.parametrize("state_entries, hashed", [(4, 6), (0, 1 + 6)])
    def test_an_admission_hashes_each_page_of_its_prompt_once(
            self, monkeypatch, state_entries, hashed):
        """admit, plan_snapshots and register walk the same prompt:
        where rows keep a state the slot's walk is kept between them
        (24 tokens: 6 pages, 5 under the reuse cap); a family without
        one walks as it always did (admit to the first miss, register
        every page), and a later prompt on the slot starts anew."""
        alloc = self._alloc(state_entries=state_entries)
        calls = []
        chain = PageAllocator._chain
        monkeypatch.setattr(
            PageAllocator, "_chain",
            staticmethod(lambda key, toks: calls.append(1) or chain(key, toks)))
        p = list(range(24))
        alloc.admit(0, p, need_len=28)
        alloc.plan_snapshots(0, p, 0, every=8)
        alloc.register(0, p)
        assert len(calls) == hashed
        if not state_entries:
            return
        alloc.check_invariants()
        # the same slot, another prompt that shares 9 tokens: nothing
        # of the first walk is taken for it
        alloc.free_slot(0)
        del calls[:]
        q = p[:9] + [700 + i for i in range(15)]
        adm = alloc.admit(0, q, need_len=28)
        assert adm.scan_start == 8 and len(calls) == 5
        alloc.register(0, q)
        assert len(calls) == 6
        alloc.check_invariants()

    def test_plan_names_the_multiples_and_the_deepest_boundary(self):
        alloc = self._alloc()
        p = list(range(23))  # reuse cap 22: boundaries 4 .. 20
        alloc.admit(0, p, need_len=24)
        plan = alloc.plan_snapshots(0, p, 0, every=8)
        # (a) 8 and 16, (b) 22 // 4 x 4 = 20; distinct pool entries
        assert [pos for pos, _ in plan] == [8, 16, 20]
        assert sorted(e for _, e in plan) == sorted(set(e for _, e in plan))
        assert all(2 <= e < 6 for _, e in plan)
        # nothing is indexed until register has run for the slot
        assert alloc.stats()["state_snapshots_taken"] == 0
        assert alloc.stats()["state_pool_in_use"] == 3
        alloc.check_invariants()
        alloc.register(0, p)
        assert alloc.stats()["state_snapshots_taken"] == 3
        alloc.check_invariants()
        # from a start past 8, and where 16 and 20 are held: nothing new
        alloc.admit(1, p, need_len=24)
        assert alloc.plan_snapshots(1, p, 8, every=8) == []

    def test_lookup_is_clipped_to_the_deepest_page_with_a_snapshot(self):
        alloc = self._alloc()
        p = list(range(24))  # 6 full pages; cap 23: snapshots at 8, 16, 20
        alloc.admit(0, p, need_len=24)
        alloc.plan_snapshots(0, p, 0, every=8)
        alloc.register(0, p)
        # the same prompt again: 5 pages match under the cap (20 tokens)
        adm = alloc.admit(1, p, need_len=28)
        assert adm.scan_start == adm.merge_start == 20 and adm.state_src >= 2
        assert alloc.stats()["state_tokens_recomputed"] == 0
        alloc.free_slot(1)
        # a prompt that leaves after 14 tokens: 3 pages match (12), the
        # deepest state under them is at 8: one page runs again, into
        # a page of the slot's own, and no divergent page is copied
        q = p[:14] + [900 + i for i in range(10)]
        adm = alloc.admit(1, q, need_len=28)
        assert adm.scan_start == adm.merge_start == 8 and adm.pages_shared == 2
        assert alloc.tables[1][2] != alloc.tables[0][2]
        assert alloc.cow_copies == 0
        st = alloc.stats()
        assert st["state_snapshot_lookups"] == 3  # the cold one too
        assert st["state_snapshot_hits"] == 2
        assert st["state_tokens_matched"] == 20 + 12
        assert st["state_tokens_recomputed"] == 4
        assert st["paged_pages_reused"] == 5 + 2
        alloc.check_invariants()

    def test_pages_without_a_snapshot_are_not_reused_at_all(self):
        alloc = self._alloc()
        p = list(range(24))
        alloc.admit(0, p, need_len=24)
        alloc.register(0, p)  # pages indexed, no state captured
        adm = alloc.admit(1, p, need_len=24)
        assert (adm.scan_start, adm.pages_shared, adm.state_src) == (0, 0, -1)
        assert alloc.stats()["state_tokens_recomputed"] == 20
        alloc.check_invariants()

    def test_lru_eviction_spares_what_the_round_reads(self):
        alloc = self._alloc()
        a, b = list(range(24)), list(range(100, 124))
        alloc.admit(0, a, need_len=24)
        alloc.plan_snapshots(0, a, 0, every=8)  # 8, 16, 20
        alloc.register(0, a)
        alloc.free_slot(0)
        # this round: slot 0 restores a's deepest snapshot (pinned) ...
        adm = alloc.admit(0, a, need_len=28)
        pinned = adm.state_src
        # ... and slot 1's cold prompt wants three entries of four: one
        # is free, two come from a's least recently used, never the pin
        alloc.admit(1, b, need_len=24)
        plan = alloc.plan_snapshots(1, b, 0, every=8)
        assert len(plan) == 3 and pinned not in [e for _, e in plan]
        assert alloc.stats()["state_snapshot_evictions"] == 2
        alloc.check_invariants()
        alloc.release_snapshot_pins()
        alloc.register(1, b)
        alloc.check_invariants()
        # a's snapshots at 8 and 16 went: a prefix of 16 tokens of it
        # now matches pages and finds no state
        alloc.free_slot(1)
        adm = alloc.admit(1, a[:17], need_len=20)
        assert (adm.scan_start, adm.state_src) == (0, -1)
        alloc.check_invariants()

    def test_a_snapshot_goes_with_its_page_and_a_failed_admission_leaves_none(
            self):
        alloc = PageAllocator(8, 4, slots=2, table_width=8, state_entries=4)
        a = list(range(13))
        alloc.admit(0, a, need_len=16)
        alloc.plan_snapshots(0, a, 0, every=8)  # 8, 12
        alloc.register(0, a)
        alloc.free_slot(0)
        assert alloc.stats()["state_pool_in_use"] == 2
        # pressure evicts a's pages, and the snapshots hung on them
        alloc.admit(1, list(range(200, 230)), need_len=32)
        assert alloc.stats()["state_pool_in_use"] == 0
        alloc.check_invariants()
        alloc.free_slot(1)
        # a failed admission: planned, eagerly indexed, then discarded
        alloc.admit(0, a, need_len=16)
        alloc.plan_snapshots(0, a, 0, every=8)
        alloc.register(0, a)
        assert alloc.stats()["state_pool_in_use"] == 2
        alloc.free_slot(0, discard_index=True)
        assert alloc.stats()["state_pool_in_use"] == 0
        alloc.check_invariants()
        # planned and never registered
        alloc.admit(0, a, need_len=16)
        alloc.plan_snapshots(0, a, 0, every=8)
        alloc.free_slot(0, discard_index=True)
        assert alloc.stats()["state_pool_in_use"] == 0
        alloc.reset()
        alloc.check_invariants()


# ---------------------------------------------------------------------------
# Bit-identity: paged on == paged off == engine.generate
# ---------------------------------------------------------------------------


class TestWindowPages:
    """Two kinds of page (docs/paged_kv.md): the window layers' pages
    beside the allocator's own, host side only. Pages of 4 tokens, a
    window of 16 (4 pages), a chunk of 8, 3 slots; a row may hold 9
    window pages."""

    P, W, CHUNK = 4, 16, 8

    def _alloc(self, n_pages_w=27, per_slot=9, lookahead=3, slots=3):
        win = WindowPages(
            n_pages_w, self.P, slots=slots, table_width=32, window=self.W,
            per_slot=per_slot, lookahead=lookahead, retain=self.CHUNK)
        return PageAllocator(
            128, self.P, slots=slots, table_width=32, window=win)

    @staticmethod
    def _mapped(alloc, slot):
        row = alloc.window.tables[slot]
        return [int(j) for j in np.nonzero(row != alloc.window.sentinel)[0]]

    def test_the_arena_a_slot_is_sized_from_window_chunk_and_page(self):
        # ISSUE 53's cell: (4,096 + 512 + 24 mapped ahead) / 16 -> 290, + 2
        assert window_pages_per_slot(4096, 512, 16, 24) == 292
        assert window_pages_per_slot(32, 32, 16, 2) == 7
        assert window_pages_per_slot(16, 8, 4, 3) == 9

    def test_a_cold_long_prompt_maps_its_live_tail_only(self):
        alloc = self._alloc()
        prompt = list(range(100, 150))  # 50 tokens: blocks 0..12
        adm = alloc.admit(0, prompt, need_len=80)
        assert adm.pages_shared == 0
        # the full layers' row covers the request's whole lifetime
        assert int((alloc.tables[0] != alloc.sentinel).sum()) == 20
        # the window layers': from the block the first decode query (at
        # 50) can read, (50 - 16 + 1) // 4 = 8, to 50 + 3 mapped ahead
        assert self._mapped(alloc, 0) == list(range(8, 14))
        assert alloc.window.stats()["paged_window_pages_mapped"] == 6
        alloc.check_invariants()

    def test_the_free_rule_by_position_and_the_mapping_ahead(self):
        alloc = self._alloc()
        alloc.admit(0, list(range(100, 150)), need_len=80)
        alloc.register(0, list(range(100, 150)))
        win = alloc.window
        for pos in range(50, 78):  # the row's next query
            win.extend(0, pos)
            freed = win.release(0, pos)
            mapped = self._mapped(alloc, 0)
            # what the query can read is there (keys pos - 15 .. pos),
            # and what a follow-up turn's hit at the end of the prompt's
            # full pages (48) would read, as far as a chunk behind pos
            kept_from = min(pos, max(48, pos - self.CHUNK))
            assert mapped[0] == (kept_from - self.W + 1) // self.P
            assert mapped[0] <= (pos - self.W + 1) // self.P
            assert mapped[-1] >= pos // self.P
            assert mapped == list(range(mapped[0], mapped[-1] + 1))
            assert len(mapped) <= 9 and freed in (0, 1)
            alloc.check_invariants()
        # never past the request's extent (80 positions: 20 blocks)
        assert self._mapped(alloc, 0)[-1] == 19
        assert win.stats()["paged_window_pages_freed"] == 5
        # let go is the row's reference: the prompt's indexed pages stay
        # resident at refcount 0, the generated tokens' pages are free
        assert win.in_use() == len(self._mapped(alloc, 0)) + 4
        assert len(win._stamp) == 4  # blocks 8..11 of the prompt

    def test_a_shared_prefix_whose_first_reader_moved_past_the_window(self):
        """Row 0 admits a prompt, registers it and decodes on past the
        window: its references on the prompt's window pages are gone,
        the pages are cached. Row 1 then hits on the same prompt: it
        takes references on the pages a query at the hit's end reads
        (refcount 1, not 2: the first reader let go), and the full
        layers' pages are shared by both (refcount 2)."""
        alloc = self._alloc()
        prompt = list(range(100, 149))  # 49 tokens: 12 full pages
        alloc.admit(0, prompt, need_len=90)
        alloc.register(0, prompt)
        held = [int(alloc.window.tables[0, j]) for j in range(8, 12)]
        for pos in range(49, 90):
            alloc.window.extend(0, pos)
            alloc.window.release(0, pos)
        assert all(alloc.window._ref[pg] == 0 for pg in held)
        follow = prompt + list(range(500, 510))
        adm = alloc.admit(1, follow, need_len=80)
        assert (adm.pages_shared, adm.scan_start) == (12, 48)
        # a query at 48 reads keys 33..: blocks 8..11, the same pages
        assert [int(alloc.window.tables[1, j]) for j in range(8, 12)] == held
        assert all(alloc.window._ref[pg] == 1 for pg in held)
        assert all(alloc._ref[int(pg)] == 2 for pg in alloc.tables[1, :12])
        # fresh pages from the block the first decode query (at 59) reads
        assert self._mapped(alloc, 1) == list(range(8, 16))
        assert alloc.window.stats()["paged_window_hits_refused"] == 0
        alloc.check_invariants()
        alloc.free_slot(0)
        alloc.free_slot(1)
        alloc.check_invariants()
        assert all(alloc.window._ref[pg] == 0 for pg in held)

    def _evict(self, alloc, prompt, block):
        """Drop the window page of `prompt`'s `block` as pressure would."""
        win = alloc.window
        key = alloc._walk_keys(
            2, np.asarray(prompt, np.int32), 0, block + 1)[block]
        alloc._walk.pop(2, None)
        page = win._index.pop(key)
        del win._key_of[page], win._stamp[page]
        win._free.append(page)
        alloc.check_invariants()

    def test_a_hit_is_refused_where_a_window_page_was_evicted(self):
        """The full layers' pages of the whole prompt are resident, one
        window page a query at the hit's end reads is not: the hit is
        cut back to the longest prefix whose window pages are all
        there, or dropped, and counted."""
        alloc = self._alloc()
        short = list(range(100, 117))  # 17 tokens: blocks 0..3, all stored
        alloc.admit(0, short, need_len=30)
        alloc.register(0, short)
        alloc.free_slot(0)
        self._evict(alloc, short, 3)
        adm = alloc.admit(1, short + [7, 8, 9], need_len=40)
        # a query at 16 would read blocks 0..3; at 12 blocks 0..2 are there
        assert (adm.pages_shared, adm.scan_start) == (3, 12)
        assert alloc.window.stats()["paged_window_hits_refused"] == 1
        assert alloc.hits == 1
        alloc.free_slot(1)
        # a long cold prompt stores its live tail alone (blocks 8..11 of
        # 12): with block 10 gone no hit length has its window whole
        prompt = list(range(300, 349))
        alloc.admit(0, prompt, need_len=60)
        alloc.register(0, prompt)
        alloc.free_slot(0)
        self._evict(alloc, prompt, 10)
        adm = alloc.admit(1, prompt + [7, 8, 9], need_len=70)
        assert (adm.pages_shared, adm.scan_start) == (0, 0)
        assert alloc.window.stats()["paged_window_hits_refused"] == 2
        # the full layers' pages were all there: without the window rule
        # this would have been a hit of 12 pages
        assert len(alloc.chain_pages(prompt)) == 12
        alloc.check_invariants()

    def test_a_hit_that_does_not_fit_a_rows_share_goes_cold(self):
        """A hit and its suffix would hold more window pages than a
        row's share of the arena (4 shared + 6 fresh > 9): refused, the
        row is admitted cold on its live tail, which is what keeps every
        later mapping ahead free or evictable."""
        alloc = self._alloc(per_slot=9)
        head = list(range(100, 140))  # 10 pages
        alloc.admit(0, head + [1], need_len=50)
        alloc.register(0, head + [1])
        alloc.free_slot(0)
        adm = alloc.admit(1, head + list(range(200, 218)), need_len=70)
        assert adm.pages_shared == 0
        assert alloc.window.stats()["paged_window_hits_refused"] == 1
        assert len(self._mapped(alloc, 1)) == 6
        alloc.free_slot(1)
        # two tokens fewer and it fits: 4 shared + 5 fresh
        adm = alloc.admit(1, head + list(range(200, 216)), need_len=70)
        assert adm.pages_shared == 10 and len(self._mapped(alloc, 1)) == 9
        alloc.check_invariants()

    def test_exhaustion_is_all_or_nothing_for_both_kinds(self):
        alloc = self._alloc(n_pages_w=6, slots=2)
        alloc.admit(0, list(range(100, 150)), need_len=60)  # 6 window pages
        before = alloc.tables.copy(), alloc.window.tables.copy()
        with pytest.raises(PageExhaustedError, match="window page pool"):
            alloc.admit(1, list(range(300, 330)), need_len=40)
        np.testing.assert_array_equal(alloc.tables, before[0])
        np.testing.assert_array_equal(alloc.window.tables, before[1])
        alloc.check_invariants()
        alloc.reset()
        alloc.check_invariants()
        assert alloc.window.in_use() == 0

    @pytest.mark.parametrize("retain, refused", [(-1, False), (0, True)])
    def test_the_cells_schedule_on_the_host_alone(self, retain, refused):
        """scripts/window_pages_sim.py: the allocator under `mixed-ctx`
        at the cell's sizes (32 slots, a window arena of 292 pages a
        slot), no device. With the tail kept (`retain` = the chunk) no
        follow-up turn finds its window pages gone and only sessions'
        first turns are cold; without it some do, and re-run a whole
        document. The books balance every 50 ticks either way."""
        import json
        import os
        import subprocess
        import sys

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        out = subprocess.run(
            [sys.executable, os.path.join(root, "scripts",
                                          "window_pages_sim.py"),
             "--config", os.path.join(
                 root, "benchmark", "configs",
                 "smallthinker-21b-a3b-bf16-1chip.json"),
             "--ticks", "1500", "--retain", str(retain)],
            capture_output=True, text=True, timeout=300, check=True)
        got = json.loads(out.stdout.strip().splitlines()[-1])
        assert got["window_pages_a_slot"] == 292
        assert (got["window_hits_refused"] > 0) == refused
        assert got["calls"] > 2500 and got["window_pages_used_share_mean"] > 0.9
        if not refused:
            # 32 first turns, then a new session every 16 calls a client,
            # three of four on a long document
            assert got["cold_long_admissions"] <= 24 + got["calls"] * 3 // 64 + 8
            assert got["pages_reused_share"] > 0.85

    def test_no_window_means_no_window_work(self):
        alloc = PageAllocator(16, 4, slots=2, table_width=8)
        assert alloc.window is None
        stats = alloc.stats()
        assert {k: v for k, v in stats.items() if "window" in k} == {
            "kv_window_pages_total": 0, "kv_window_pages_in_use": 0,
            "paged_window_pages_freed": 0, "paged_window_hits_refused": 0,
            "paged_window_pages_mapped": 0}


class TestPagedBitIdentity:
    async def test_all_admission_paths_match_flat_and_engine(self, engine):
        """One mixed wave exercising fused (short cold), paged-prefix
        (shared preamble), and chunked (long cold) admission — paged-on
        outputs byte-equal to paged-off AND the uncached engine."""
        head = prompt_of(24)
        prompts = (
            [prompt_of(12, salt=50)]  # fused short
            + [head + prompt_of(6, salt=s) for s in range(4)]  # shared
            + [prompt_of(80, salt=9)]  # chunked long
        )
        expected, _ = engine.generate(prompts, max_new_tokens=5, seed=0)
        outs_off, _ = await run_wave(
            engine, flat_cfg(prefill_chunk=32), prompts
        )
        outs_on, paged = await run_wave(
            engine, paged_cfg(prefill_chunk=32), prompts
        )
        assert outs_off == expected
        assert outs_on == expected
        stats = paged.counter_stats()
        assert stats["paged_prefix_hits"] >= 1
        assert stats["prefix_cache_hits"] + stats["prefix_cache_misses"] \
            == len(prompts)

    async def test_repeat_prompt_hits_and_matches(self, engine):
        prompt = prompt_of(40)
        expected, _ = engine.generate([prompt], max_new_tokens=6, seed=0)
        batcher = ContinuousBatcher(engine, paged_cfg())
        batcher.warmup()  # covers the paged warm ladder
        batcher.start()
        try:
            out1, _ = await collect(batcher, prompt, 6)
            assert (batcher.prefix_hits, batcher.prefix_misses) == (0, 1)
            out2, _ = await collect(batcher, prompt, 6)
            assert batcher.prefix_hits == 1
            assert batcher.pages.pages_reused >= 4  # 32+ shared tokens
        finally:
            await batcher.stop()
        assert out1 == expected[0]
        assert out2 == expected[0]

    async def test_interleaved_admission_matches(self, engine):
        """Paged + prefill_interleave: the chunk-per-tick mini rides
        unchanged and _ilv_finish merges into pages. The engine's
        uncached generate is the reference — the contiguous interleaved
        path's equality to it is already pinned by test_interleave."""
        prompts = [prompt_of(16, salt=s) for s in range(3)] + [
            prompt_of(100, salt=7)
        ]
        expected, _ = engine.generate(prompts, max_new_tokens=5, seed=0)
        outs_on, paged = await run_wave(
            engine, paged_cfg(prefill_chunk=32, prefill_interleave="on"),
            prompts,
        )
        assert outs_on == expected

    async def test_chaos_tick_faults_replay_bit_identical(self, engine):
        """Injected tick faults: the paged arena dies with the donated
        call; block tables are HOST state — recovery resets the
        allocator and replay re-maps through admission. Greedy outputs
        stay byte-equal to the fault-free contiguous run."""
        head = prompt_of(24)
        prompts = [head + prompt_of(6, salt=s) for s in range(4)] + [
            prompt_of(60, salt=8)
        ]
        outs_off, _ = engine.generate(prompts, max_new_tokens=5, seed=0)
        failpoints.registry.arm("tick_fail", every=4, times=2)
        try:
            outs_chaos, chaos = await run_wave(
                engine,
                paged_cfg(prefill_chunk=32, tick_retry_limit=3),
                prompts,
            )
        finally:
            failpoints.registry.disarm()
        assert outs_chaos == outs_off
        assert chaos.replayed >= 1

    async def test_grammar_row_in_paged_batch(self, engine):
        """A DFA-constrained row and plain greedy rows share one paged
        batch; the plain rows stay byte-equal to the contiguous path
        and the constrained row completes its schema."""
        schema = {
            "type": "object",
            "properties": {"ok": {"type": "boolean"}},
            "required": ["ok"],
        }
        g = compile_schema(schema, vocab_size=512)
        plain = prompt_of(20)
        expected, _ = engine.generate([plain], max_new_tokens=5, seed=0)
        batcher = ContinuousBatcher(engine, paged_cfg())
        batcher.start()
        try:
            (out_plain, _), (out_g, reason_g) = await asyncio.gather(
                collect(batcher, plain, 5),
                collect(batcher, prompt_of(20, salt=3), 64, grammar=g),
            )
        finally:
            await batcher.stop()
        assert out_plain == expected[0]
        assert reason_g == "grammar_complete" and len(out_g) >= 1

    async def test_int8_kv_pages_match_contiguous_int8(self):
        engine8 = GenerationEngine(
            llama.CONFIGS["tiny-llama"],
            ServingConfig(
                mesh=MeshConfig(tensor=2, data=0), kv_cache_dtype="int8"
            ),
        )
        head = prompt_of(24)
        prompts = [head + prompt_of(6, salt=s) for s in range(3)]
        expected, _ = engine8.generate(prompts, max_new_tokens=5, seed=0)
        outs_on, _ = await run_wave(engine8, paged_cfg(), prompts)
        assert outs_on == expected


# ---------------------------------------------------------------------------
# Sharing mechanics on the live batcher
# ---------------------------------------------------------------------------


class TestPagedSharing:
    async def test_concurrent_same_preamble_share_physical_pages(
        self, engine
    ):
        """While a same-preamble wave decodes, the preamble's pages are
        refcount-shared — stored once, referenced by every slot."""
        head = prompt_of(32)
        batcher = ContinuousBatcher(engine, paged_cfg())
        batcher.start()
        shared_peak = {"v": 0}

        async def probe():
            while True:
                shared_peak["v"] = max(
                    shared_peak["v"], batcher.pages.shared()
                )
                await asyncio.sleep(0.002)

        try:
            await collect(batcher, head + [401], 2)  # seed the index
            probe_task = asyncio.ensure_future(probe())
            try:
                await asyncio.gather(*(
                    collect(batcher, head + [410 + i], 24, seed=i)
                    for i in range(4)
                ))
            finally:
                probe_task.cancel()
        finally:
            await batcher.stop()
        # 32-token preamble at page 8 = 4 full pages shared while the
        # wave decodes; every wave member hit the index.
        assert shared_peak["v"] >= 4
        assert batcher.pages.hits >= 4
        assert batcher.pages.pages_reused >= 16

    async def test_longer_prompt_reuses_shorter_then_its_own_chain(
        self, engine
    ):
        """A longer prompt after a shorter one shares the shorter
        one's pages and runs its multi-chunk suffix; repeated, it
        shares its OWN longer chain. Outputs match the engine."""
        short = prompt_of(16)
        longer = short + prompt_of(44, salt=3)
        expected, _ = engine.generate([longer], max_new_tokens=4, seed=0)
        batcher = ContinuousBatcher(engine, paged_cfg(prefill_chunk=16))
        batcher.start()
        try:
            await collect(batcher, short, 3)  # indexes 2 full pages
            out1, _ = await collect(batcher, longer, 4)
            assert batcher.prefix_hits == 1
            assert batcher.pages.pages_reused == 2
            out2, _ = await collect(batcher, longer, 4)
            assert batcher.prefix_hits == 2
            # 60 tokens at page 8: 7 full pages, every one shared.
            assert batcher.pages.pages_reused == 2 + 7
        finally:
            await batcher.stop()
        assert out1 == expected[0]
        assert out2 == expected[0]

    async def test_burst_of_distinct_prompts_shares_no_page(self, engine):
        batcher = ContinuousBatcher(engine, paged_cfg(max_batch_size=8))
        batcher.start()
        try:
            await asyncio.gather(*(
                collect(batcher, prompt_of(20, salt=50 + i), 4, seed=i)
                for i in range(6)
            ))
        finally:
            await batcher.stop()
        stats = batcher.counter_stats()
        assert stats["paged_prefix_hits"] == 0
        assert stats["paged_pages_reused"] == 0
        assert (batcher.prefix_hits, batcher.prefix_misses) == (0, 6)

    async def test_cold_burst_registers_and_next_burst_hits(self, engine):
        """A cold burst carrying one NEW preamble registers its pages
        in the same round (the later rows of the round already share
        them), and every row of the next same-preamble burst hits."""
        head = prompt_of(24, salt=9)
        burst1 = [head + prompt_of(4, salt=100 + s) for s in range(16)]
        burst2 = [head + prompt_of(4, salt=200 + s) for s in range(16)]
        batcher = ContinuousBatcher(engine, paged_cfg(max_batch_size=16))
        batcher.start()
        try:
            outs1 = await asyncio.gather(
                *(collect(batcher, p, 4) for p in burst1)
            )
            assert all(r in ("length", "stop") for _, r in outs1)
            hits1 = batcher.prefix_hits
            assert hits1 >= 1 and batcher.prefix_misses >= 1
            reused1 = batcher.pages.pages_reused
            outs2 = await asyncio.gather(
                *(collect(batcher, p, 4) for p in burst2)
            )
            assert batcher.prefix_hits - hits1 == 16
            # 24-token head at page 8 = 3 full pages a row.
            assert batcher.pages.pages_reused - reused1 == 16 * 3
        finally:
            await batcher.stop()
        expected, _ = engine.generate(burst2[:2], max_new_tokens=4, seed=0)
        assert [o for o, _ in outs2[:2]] == expected

    async def test_paged_holds_hit_rate_at_3x_working_set(self, engine):
        """12 distinct 32-token preambles revisited by 64 concurrent
        sessions over a 16-slot arena: pages store each preamble once,
        exactly sized, so the whole working set stays resident and at
        least nine admissions in ten reuse it. A call may stop on the
        end-of-sequence id before it emits a token."""
        preambles = [prompt_of(32, salt=100 + p) for p in range(12)]
        batcher = ContinuousBatcher(engine, paged_cfg(max_batch_size=16))
        batcher.warmup()
        batcher.start()
        try:
            for p, pre in enumerate(preambles):  # every preamble once
                await collect(batcher, pre + [400 + p], 4, seed=p)
            h0, m0 = batcher.prefix_hits, batcher.prefix_misses
            results = await asyncio.gather(*(
                collect(
                    batcher,
                    preambles[i % 12] + [300 + i, (i * 7) % 200 + 1],
                    4, seed=i,
                )
                for i in range(64)
            ))
            hits = batcher.prefix_hits - h0
            misses = batcher.prefix_misses - m0
        finally:
            await batcher.stop()
        assert all(r in ("stop", "length") for _, r in results)
        assert hits + misses == 64
        assert hits / 64 >= 0.9, f"hit rate {hits / 64:.2f}"

    async def test_tick_records_carry_page_occupancy(self, engine):
        batcher = ContinuousBatcher(engine, paged_cfg())
        batcher.start()
        try:
            await collect(batcher, prompt_of(20), 6)
        finally:
            await batcher.stop()
        ticks, _ = batcher.flight_snapshot()
        assert ticks and any(t.kv_pages_in_use > 0 for t in ticks)
        assert "kvPagesInUse" in ticks[-1].to_dict()

    async def test_stats_flow_to_proto(self, engine):
        """counter_stats' paged keys construct a ServingStatsResponse —
        the loud-drift contract the proto↔metrics test leans on."""
        from ggrmcp_tpu.rpc.pb import serving_pb2

        batcher = ContinuousBatcher(engine, paged_cfg())
        batcher.start()
        try:
            await collect(batcher, prompt_of(20), 3)
            await collect(batcher, prompt_of(20), 3)
        finally:
            await batcher.stop()
        msg = serving_pb2.ServingStatsResponse(**batcher.stats())
        assert msg.kv_pages_total == batcher.pages.n_pages
        assert msg.kv_pages_in_use > 0
        assert msg.paged_prefix_hits >= 1

    async def test_tiered_composes_with_paged(self, engine):
        head = prompt_of(24)
        prompts = [head + prompt_of(6, salt=s) for s in range(4)]
        expected, _ = engine.generate(prompts, max_new_tokens=5, seed=0)
        tiered = TieredBatcher(engine, BatchingConfig(
            kv_tiers=[[64, 4], [256, 2]],
            paged_kv="on", paged_kv_page_size=8,
        ))
        tiered.start()
        try:
            results = await asyncio.gather(*(
                collect(tiered, p, 5, seed=i)
                for i, p in enumerate(prompts)
            ))
        finally:
            await tiered.stop()
        assert [out for out, _ in results] == expected
        stats = tiered.stats()
        assert stats["kv_pages_total"] == sum(
            t.pages.n_pages for t in tiered.tiers
        )

    async def test_mixed_batch_compile_count_stable(self, engine):
        """Mixed shared/unshared/sampled rows all ride ONE compiled
        paged tick — zero new tick compiles after the first wave."""
        head = prompt_of(24)
        batcher = ContinuousBatcher(engine, paged_cfg())
        batcher.start()
        try:
            await collect(batcher, head + [400], 4)  # warm tick + index
            before = batcher._tick._cache_size()
            await asyncio.gather(
                collect(batcher, head + [401], 4),  # shared
                collect(batcher, prompt_of(12, salt=60), 4),  # cold
                collect(batcher, prompt_of(12, salt=61), 4,
                        sampling=SamplingConfig(temperature=0.9), seed=5),
            )
            assert batcher._tick._cache_size() == before
        finally:
            await batcher.stop()


@contextlib.contextmanager
def recorded_shapes(batcher, program: str):
    """The token-grid shape of every call of a batcher's jitted
    admission `program` while the block runs."""
    shapes: list[tuple] = []
    real = getattr(batcher, program)

    def recording(*args):
        shapes.append(tuple(args[1].shape))
        return real(*args)

    setattr(batcher, program, recording)
    try:
        yield shapes
    finally:
        setattr(batcher, program, real)


class TestFusedAdmissionShapes:
    """One device call a group, at the bucketed row count."""

    async def test_same_preamble_wave_is_one_fused_call(self, engine):
        batcher = ContinuousBatcher(engine, paged_cfg())
        batcher.warmup()
        batcher.start()
        head = prompt_of(24, salt=400)
        try:
            await collect(batcher, head + prompt_of(3, salt=401), 3)
            with recorded_shapes(batcher, "_admit_paged_pfx") as shapes:
                outs = await asyncio.gather(*(
                    collect(batcher, head + prompt_of(3, salt=410 + i), 4,
                            seed=i)
                    for i in range(3)
                ))
        finally:
            await batcher.stop()
        assert all(r in ("length", "stop") for _, r in outs)
        # The 3-request wave shares one geometry key -> ONE fused
        # [R, 1, W] call; a straggler admitted on a later round may add
        # one more.
        assert 1 <= len(shapes) <= 2, shapes
        assert all(s[1] == 1 for s in shapes)
        assert sum(s[0] for s in shapes) >= 3

    async def test_long_group_uses_bucketed_rows(self, engine):
        """Long-prompt groups run at the bucketed row count, not the
        full slot pool — a trickle long admission must not pay B x the
        prefill compute."""
        batcher = ContinuousBatcher(engine, paged_cfg(prefill_chunk=32))
        batcher.warmup()
        batcher.start()
        try:
            with recorded_shapes(batcher, "_admit_chunked") as shapes:
                _, reason = await collect(
                    batcher, prompt_of(100, salt=500), 4
                )
        finally:
            await batcher.stop()
        assert reason in ("length", "stop")
        # One trickle admission: R=1 rows, on the batcher's one grid of
        # T_max = 256/32 = 8 chunks (the program runs the prompt's 4).
        assert shapes == [(1, 8, 32)], shapes


# ---------------------------------------------------------------------------
# Exhaustion: typed shed, no corruption
# ---------------------------------------------------------------------------


class TestPageExhaustion:
    async def test_tiny_pool_sheds_typed_and_stays_sane(self, engine):
        """A pool too small for the request sheds "overloaded" (the
        RESOURCE_EXHAUSTED → 429 ladder) and resident tables survive:
        a live request keeps decoding correctly and a smaller follow-up
        admits fine."""
        expected, _ = engine.generate(
            [prompt_of(10)], max_new_tokens=40, seed=0
        )
        batcher = ContinuousBatcher(
            engine, paged_cfg(paged_kv_pages=10)
        )
        batcher.start()
        try:
            live = asyncio.ensure_future(collect(batcher, prompt_of(10), 40))
            await asyncio.sleep(0.05)  # let it admit (7 of 10 pages)
            # 200 + 8 + 1 tokens = 27 pages — more than the whole
            # 10-page arena, so the shed is deterministic whether or
            # not the live request has finished yet.
            out, reason = await collect(batcher, prompt_of(200, salt=5), 8)
            assert reason == "overloaded" and out == []
            assert batcher.shed == 1
            out_live, _ = await live
            assert out_live == expected[0]  # bystander unharmed
            out2, r2 = await collect(batcher, prompt_of(10, salt=2), 4)
            assert r2 in ("stop", "length") and len(out2) >= 1
        finally:
            await batcher.stop()

    async def test_failpoint_forces_exhaustion_path(self, engine):
        batcher = ContinuousBatcher(engine, paged_cfg())
        batcher.start()
        failpoints.registry.arm("page_exhausted", every=1, times=1)
        try:
            out, reason = await collect(batcher, prompt_of(12), 4)
            assert reason == "overloaded" and batcher.shed == 1
            out2, r2 = await collect(batcher, prompt_of(12), 4)
            assert r2 in ("stop", "length") and len(out2) >= 1
        finally:
            failpoints.registry.disarm()
            await batcher.stop()


# ---------------------------------------------------------------------------
# Config hygiene (satellite: typed composition errors)
# ---------------------------------------------------------------------------


class TestPagedConfig:
    def _cfg(self, **batching) -> Config:
        cfg = Config()
        for key, value in batching.items():
            setattr(cfg.serving.batching, key, value)
        return cfg

    def test_defaults_validate(self):
        self._cfg(paged_kv="on").validate()

    def test_bad_mode_rejected(self):
        with pytest.raises(ValueError, match="paged_kv"):
            self._cfg(paged_kv="maybe").validate()

    def test_kv_ring_mutually_exclusive(self):
        cfg = self._cfg(paged_kv="on")
        cfg.serving.kv_ring = True
        cfg.serving.model = "tiny-mistral"
        with pytest.raises(ValueError, match="mutually exclusive"):
            cfg.validate()

    def test_page_size_must_divide_max_seq(self):
        with pytest.raises(ValueError, match="divide"):
            self._cfg(
                paged_kv="on", paged_kv_page_size=24, kv_cache_max_seq=256
            ).validate()

    def test_page_size_must_divide_tier_max_seq(self):
        with pytest.raises(ValueError, match="tier"):
            self._cfg(
                paged_kv="on", paged_kv_page_size=16,
                kv_tiers=[[72, 4], [256, 2]], kv_cache_max_seq=256,
            ).validate()

    def test_tier_entry_is_max_seq_and_slots(self):
        with pytest.raises(ValueError, match=r"\[max_seq, slots\]"):
            self._cfg(
                paged_kv="on", kv_tiers=[[64, 4, 2], [256, 2]],
            ).validate()

    def test_batcher_mirrors_validation(self, engine):
        with pytest.raises(ValueError, match="divide"):
            ContinuousBatcher(engine, paged_cfg(paged_kv_page_size=24))
