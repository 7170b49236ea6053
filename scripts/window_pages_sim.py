#!/usr/bin/env python3
"""`serving/pages.py` (PageAllocator + WindowPages) driven by a benchmark
traffic file on the host alone: no JAX, no chip, no clock. Every live
row advances `--steps` tokens a tick, a finished call's client sends
its next turn at once, admissions are instantaneous.

What it is for: the allocator's own numbers under a cell's schedule at
the cell's real sizes, before a chip call (refused hits, cold long
admissions, the share of mapped window pages let go by position, how
full each arena runs), with `check_invariants` every 50 ticks. It says
nothing about time. `--retain 0` shows what the window arena does
without the exception for a session's tail (`WindowPages.retain`).

    python3 scripts/window_pages_sim.py --config \\
        benchmark/configs/smallthinker-21b-a3b-bf16-1chip.json
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import schedule  # noqa: E402
from ggrmcp_tpu.serving.pages import (  # noqa: E402
    PageAllocator,
    WindowPages,
    window_pages_per_slot,
)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", default="mixed-ctx")
    ap.add_argument("--ticks", type=int, default=3000)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--retain", type=int, default=-1,
                    help="positions of its prompt's tail a row keeps "
                         "(-1: prefill_chunk, as the batcher)")
    args = ap.parse_args()
    with open(args.config) as f:
        config = json.load(f)
    batching = config["stack"]["serving"]["batching"]
    slots, page = batching["max_batch_size"], batching["paged_kv_page_size"]
    chunk, width = batching["prefill_chunk"], batching["kv_cache_max_seq"] // page
    window = config["sliding_window_size"]
    reserve = 2 * args.steps - 1  # the pipelined loop's
    lookahead = reserve + 1 + args.steps
    per_slot = window_pages_per_slot(window, chunk, page, lookahead)
    win = WindowPages(
        slots * per_slot, page, slots, width, window, per_slot, lookahead,
        chunk if args.retain < 0 else args.retain)
    alloc = PageAllocator(slots * width, page, slots, width, window=win)
    sched = schedule.load(args.traffic, slots, os.path.join(ROOT, "benchmark"))
    vocab, rng = config["vocab_size"], random.Random(1)

    clients = [
        {"i": i, "index": sched.offsets[i], "session": 0, "turn": 0,
         "history": []} for i in range(sched.clients)]
    rows: dict = {}  # slot -> [client, prompt, new ids, output tokens, made]
    free = list(range(slots))
    calls = cold_long = 0
    used = []
    for tick in range(args.ticks):
        busy = {r[0]["i"] for r in rows.values()}
        for c in clients:
            if c["i"] in busy or not free:
                continue
            p_len, o_len = schedule.session_block(sched, c["index"])[c["turn"]]
            new = schedule.token_ids(
                7, vocab, p_len, "c", c["i"], c["session"], c["turn"])
            prompt = c["history"] + new
            slot = free.pop()
            adm = alloc.admit(slot, prompt, len(prompt) + o_len + reserve + 1)
            alloc.register(slot, prompt)
            cold_long += adm.scan_start == 0 and len(prompt) > window
            rows[slot] = [c, prompt, new, o_len, 1]
        for slot, r in rows.items():  # a dispatch maps ahead
            win.extend(slot, len(r[1]) + r[4] - 1)
        for slot, r in list(rows.items()):  # a collect lets go
            r[4] += args.steps
            if r[4] < r[3]:
                win.release(slot, len(r[1]) + r[4] - 1)
                continue
            c = r[0]
            alloc.free_slot(slot)
            free.append(slot)
            del rows[slot]
            calls += 1
            c["history"] = r[1] + [
                rng.randrange(3, vocab) for _ in range(r[3])]
            c["turn"] += 1
            if c["turn"] == sched.session_turns:
                c.update(turn=0, history=[], session=c["session"] + 1,
                         index=(c["index"] + sched.session_turns)
                         % len(sched.pairs))
        if tick % 50 == 0:
            alloc.check_invariants()
            used.append(win.in_use() / win.n_pages)
    stats = win.stats()
    print(json.dumps({
        "calls": calls, "cold_long_admissions": cold_long,
        "hits": alloc.hits, "misses": alloc.misses,
        "pages_reused_share": alloc.pages_reused / alloc.pages_admitted,
        "window_hits_refused": stats["paged_window_hits_refused"],
        "window_pages_freed_share": stats["paged_window_pages_freed"]
        / max(1, stats["paged_window_pages_mapped"]),
        "window_pages_used_share_mean": sum(used) / len(used),
        "pages_used_share": alloc.in_use() / alloc.n_pages,
        "window_pages_a_slot": per_slot, "retain": win.retain,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
