"""The rule that is new: the seed changes token ids and nothing else."""

import json
import os

import pytest

from benchmark import schedule
from benchmark.run import probe_lengths

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
MIXES = [("decode-steady", 8), ("agent-shared", 8), ("decode-steady", 32)]


def calls_of(sched, seed, vocab=32000, sessions=3):
    """Every call the closed-loop clients would make, as the client
    builds them (outputs stood in for by seeded pads)."""
    prefix = schedule.token_ids(seed, vocab, sched.shared_prefix_tokens, "prefix")
    out = []
    for client in range(sched.clients):
        index = sched.offsets[client]
        for session in range(sessions):
            history = []
            for turn, (p, o) in enumerate(schedule.session_block(sched, index)):
                new = schedule.token_ids(seed, vocab, p, "c", client, session, turn)
                prompt = prefix + history + new
                out.append((client, session, turn, prompt, o))
                if sched.session_turns > 1:
                    history = history + new + schedule.token_ids(
                        seed, vocab, o, "pad", client, session, turn)
            index = (index + sched.session_turns) % len(sched.pairs)
    return out


@pytest.mark.parametrize("name,slots", MIXES)
def test_schedule_is_identical_for_two_seeds(name, slots):
    a = schedule.load(name, slots, BENCH)
    b = schedule.load(name, slots, BENCH)
    assert json.dumps(a.describe(), sort_keys=True) == json.dumps(
        b.describe(), sort_keys=True)
    ca, cb = calls_of(a, 7), calls_of(b, 2**31 + 11)
    shape = lambda cs: [(c, s, t, len(p), o) for c, s, t, p, o in cs]  # noqa: E731
    assert shape(ca) == shape(cb)  # lengths, order, clients: the same
    assert [p for *_, p, _ in ca] != [p for *_, p, _ in cb]  # ids: not
    assert probe_lengths(a, 16) == probe_lengths(b, 16)


@pytest.mark.parametrize("name,slots", MIXES)
def test_token_ids_stay_clear_of_special_ids_and_repeat(name, slots):
    sched = schedule.load(name, slots, BENCH)
    for _, _, _, prompt, _ in calls_of(sched, 2**31 + 5, sessions=1):
        assert min(prompt) >= schedule.FIRST_ID and max(prompt) < 32000
    assert calls_of(sched, 5, sessions=1) == calls_of(sched, 5, sessions=1)


def test_prompts_of_different_clients_differ_after_the_shared_part():
    sched = schedule.load("agent-shared", 8, BENCH)
    first = [p for c, s, t, p, _ in calls_of(sched, 1, sessions=1) if t == 0]
    n = sched.shared_prefix_tokens
    assert len({tuple(p[:n]) for p in first}) == 1
    assert len({tuple(p[n: n + 4]) for p in first}) == len(first)


@pytest.mark.parametrize("name", ["decode-steady", "agent-shared"])
def test_written_list_is_the_quantile_grid_it_names(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        spec = json.load(f)
    assert spec["pairs"] == schedule.grid_pairs(spec["pair_grid_of_the_list"])


def test_the_mixes_are_what_the_cells_say():
    d = schedule.load("decode-steady", 8, BENCH)
    assert d.clients == 16 and len(d.pairs) == 64
    assert sum(o for _, o in d.pairs) / 64 == 144
    assert min(min(p) for p in d.pairs) >= 32 and max(max(p) for p in d.pairs) <= 256
    assert d.offsets == tuple(4 * i for i in range(16))
    a = schedule.load("agent-shared", 8, BENCH)
    assert a.clients == 8 and a.offsets == tuple(6 * i for i in range(8))
    assert 47 < sum(o for _, o in a.pairs) / 48 < 49
    # every session stays inside 2,048 positions with the tick's reserve
    assert a.longest_prompt() + max(o for _, o in a.pairs) + 24 <= 2048


def test_probe_lengths_cover_every_admission_width():
    d = schedule.load("decode-steady", 8, BENCH)
    assert probe_lengths(d, 16) == [62, 128, 254]  # widths 64, 128, 256
    a = schedule.load("agent-shared", 8, BENCH)
    widths = sorted({1 << (n - 1).bit_length() for n in probe_lengths(a, 16)})
    assert widths == [64, 128, 256]


def test_open_loop_arrivals_are_a_fixed_timetable():
    spec = {"loop": "open", "rate_rps": 4.0, "pairs": [[8, 8]]}
    sched = _load(spec)
    assert schedule.arrivals(sched, 1.0) == [0.0, 0.25, 0.5, 0.75]
    burst = _load({"loop": "open", "burst": {"size": 3, "every_s": 0.5},
                   "pairs": [[8, 8]]})
    assert schedule.arrivals(burst, 1.0) == [0.0] * 3 + [0.5] * 3


def _load(spec):
    import tempfile

    with tempfile.TemporaryDirectory() as tmp:
        os.makedirs(os.path.join(tmp, "traffic"))
        with open(os.path.join(tmp, "traffic", "x.json"), "w") as f:
            json.dump(spec, f)
        return schedule.load("x", 8, tmp)
