"""The readers of the admission round's split (PR 39): each one on
hand-made `ctx` dicts — a window with traffic, a window with none, a
program that has no such counter (the parent commit) — and all of them
through one CPU rehearsal of a cell whose BENCHMARK.json names them."""

import json
import os
import shutil

import pytest

from benchmark import plugins
from tests.benchmark.test_bench_rehearsal import REHEARSAL, ROOT, run_cell

READERS = [os.path.join(ROOT, "benchmark")]
NEW = ("admit_device_ms_mean", "admit_device_share", "host_work_share",
       "prefill_device_tok_s")

# ServingStats as the harness holds it (protojson through `numbers`).
# Over a window of 10 s: 40 admission program calls in 30 rounds,
# 2,400 ms of device in them and 300 ms of host work; the admit phase
# holds both and 500 ms of waiting for the tick in flight. The ticks'
# durations sum to 20 s, twice the window, as on a pipelined loop.
WINDOW_S = 10.0
STATS0 = {
    "tickDurationMsSum": 10000.0,
    "tickPhaseAdmitMsSum": 3000.0, "tickPhaseSyncMsSum": 100.0,
    "tickPhaseDispatchMsSum": 200.0, "tickPhaseHostMsSum": 300.0,
    "admitDeviceMsSum": 2000.0, "admitDeviceMsCount": 20,
    "admitHostMsSum": 400.0, "admitHostMsCount": 15,
    "prefillTokensComputed": 50000,
}
STATS1 = {
    "tickDurationMsSum": 30000.0,
    "tickPhaseAdmitMsSum": 6200.0, "tickPhaseSyncMsSum": 150.0,
    "tickPhaseDispatchMsSum": 350.0, "tickPhaseHostMsSum": 700.0,
    "admitDeviceMsSum": 4400.0, "admitDeviceMsCount": 60,
    "admitHostMsSum": 700.0, "admitHostMsCount": 45,
    "prefillTokensComputed": 74000,
}
# The parent commit's: the tick phases and the token counter, no split.
OLD0 = {k: v for k, v in STATS0.items() if not k.startswith("admit")}
OLD1 = {k: v for k, v in STATS1.items() if not k.startswith("admit")}


def read(name, stats0, stats1, window_s=WINDOW_S):
    ctx = {"stats0": stats0, "stats1": stats1, "memory": {},
           "reader_roots": READERS, "calls": [], "window_s": window_s}
    return plugins.load("layer_metrics", name, READERS).read(ctx)


@pytest.mark.parametrize("name, value", [
    ("admit_device_ms_mean", 60.0),         # 2400 / 40
    ("admit_device_share", 24.0),           # 2400 ms of the 10 s
    ("host_work_share", 9.0),               # 300 + 50 + 150 + 400 ms of them
    ("prefill_device_tok_s", 10000.0),      # 24000 tokens in 2.4 s
])
def test_reader_reads_the_window_as_a_delta(name, value):
    assert read(name, STATS0, STATS1) == pytest.approx(value)


def test_the_two_shares_leave_room_for_the_wait_and_the_tick():
    """host_work_share puts admit_host_ms where tick_host_share has the
    whole admit phase, so of one divisor it would read lower by the
    admission programs' device time and the wait for the tick in
    flight; and both new shares are of the window, where the old one is
    of tick durations that overlap (here twice the window)."""
    host, device, old = (
        read(n, STATS0, STATS1)
        for n in ("host_work_share", "admit_device_share", "tick_host_share"))
    assert host + device <= 100.0
    # tick_host_share: (3200 + 50 + 150 + 400) of 20000 = 19%; of the
    # window its numerator is 38% = host + device + 500 ms of waiting.
    assert 2 * old - host == pytest.approx(device + 5.0)


@pytest.mark.parametrize("name", ["admit_device_share", "host_work_share"])
def test_a_share_is_of_the_window_not_of_the_tick_durations(name):
    """Ticks that overlap more (a deeper pipeline) leave the share as it
    is; a window twice as long halves it."""
    deeper = dict(STATS1, tickDurationMsSum=50000.0)
    assert read(name, STATS0, deeper) == pytest.approx(read(name, STATS0, STATS1))
    assert read(name, STATS0, STATS1, 2 * WINDOW_S) == pytest.approx(
        read(name, STATS0, STATS1) / 2)


@pytest.mark.parametrize("name", NEW)
def test_reader_returns_none_on_an_empty_window(name):
    """Nothing counted between the two reads: no value, never 0/0."""
    assert read(name, STATS1, STATS1) is None


@pytest.mark.parametrize("name", NEW)
def test_reader_returns_none_where_the_program_lacks_the_counter(name):
    """The parent commit's ServingStats: ticks and prefill tokens were
    counted, the split was not. The reader finds nothing, returns None
    and does not raise (the driver lays this PR's benchmark files over
    the parent's checkout for traced runs)."""
    assert read(name, OLD0, OLD1) is None


def test_the_rehearsed_cell_reports_all_four(tmp_path, monkeypatch):
    """One CPU rehearsal with the four metrics named in BENCHMARK.json,
    as the root's file names them: all print, and what the served stack
    reported holds together (the shares are parts of one partition;
    every value is a count or a share of the program's own clocks, none
    is a device speed: a rehearsal's line is never written down)."""
    root = str(tmp_path / "bench")
    shutil.copytree(REHEARSAL, root)
    # A temporary directory of its own: the sidecar writes a capture
    # under <tmp>/ggrmcp-profiles/bench/ and run.py removes that whole
    # directory once it has read its trace, so two traced rehearsals in
    # two workers at once can take each other's file away.
    (tmp_path / "tmp").mkdir()
    monkeypatch.setenv("TMPDIR", str(tmp_path / "tmp"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # A cell name of its own: a rehearsal writes under
    # benchmark_out/<cell>/, and another test file rehearses
    # tiny-agent.cpu, maybe at the same time in another worker.
    cell = dict(next(w for w in bench["workloads"] if w["name"] == "tiny-agent.cpu"),
                name="tiny-agent-admit.cpu")
    bench["workloads"].append(cell)
    for m in bench["per_layer"]:
        if "workloads" in m and "tiny-agent.cpu" in m["workloads"]:
            m["workloads"].append(cell["name"])
    for name in NEW:
        assert entries[name]["source"] == "program_counter"
        assert entries[name]["moves"] == "call_ms_p50"
        bench["per_layer"].append(dict(entries[name], workloads=[cell["name"]]))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    line, _ = run_cell(root, cell["name"], 1)
    assert line["correct"] is True and line["failed"] == 0
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW) <= set(got)
    assert got["admit_device_ms_mean"] > 0
    assert 0 < got["admit_device_share"] < 100
    assert 0 < got["host_work_share"] < 100
    assert got["admit_device_share"] + got["host_work_share"] <= 100
    assert got["prefill_device_tok_s"] > 0


def test_the_root_file_lists_them_after_pr_38s_and_where_the_issue_says():
    """Looked up by name, and in prefix form: a later PR appends metrics
    after these four and cells to their lists without touching this
    test (the two tests that pinned a tail with `==` fail since the
    first cell appended after them)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    names = [m["name"] for m in bench["per_layer"]]
    at = [names.index(n) for n in NEW]
    assert at[0] >= 26 and at == sorted(at)     # after the 26 of PR 38, in order
    cold = next(m for m in bench["per_layer"] if m["name"] == "cold_prefill_tok_s")
    for i in at:
        m = bench["per_layer"][i]
        if m["name"] == "prefill_device_tok_s":
            assert set(cold["workloads"]) <= set(m["workloads"])
        else:
            assert m["workloads"][:6] == cells[:6]
        assert set(m["workloads"]) <= set(cells)
        reader = plugins.load("layer_metrics", m["name"], READERS)
        assert (reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) == (
            m["unit"], m["layer"], m["moves"], m["source"])
