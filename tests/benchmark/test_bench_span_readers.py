"""The readers of the program's time partition (PR 26): each one on
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
NEW = ("pending_ms_mean", "prefill_ms_mean", "tick_handoff_share",
       "loop_lag_ms_mean", "sidecar_added_ms", "hbm_peak_gb",
       "setup_compile_s")

# ServingStats as the harness holds it (protojson through `numbers`).
STATS0 = {
    "compileMs": 61250.0,
    "queueMsSum": 1000.0, "queueMsCount": 10,
    "pendingMsSum": 600.0, "pendingMsCount": 10,
    "prefillMsSum": 400.0, "prefillMsCount": 10,
    "e2eMsSum": 20000.0, "e2eMsCount": 10,
    "rpcGenerateMsSum": 20050.0, "rpcGenerateMsCount": 10,
    "loopExecWaitMsSum": 10.0, "loopExecWaitMsCount": 100,
    "loopWorkMsSum": 900.0, "loopLagMsSum": 50.0, "loopLagMsCount": 100,
    "loopHostMsSum": 40.0, "loopBusyMsSum": 1000.0,
}
STATS1 = {
    "compileMs": 61250.0,
    "queueMsSum": 6000.0, "queueMsCount": 20,
    "pendingMsSum": 2600.0, "pendingMsCount": 20,
    "prefillMsSum": 3400.0, "prefillMsCount": 20,
    "e2eMsSum": 39000.0, "e2eMsCount": 20,
    "rpcGenerateMsSum": 39120.0, "rpcGenerateMsCount": 20,
    "loopExecWaitMsSum": 30.0, "loopExecWaitMsCount": 300,
    "loopWorkMsSum": 2700.0, "loopLagMsSum": 250.0, "loopLagMsCount": 300,
    "loopHostMsSum": 120.0, "loopBusyMsSum": 3100.0,
}
MEMORY = {"deviceBytesInUse": ["10250000000"],
          "devicePeakBytesInUse": ["12400000000", "11000000000"]}


def read(name, stats0, stats1, memory):
    ctx = {"stats0": stats0, "stats1": stats1, "memory": memory,
           "reader_roots": READERS, "calls": [], "window_s": 45.0}
    return plugins.load("layer_metrics", name, READERS).read(ctx)


@pytest.mark.parametrize("name, value", [
    ("pending_ms_mean", 200.0),     # (2600 - 600) / 10
    ("prefill_ms_mean", 300.0),     # (3400 - 400) / 10
    ("tick_handoff_share", 100.0 * (20 + 200 + 80) / 2100),
    ("loop_lag_ms_mean", 1.0),      # 200 / 200
    ("sidecar_added_ms", 7.0),      # 19070 / 10 - 19000 / 10
    ("hbm_peak_gb", 12.4),          # the fullest chip
    ("setup_compile_s", 61.25),     # everything before the window
])
def test_reader_reads_the_window_as_a_delta(name, value):
    assert read(name, STATS0, STATS1, MEMORY) == pytest.approx(value)


def test_the_two_halves_add_up_to_queue_ms_mean():
    total = read("pending_ms_mean", STATS0, STATS1, MEMORY) + read(
        "prefill_ms_mean", STATS0, STATS1, MEMORY)
    assert total == pytest.approx(read("queue_ms_mean", STATS0, STATS1, MEMORY))


@pytest.mark.parametrize("name", [n for n in NEW if n != "setup_compile_s"])
def test_reader_returns_none_on_an_empty_window(name):
    """Nothing counted between the two reads: no value, never 0/0."""
    assert read(name, STATS1, STATS1, {}) is None


@pytest.mark.parametrize("name", NEW)
def test_reader_returns_none_where_the_program_lacks_the_counter(name):
    """The parent commit's ServingStats and /debug/memory: the reader
    finds nothing, returns None and does not raise (the driver lays this
    PR's benchmark files over the parent's checkout for traced runs)."""
    old0 = {"queueMsSum": 1000.0, "queueMsCount": 10,
            "e2eMsSum": 20000.0, "e2eMsCount": 10}
    old1 = {"queueMsSum": 6000.0, "queueMsCount": 20,
            "e2eMsSum": 39000.0, "e2eMsCount": 20}
    assert read(name, old0, old1, {"deviceBytesInUse": ["1"]}) is None


def test_the_rehearsed_cell_reports_every_reader_with_something_to_read(tmp_path):
    """One CPU rehearsal with the seven metrics named in BENCHMARK.json:
    all print but the allocator's peak, which the CPU backend has not;
    the partitions hold in what the served stack itself reported."""
    root = str(tmp_path / "bench")
    shutil.copytree(REHEARSAL, root)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for name in NEW:
        bench["per_layer"].append(dict(entries[name], workloads=["tiny-agent.cpu"]))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    line, _ = run_cell(root, "tiny-agent.cpu", 1)
    assert line["correct"] is True and line["failed"] == 0
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW) - set(got) == {"hbm_peak_gb"}
    assert got["pending_ms_mean"] + got["prefill_ms_mean"] == pytest.approx(
        got["queue_ms_mean"], rel=1e-6)
    assert got["pending_ms_mean"] >= 0 and got["prefill_ms_mean"] > 0
    assert 0 < got["tick_handoff_share"] < 100
    assert got["loop_lag_ms_mean"] > 0
    assert 0 <= got["sidecar_added_ms"] <= got["gateway_added_ms"]
    assert got["setup_compile_s"] > 0
