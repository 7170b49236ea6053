"""BENCHMARK.json against the contract's static rules, and every
per-layer metric against its reader file."""

import json
import os
import re

import pytest

from benchmark import plugins

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = {w["name"]: w for w in BENCH["workloads"]}
E2E = {m["name"]: m for m in BENCH["end_to_end"]}


def test_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51 and isinstance(BENCH["run_seconds"], int)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    assert len({(w["config"], w["traffic"]) for w in BENCH["workloads"]}) == len(CELLS)


def test_every_configuration_is_used_and_its_files_exist():
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used and NAME.match(c["name"])
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["source"] == c["source"] and body["reduced"] == c["reduced"]
    for w in BENCH["workloads"]:
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "traffic", w["traffic"] + ".json"))


def test_end_to_end_metrics():
    assert "setup_s" in E2E and E2E["setup_s"]["bound"] <= 0.1
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", [])) <= set(CELLS)
    # the tail does not decide: it is per-layer (PERF.md, section 2)
    assert "call_ms_p95" not in E2E
    # tokens/s decides only where one call at the window's edge is a
    # small part of the total (PERF.md, section 2)
    assert E2E["out_tok_s"]["workloads"] == ["agent-shared.int8-1chip"]
    for cell in CELLS:
        reported = [m for m in BENCH["end_to_end"]
                    if cell in m.get("workloads", CELLS)]
        assert len(reported) >= 2 and any(m["name"] == "setup_s" for m in reported)


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_matches_its_reader(metric):
    assert set(metric) - {"workloads"} == {
        "name", "unit", "better", "source", "layer", "moves"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["source"] in (
        "device_trace", "program_span", "program_counter", "host_clock")
    reader = plugins.load(
        "layer_metrics", metric["name"], [os.path.join(ROOT, "benchmark")])
    assert (reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) == (
        metric["unit"], metric["layer"], metric["moves"], metric["source"])
    moved = E2E[metric["moves"]]
    for cell in metric.get("workloads", CELLS):
        assert cell in CELLS
        assert cell in moved.get("workloads", CELLS), (
            f"{metric['name']} moves {moved['name']}, which {cell} does not report")
    if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


def test_every_cell_has_a_per_layer_metric():
    for cell in CELLS:
        assert any(cell in m.get("workloads", CELLS) for m in BENCH["per_layer"])
