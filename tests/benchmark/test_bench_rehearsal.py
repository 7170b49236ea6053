"""One cell end to end on the CPU at tiny-mistral: the line has the
contract's shape, names the CPU, carries no device metric; the control
(int8 KV switched on) comes out as not correct; a configuration, a
traffic mix, a cell and a per-layer metric are added by files alone."""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
REHEARSAL = os.path.join(HERE, "rehearsal")


def run_cell(bench_root, workload, trace, *extra, seed=2**31 + 77):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--bench-root", bench_root, "--workload", workload, "--seed", str(seed),
         "--seconds", "5", "--trace", str(trace), "--cpu-rehearsal", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    return json.loads(lines[-1]), lines


def test_rehearsal_prints_the_contracts_line_and_names_the_cpu():
    line, out = run_cell(REHEARSAL, "tiny-decode.cpu", 0)
    assert out[0].startswith("CPU REHEARSAL") and line["rehearsal"] is True
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 10
    assert set(line["metrics"]) == {"call_ms_p50", "setup_s"}
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    # every number compared is printed beside its limit
    assert any("limit 0" in ln for ln in out) and any("(limit 0.0001" in ln for ln in out)


def test_the_control_comes_out_as_not_correct():
    """int8 KV, the nearest precision below the configuration's: at
    this size sound runs read mean_margin_sigma 0.0 (float32 serving
    agrees with the float32 reference to the token) and the control
    3.8e-4; the rehearsal configuration's limit is 1e-4."""
    line, out = run_cell(REHEARSAL, "tiny-decode.cpu", 0, "--control", "int8_kv")
    assert line["correct"] is False, out[-4:]
    assert any("OVER" in ln for ln in out)


def test_config_traffic_cell_and_metric_are_added_by_files_alone(tmp_path):
    root = str(tmp_path / "bench")
    shutil.copytree(REHEARSAL, root)
    data = os.path.join(root, "benchmark")
    with open(os.path.join(data, "configs", "tiny-mistral-cpu.json")) as f:
        config = json.load(f)
    config["name"] = "tiny-mistral-4slot"
    config["stack"]["serving"]["batching"]["max_batch_size"] = 4
    with open(os.path.join(data, "configs", "tiny-mistral-4slot.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(data, "traffic", "new-mix.json"), "w") as f:
        json.dump({"loop": "closed", "clients": 3, "ramp": "session",
                   "shared_prefix_tokens": 48, "session_turns": 2,
                   "think_time_s": 0.01, "trace_ms": 500,
                   "pairs": [[9, 5], [12, 7], [10, 6], [15, 4]]}, f)
    os.makedirs(os.path.join(data, "layer_metrics"))
    with open(os.path.join(data, "layer_metrics", "calls_per_s.py"), "w") as f:
        f.write('UNIT, LAYER, MOVES, SOURCE = "calls/s", "gateway", '
                '"call_ms_p50", "host_clock"\n\n'
                'def read(ctx):\n'
                '    return len(ctx["calls"]) / ctx["window_s"]\n')
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-mistral-4slot", "source": "test",
        "file": "benchmark/configs/tiny-mistral-4slot.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "new-mix.4slot", "config": "tiny-mistral-4slot",
        "traffic": "new-mix", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "calls_per_s", "unit": "calls/s", "better": "higher",
        "source": "host_clock", "layer": "gateway", "moves": "call_ms_p50",
        "workloads": ["new-mix.4slot"]})
    for m in bench["per_layer"]:
        if m["name"] in ("queue_ms_mean", "prefix_reuse_share"):
            m["workloads"].append("new-mix.4slot")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    line, out = run_cell(root, "new-mix.4slot", 1)
    assert line["correct"] is True and line["failed"] == 0
    metrics = line["metrics"]
    assert set(metrics) == {"calls_per_s", "queue_ms_mean", "prefix_reuse_share"}
    assert metrics["calls_per_s"]["value"] > 1
    assert metrics["prefix_reuse_share"]["value"] > 20  # the prefix is shared
    # a CPU run never reports a device metric, nor device busy time
    assert "busy_s" not in line["device"] and "breakdown" not in line


@pytest.mark.parametrize("args", [
    ["--workload", "no-such-cell", "--seconds", "1"],
])
def test_an_unknown_cell_fails_without_a_result(args):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), *args],
        cwd=ROOT, capture_output=True, text=True, timeout=60)
    assert proc.returncode != 0 and not proc.stdout.strip()
