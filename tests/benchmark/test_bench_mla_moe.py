"""What PR 28 added to the benchmark, on the CPU: the two traffic files
hold the work whatever the seed; the new cell's bytes against a hand
count; the new readers on recorded ServingStats, on a window with no
traffic and on a program without the counters (the parent commit); and
the new cell rehearsed at the family's tiny preset from
`rehearsal_mla_moe/`, sound and with its control."""

import json
import os

import pytest

from benchmark import plugins, roofline_mla_moe, schedule
from benchmark.run import probe_lengths
from tests.benchmark.test_bench_rehearsal import ROOT, run_cell
from tests.benchmark.test_bench_schedule import calls_of

BENCH_DIR = os.path.join(ROOT, "benchmark")
REHEARSAL = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "rehearsal_mla_moe")
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
with open(os.path.join(
        BENCH_DIR, "configs", "kanana-2-30b-a3b-bf16-1chip.json")) as f:
    KANANA = json.load(f)
CELL = "doc-sessions.kanana-bf16-1chip"
NEW = ("moe_experts_hit_share", "moe_load_max_over_mean",
       "cold_prefill_tok_s", "mla_moe_step_roofline")


@pytest.mark.parametrize("name, slots, vocab", [
    ("doc-sessions", 16, 128256), ("long-prefill", 8, 32000)])
def test_new_traffic_is_identical_for_two_seeds(name, slots, vocab):
    a = schedule.load(name, slots, BENCH_DIR)
    b = schedule.load(name, slots, BENCH_DIR)
    assert a.describe() == b.describe()
    ca = calls_of(a, 7, vocab, sessions=2)
    cb = calls_of(b, 2**31 + 11, vocab, sessions=2)
    shape = lambda cs: [(c, s, t, len(p), o) for c, s, t, p, o in cs]  # noqa: E731
    assert shape(ca) == shape(cb)
    assert [p for *_, p, _ in ca] != [p for *_, p, _ in cb]
    assert probe_lengths(a, 16) == probe_lengths(b, 16)


def test_doc_sessions_is_what_the_cell_says():
    sched = schedule.load("doc-sessions", 16, BENCH_DIR)
    assert (sched.clients, sched.session_turns, len(sched.pairs)) == (16, 4, 64)
    assert sched.shared_prefix_tokens == 0 and sched.ramp == "session"
    docs = sorted(p for p, _ in sched.pairs[0::4])
    assert docs == [6336 + 384 * k for k in range(16)]  # even grid, 6,144-12,288
    follow = [p for i, (p, _) in enumerate(sched.pairs) if i % 4]
    assert len(set(follow)) == 48 and (min(follow), max(follow)) == (33, 127)
    outs = [o for _, o in sched.pairs]
    assert (min(outs), max(outs), sum(outs) / 64) == (33, 127, 80.0)
    assert sorted(sched.offsets) == [4 * k for k in range(16)]  # a session each
    need = sched.longest_prompt() + max(outs) + 24
    assert need <= KANANA["stack"]["serving"]["batching"]["kv_cache_max_seq"]
    # cold documents reach two chunk-grid depths, follow-ups (the new
    # tokens, the previous answer and a page's remainder) three widths
    assert [1 << (n - 1).bit_length() for n in probe_lengths(sched, 16)] == [
        128, 256, 512, 8192, 16384]


def test_long_prefill_is_section_7s_cell():
    sched = schedule.load("long-prefill", 8, BENCH_DIR)
    with open(os.path.join(BENCH_DIR, "traffic", "long-prefill.json")) as f:
        spec = json.load(f)
    assert [list(p) for p in sched.pairs] == schedule.grid_pairs(
        spec["pair_grid_of_the_list"])
    prompts = [p for p, _ in sched.pairs]
    assert 1024 <= min(prompts) and max(prompts) <= 1900
    assert {o for _, o in sched.pairs} == {16} and sched.clients == 8
    assert sched.session_turns == 1 and sched.shared_prefix_tokens == 0


def test_the_check_limits_lie_between_their_two_readings():
    check = KANANA["check"]
    assert check["name"] == "logit_margin_mla_moe"
    # (sound runs' largest, control's smallest) on the chip: PERF.md
    read = {"mean_sq_margin_sigma": (0.156, 0.247), "flip_share": (0.24, 0.412)}
    assert set(check["limits"]) == set(read)
    for name, (sound, control) in read.items():
        assert sound < check["limits"][name] < control
    assert set(KANANA["controls"]) == {"fp8_kv"}


def test_the_check_holds_every_limit_it_is_given(tmp_path, monkeypatch):
    """One limit over is not correct; which one is said in the line."""
    import subprocess

    check = plugins.load("checks", "logit_margin_mla_moe", [BENCH_DIR])
    result = {"tokens": 8, "flip_share": 0.2, "mean_sq_margin_sigma": 0.1,
              "max_margin_sigma": 1.0, "mean_margin_sigma": 0.05, "finite": True,
              "per_sequence": [{"tokens": 8, "flip_share": 0.2,
                                "mean_sq_margin_sigma": 0.1}],
              "seconds": 1.0, "platform": "cpu", "kind": "cpu"}
    monkeypatch.setattr(check.subprocess, "run", lambda *a, **k: (
        subprocess.CompletedProcess(a, 0, json.dumps(result), "")))
    seg = type("Call", (), {})
    call = seg()
    call.phase, call.session, call.client, call.turn = "run", 0, 0, 0
    call.ok, call.done, call.prompt, call.output = True, 1.0, [1, 2], [3]
    call.segments = [(2, 3)]
    ctx = {"all_calls": [call], "t0": 0.0, "t1": 2.0, "out_dir": str(tmp_path),
           "config_path": "x", "cpu": True, "root": ROOT,
           "harness_dir": BENCH_DIR, "check_timeout_s": 5.0}
    verdicts = {}
    for flips in (0.3, 0.15):
        ctx["config"] = {"check": {"limits": {
            "mean_sq_margin_sigma": 0.2, "flip_share": flips}}}
        out = check.run(ctx)
        verdicts[flips] = out["correct"]
        assert ("flip_share = 0.2 (limit 0.15: OVER)" in out["lines"][1]) == (
            flips == 0.15)
        assert "mean_sq_margin_sigma = 0.1 (limit 0.2: within)" in out["lines"][1]
    assert verdicts == {0.3: True, 0.15: False}


def test_configuration_file_carries_every_published_key():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = next(r for r in rows if r["name"] == "kanana-2-30b-a3b-instruct-2601")
    assert KANANA["source"] == row["source_url"]
    differ = [k for k, v in row["config"].items() if KANANA.get(k, "absent") != v]
    assert differ == KANANA["reduced"] == ["num_hidden_layers"]
    assert KANANA["num_hidden_layers"] == 6 >= 4 + KANANA["first_k_dense_replace"]


def test_step_bytes_by_hand():
    # Attention: W_q 2048 x 6144, W_kv_a 2048 x 576, W_kv_b 512 x 8192,
    # W_o 4096 x 2048.
    attn = 2048 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048
    assert attn == roofline_mla_moe.attention_params(KANANA) == 26_345_472
    assert roofline_mla_moe.expert_bytes(KANANA) == 3 * 2048 * 768 * 2 == 9_437_184
    norms = 6 * (2 * 2048 + 512) + 2048
    fixed = (6 * attn + norms + 3 * 2048 * 6144 + 5 * 3 * 2048 * 1536
             + 2048 * 128256) * 2 + 5 * (2048 * 128 + 128) * 4
    assert roofline_mla_moe.fixed_weight_bytes(KANANA) == fixed
    assert fixed == pytest.approx(1.017e9, rel=2e-3)
    assert roofline_mla_moe.latent_bytes_per_token(KANANA) == 6 * 576 * 2 == 6912
    # 16 rows, 54% of 5 x 128 experts hit, 16 x 10,000 live tokens
    assert roofline_mla_moe.step_bytes(KANANA, 345.6, 160_000) == pytest.approx(
        fixed + 345.6 * 9_437_184 + 160_000 * 6912)
    assert roofline_mla_moe.step_floor_ms(
        KANANA, "TPU v5 lite", 345.6, 160_000) == pytest.approx(6.575, rel=2e-3)
    with pytest.raises(KeyError):
        roofline_mla_moe.step_floor_ms(KANANA, "no such chip", 1, 1)


# ServingStats as the harness holds it (protojson through `numbers`).
STATS0 = {"decodeSteps": 1000, "ticks": 1000, "moeExpertsHit": 300_000,
          "moeLayerSteps": 5000, "moeLoadMaxSum": 20_000,
          "moeRoutedPairs": 400_000, "prefillTokensComputed": 100_000,
          "prefillTokensReused": 500_000, "tickPhaseAdmitMs": 4000.0}
STATS1 = {"decodeSteps": 3000, "ticks": 3000, "moeExpertsHit": 990_000,
          "moeLayerSteps": 15000, "moeLoadMaxSum": 70_000,
          "moeRoutedPairs": 1_360_000, "prefillTokensComputed": 460_000,
          "prefillTokensReused": 1_700_000, "tickPhaseAdmitMs": 16_000.0}


def read(name, stats0, stats1, trace=None, config=KANANA):
    roots = [BENCH_DIR]
    ctx = {"stats0": stats0, "stats1": stats1, "reader_roots": roots,
           "calls": [], "window_s": 45.0, "config": config, "trace": trace,
           "device": {"kind": "TPU v5 lite", "count": 1}}
    return plugins.load("layer_metrics", name, roots).read(ctx)


@pytest.mark.parametrize("name, value", [
    ("moe_experts_hit_share", 100.0 * 690_000 / 10_000 / 128),  # 53.9%
    ("moe_load_max_over_mean", 50_000 / 960_000 * 128),
    ("cold_prefill_tok_s", 360_000 / 12.0),
])
def test_new_readers_on_recorded_stats(name, value):
    assert read(name, STATS0, STATS1) == pytest.approx(value)


def test_roofline_reader_divides_the_floor_by_the_traced_step():
    # 2 s of the tick program over 100 launches of one step: 20 ms a step
    trace = {"program_s": 2.0, "program_runs": 100}
    hit = 690_000 / 2000  # experts a step, over its 5 expert layers
    floor = roofline_mla_moe.step_floor_ms(KANANA, "TPU v5 lite", hit, 0.0)
    assert read("mla_moe_step_roofline", STATS0, STATS1, trace) == pytest.approx(
        100.0 * floor / 20.0)
    assert read("mla_moe_step_roofline", STATS0, STATS1, None) is None


@pytest.mark.parametrize("name", NEW)
def test_new_readers_find_nothing_on_the_parent_or_in_an_idle_window(name):
    trace = {"program_s": 2.0, "program_runs": 100}
    assert read(name, STATS0, STATS0, trace) is None  # nothing happened
    # a program without the fields (the parent has the admit phase's clock)
    old = {"decodeSteps": 1000, "ticks": 1000, "tickPhaseAdmitMs": 4000.0}
    new = dict(old, decodeSteps=3000, ticks=3000, tickPhaseAdmitMs=16_000.0)
    assert read(name, old, new, trace) is None


def test_the_unlisted_roofline_reader_is_what_its_entry_will_name():
    """`mla_moe_step_roofline` has no `BENCHMARK.json` entry yet (see
    below); the contract's reader check does not reach it, so hold it
    here to what ISSUE 28 asked for."""
    reader = plugins.load("layer_metrics", "mla_moe_step_roofline", [BENCH_DIR])
    assert (reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) == (
        "%", "kernels", "call_ms_p50", "device_trace")


def test_the_cells_capture_fits_a_traced_runs_time_limit():
    """The driver stops a run at 360 s. Warm, this cell's run is ~240 s
    before the profiler writes its capture out, at 36-40 s a second
    captured (my chip runs, PR 28): one second, never the default 3."""
    sched = schedule.load("doc-sessions", 16, BENCH_DIR)
    assert 240 + sched.trace_ms / 1000.0 * 41 - 33.75 < 360 - 60  # a minute to spare


def test_new_metrics_and_cells_are_named_where_the_issue_says():
    per_layer = {m["name"]: m for m in BENCH["per_layer"]}
    # The step's two trace metrics are NOT listed for the cell: its 1 s
    # capture (all a traced run's time limit leaves room for, at ~40 s
    # of writing a second captured) falls inside a stretch of admission
    # and holds no run of the tick program (PERF.md, section 7). The
    # reader and its bytes stay, for the PR that bounds that stretch.
    assert "mla_moe_step_roofline" not in per_layer
    for name in NEW[:3]:
        # the admission counter is stamped for every family, and cold
        # chunked admission is what `long-prefill` is for
        assert per_layer[name]["workloads"] == [CELL] + (
            ["long-prefill.int8-1chip"] if name == "cold_prefill_tok_s" else [])
    lists = {n: m["workloads"] for n, m in per_layer.items() if n not in NEW}
    assert CELL not in lists.pop("decode_step_roofline")
    assert lists.pop("step_ms_mean")[-2:] == [
        "agent-shared.int8-1chip", "long-prefill.int8-1chip"]
    assert "long-prefill.int8-1chip" not in lists.pop("prefix_reuse_share")
    for name, cells in lists.items():
        assert cells[-2:] == [CELL, "long-prefill.int8-1chip"], name
    assert [w["name"] for w in BENCH["workloads"]][2:] == [
        CELL, "long-prefill.int8-1chip"]
    assert all(w["chips"] == 1 for w in BENCH["workloads"])


def test_new_cell_rehearsed_on_the_cpu_prints_its_readers():
    line, out = run_cell(REHEARSAL, "tiny-doc-sessions.cpu", 1)
    assert out[0].startswith("CPU REHEARSAL") and line["rehearsal"] is True
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 10 and line["device"]["platform"] == "cpu"
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    # a CPU run never reports a device metric: no roofline here
    assert set(metrics) == {
        "queue_ms_mean", "prefix_reuse_share", "kv_pages_used_share",
        "out_tok_s.decode", "compiles_in_window", "moe_experts_hit_share",
        "moe_load_max_over_mean", "cold_prefill_tok_s"}
    assert metrics["compiles_in_window"] == 0
    assert 10 < metrics["moe_experts_hit_share"] <= 100
    assert 1 <= metrics["moe_load_max_over_mean"] <= 8
    assert metrics["cold_prefill_tok_s"] > 0
    assert metrics["prefix_reuse_share"] > 20  # follow-ups reuse the document
    assert any("check logit_margin_mla_moe" in ln and "within" in ln for ln in out)


def test_new_cells_control_comes_out_as_not_correct():
    """`fp8_kv`, the cell's control. At this size (float32 served
    against a float32 reference) sound runs read mean_margin_sigma 0.0,
    float8 latents 0.06 (int8 ones 0.02); the rehearsal configuration's
    limit is 1e-4. On the chip, where bf16 is served, the check cannot
    tell int8 latents from bf16 (the configuration's `blind_spot`)."""
    line, out = run_cell(
        REHEARSAL, "tiny-doc-sessions.cpu", 0, "--control", "fp8_kv")
    assert line["correct"] is False, out[-4:]
    assert any("OVER" in ln for ln in out)
    assert set(line["metrics"]) == {"call_ms_p50", "setup_s"}
