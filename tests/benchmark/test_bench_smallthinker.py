"""What PR 53 added to the benchmark, on the CPU: the configuration
file against the catalog row and the issue's count; `mixed-ctx` holds
the work whatever the seed and is what the issue says; the new cell's
bytes against a hand count; the four new readers on recorded
ServingStats, and on a program, a configuration or a capture WITHOUT
what they read (the parent commit's program, every other cell: each
returns None and does not raise, which is what PR 42 was refused for);
the check's sample takes a long session first; the lists in PREFIX form
only; and the new cell rehearsed at the family's tiny preset from
`rehearsal_smallthinker/`, sound and with its two controls."""

import glob
import json
import os

import pytest

from benchmark import plugins, roofline_smallthinker, schedule
from benchmark.run import probe_lengths
from tests.benchmark.test_bench_rehearsal import ROOT, run_cell
from tests.benchmark.test_bench_schedule import calls_of

BENCH_DIR = os.path.join(ROOT, "benchmark")
HERE = os.path.dirname(os.path.abspath(__file__))
REHEARSAL = os.path.join(HERE, "rehearsal_smallthinker")
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
with open(os.path.join(
        BENCH_DIR, "configs", "smallthinker-21b-a3b-bf16-1chip.json")) as f:
    MODEL = json.load(f)
CELL = "mixed-ctx.smallthinker-bf16-1chip"
NEW = ("window_step_roofline", "window_keys_read_share",
       "kv_window_pages_used_share", "window_pages_freed_share")


def test_configuration_file_carries_every_published_key_and_the_count():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = next(r for r in rows if r["name"] == "SmallThinker-21BA3B-Instruct")
    assert MODEL["source"] == row["source_url"]
    differ = [k for k, v in row["config"].items()
              if MODEL.get(k, "absent") != v]
    assert differ == MODEL["reduced"] == ["num_hidden_layers"]
    assert MODEL["num_hidden_layers"] == 8
    assert MODEL["n_routed_experts"] == MODEL["moe_num_primary_experts"] == 64
    entry = next(c for c in BENCH["configs"] if c["name"] == MODEL["name"])
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == row["source_url"]
    assert entry["file"].endswith(MODEL["name"] + ".json")
    dep = MODEL["deployment"]
    assert (dep["pipeline_stages"], dep["stage"]) == (7, 0)
    assert "device_idle_share" in dep["stated"]
    assert set(MODEL["assumed"]) >= {
        "attention_bias", "qk_norm", "router_input", "window_convention",
        "rope_pairing", "secondary_experts", "relu_gate", "context"}
    for key in ("attention_bias", "qk_norm", "router_input",
                "window_convention", "rope_pairing", "secondary_experts",
                "relu_gate", "context"):
        assert MODEL["assumed"][key]["why"]
    # what is served is the registry's entry, to the number
    from ggrmcp_tpu.models import smallthinker

    served = smallthinker.CONFIGS[MODEL["stack"]["serving"]["model"]]
    assert MODEL["registry_model"] == served.name == "smallthinker-21b-a3b-8l"
    assert (served.num_layers, served.hidden_dim, served.num_heads,
            served.num_kv_heads, served.head_dim, served.vocab_size,
            served.num_experts, served.experts_per_token,
            served.expert_ffn_dim, served.sliding_window, served.rope_theta,
            served.norm_eps, served.max_seq_len, served.dtype,
            served.expert_act, served.router_scoring) == (
        8, MODEL["hidden_size"], MODEL["num_attention_heads"],
        MODEL["num_key_value_heads"], MODEL["head_dim"], MODEL["vocab_size"],
        MODEL["moe_num_primary_experts"],
        MODEL["moe_num_active_primary_experts"], MODEL["moe_ffn_hidden_size"],
        MODEL["sliding_window_size"], MODEL["rope_theta"],
        MODEL["rms_norm_eps"], MODEL["max_position_embeddings"], "bfloat16",
        "relu", "softmax")
    layout = MODEL["sliding_window_layout"]
    assert layout == MODEL["rope_layout"] and len(layout) == 52
    assert [("full", "window")[k] for k in layout[:8]] == list(
        served.layer_kinds)
    assert abs(smallthinker.num_params(served) * 2 / 1e9 - 7.93) < 0.01
    batching = MODEL["stack"]["serving"]["batching"]
    assert (batching["max_batch_size"], batching["kv_cache_max_seq"],
            batching["prefill_chunk"], batching["paged_kv_page_size"],
            batching["max_pending"], batching["paged_kv"]) == (
        32, 16384, 512, 16, 0, "on")
    assert "paged_kv_pages" not in batching  # no new option, none set
    assert MODEL["stack"]["serving"]["grammar"]["arena_states"] == 1025
    assert MODEL["stack"]["serving"]["mesh"] == {"tensor": 1}
    assert (MODEL["stack"]["server"]["request_timeout_s"],
            MODEL["stack"]["grpc"]["call_timeout_s"]) == (600, 600)
    assert MODEL["controls"] == {
        "fp8_kv": {"stack": {"serving": {"kv_cache_dtype": "fp8"}}},
        "no_window": {"check": {"no_window": True}}}
    assert MODEL["check"]["name"] == "logit_margin_smallthinker"
    assert "lower_planes" not in MODEL["check"]  # told nothing here
    # a long session is one past the window and its chunk
    assert MODEL["check"]["long_over"] >= 4096 + 512
    assert MODEL["check"]["limit_read_from"].startswith("my chip runs, PR 53")


def test_the_check_limits_lie_between_their_two_readings():
    check = MODEL["check"]
    read_ = check["first_readings"]
    # (sound runs' largest, the control's reading) on the chip: my chip
    # runs, PR 53, call A. `flip_share` is fp8_kv's limit,
    # `mean_sq_margin_sigma` no_window's.
    assert set(check["limits"]) == set(read_) == {
        "flip_share", "mean_sq_margin_sigma"}
    for name, limit in check["limits"].items():
        sound, control = read_[name]
        assert sound * 1.3 < limit < control / 1.3, name


def test_mixed_ctx_is_identical_for_two_seeds():
    a = schedule.load("mixed-ctx", 32, BENCH_DIR)
    b = schedule.load("mixed-ctx", 32, BENCH_DIR)
    assert a.describe() == b.describe()
    ca = calls_of(a, 7, MODEL["vocab_size"], sessions=1)
    cb = calls_of(b, 2**31 + 11, MODEL["vocab_size"], sessions=1)
    shape = lambda cs: [(c, s, t, len(p), o) for c, s, t, p, o in cs]  # noqa: E731
    assert shape(ca) == shape(cb)
    assert [p for *_, p, _ in ca] != [p for *_, p, _ in cb]
    assert probe_lengths(a, 16) == probe_lengths(b, 16) == [
        128, 256, 496, 1008, 8192, 10240]
    assert max(max(p) for *_, p, _ in ca) < MODEL["vocab_size"]


def test_mixed_ctx_is_what_the_issue_says():
    with open(os.path.join(BENCH_DIR, "traffic", "mixed-ctx.json")) as f:
        spec = json.load(f)
    assert "pair_grid" not in spec and len(spec["pairs"]) == 96 * 16
    assert (spec["clients_per_slot"], spec["think_time_s"]) == (1, 0)
    sched = schedule.load("mixed-ctx", 32, BENCH_DIR)
    assert (sched.clients, sched.session_turns, len(sched.pairs)) == (
        32, 16, 1536)
    assert sched.shared_prefix_tokens == 0 and sched.think_time_s == 0
    assert (sched.ramp, sched.trace_ms, sched.loop) == ("call", 1000, "closed")
    # client i starts at session 3 i: 24 on a long one, 8 on a short one
    assert list(sched.offsets) == [3 * 16 * i for i in range(32)]
    firsts = [sched.pairs[s * 16][0] for s in range(96)]
    longs = [p for s, p in enumerate(firsts) if s % 4 != 3]
    shorts = [p for s, p in enumerate(firsts) if s % 4 == 3]
    assert sorted(set(longs)) == [6144 + 512 * k for k in range(9)]
    assert all(longs.count(v) == 8 for v in set(longs))
    assert len(set(shorts)) == 24 and (min(shorts), max(shorts)) == (272, 1008)
    starts = [firsts[3 * i] for i in range(32)]
    assert sum(p >= 6144 for p in starts) == 24
    news = [p for k, (p, _) in enumerate(sched.pairs) if k % 16]
    outs = [o for _, o in sched.pairs]
    assert (min(news), max(news), sum(news) / len(news)) == (32, 128, 80.0)
    assert (min(outs), max(outs), sum(outs) / len(outs)) == (64, 192, 128.0)
    totals = [sum(p + o for p, o in sched.pairs[s:s + 16])
              for s in range(0, 1536, 16)]
    assert max(totals) == 13665 and sched.longest_prompt() == 13548
    # a whole session, its last output included, fits a slot; a turn's
    # suffix (last output + new tokens + a ragged page) fits one chunk
    batching = MODEL["stack"]["serving"]["batching"]
    assert max(totals) + 24 <= batching["kv_cache_max_seq"]
    assert max(outs) + max(news) + 15 <= batching["prefill_chunk"]
    # no client reaches another's first session inside ramp + 45 s: at
    # 2 s a call a client makes ~25 calls, under two sessions of 16
    assert 3 * 16 > 2 * 16


def test_step_bytes_by_hand():
    attn = 2560 * 28 * 128 + 2 * 2560 * 4 * 128 + 28 * 128 * 2560
    assert roofline_smallthinker.attention_params(MODEL) == attn == 20_971_520
    assert roofline_smallthinker.expert_bytes(MODEL) == 3 * 2560 * 768 * 2
    assert roofline_smallthinker.expert_bytes(MODEL) == 11_796_480  # 11.8 MB
    assert roofline_smallthinker.layer_counts(MODEL) == (2, 6)
    fixed = (8 * attn + 8 * 2 * 2560 + 2560 + 2560 * 151936) * 2 + (
        8 * 2560 * 64 * 4)
    assert roofline_smallthinker.fixed_weight_bytes(MODEL) == fixed
    assert fixed == pytest.approx(0.336e9 + 0.778e9 + 0.005e9, rel=5e-3)
    assert roofline_smallthinker.kv_bytes_per_key(MODEL) == 2 * 4 * 128 * 2
    # ISSUE 53's step: 61 of 64 experts hit in each of 8 layers, 24 rows
    # at 10.5k keys and 8 at 600
    context = 24 * 10_500 + 8 * 600
    windowed = 24 * 4096 + 8 * 600
    step = roofline_smallthinker.step_bytes(MODEL, 61 * 8, context, windowed)
    assert step == fixed + 61 * 8 * 11_796_480 + (
        2 * context + 6 * windowed) * 2048
    assert step == pytest.approx(9.2e9, rel=0.02)  # ISSUE 53: 9.3 GB
    assert roofline_smallthinker.step_floor_ms(
        MODEL, "TPU v5 lite", 61 * 8, context, windowed) == pytest.approx(
        step / 819e9 * 1000.0)
    with pytest.raises(KeyError):
        roofline_smallthinker.step_floor_ms(MODEL, "no such chip", 1, 1, 1)


class _Call:
    def __init__(self, prompt, n):
        self.prompt, self.completion_tokens, self.ok = [0] * prompt, n, True


def test_keys_a_step_by_hand():
    # 100 steps: one call of 5,000 + 50 tokens, one of 100 + 50
    calls = [_Call(5_000, 50), _Call(100, 50)]
    context, windowed = roofline_smallthinker.keys_per_step(calls, 100, 4096)
    assert context == (5_000 * 50 + 100 * 50 + 2 * 50 * 51 // 2) / 100
    assert windowed == (4096 * 50 + 100 * 50 + 50 * 51 // 2) / 100
    assert roofline_smallthinker.keys_per_step(calls, 0, 4096) == (0.0, 0.0)


STATS0 = {"decodeSteps": 1000, "ticks": 125, "moeExpertsHit": 480_000,
          "moeLayerSteps": 8000, "windowKeysRead": 1_000_000,
          "windowKeysContext": 2_400_000, "pagedWindowPagesFreed": 500,
          "pagedWindowPagesMapped": 800, "kvWindowPagesTotal": 9344,
          "kvWindowPagesInUse": 9000}
STATS1 = {"decodeSteps": 3000, "ticks": 375, "moeExpertsHit": 1_456_000,
          "moeLayerSteps": 24000, "windowKeysRead": 3_000_000,
          "windowKeysContext": 7_400_000, "pagedWindowPagesFreed": 2000,
          "pagedWindowPagesMapped": 2800, "kvWindowPagesTotal": 9344,
          "kvWindowPagesInUse": 9100}
SAMPLES = [dict(STATS0, kvWindowPagesInUse=n) for n in (9344, 8409.6)]


def read(name, stats0, stats1, trace=None, config=MODEL, calls=(),
         samples=()):
    roots = [BENCH_DIR]
    ctx = {"stats0": stats0, "stats1": stats1, "reader_roots": roots,
           "calls": list(calls), "window_s": 45.0, "config": config,
           "trace": trace, "samples": list(samples),
           "device": {"kind": "TPU v5 lite", "count": 1}}
    return plugins.load("layer_metrics", name, roots).read(ctx)


def test_the_new_readers_on_recorded_stats():
    assert read("window_keys_read_share", STATS0, STATS1) == pytest.approx(40.0)
    assert read("window_pages_freed_share", STATS0, STATS1) == pytest.approx(
        75.0)
    assert read("kv_window_pages_used_share", STATS0, STATS1,
                samples=SAMPLES) == pytest.approx(95.0)
    # 4 s of the tick program over 250 launches of 8 steps: 2 ms a step
    trace = {"program_s": 4.0, "program_runs": 250}
    calls = [_Call(8_000, 128)] * 300 + [_Call(600, 128)] * 100
    context, windowed = roofline_smallthinker.keys_per_step(calls, 2000, 4096)
    floor = roofline_smallthinker.step_floor_ms(
        MODEL, "TPU v5 lite", (1_456_000 - 480_000) / 2000, context, windowed)
    got = read("window_step_roofline", STATS0, STATS1, trace, calls=calls)
    assert got == pytest.approx(100.0 * floor / 2.0)


OLD_CONFIGS = sorted(
    p for p in glob.glob(os.path.join(BENCH_DIR, "configs", "*.json"))
    if not p.endswith(MODEL["name"] + ".json"))


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_finds_nothing_where_there_is_nothing_to_read(name):
    """The parent commit's program (its ServingStats have none of the
    fields; proto3 JSON leaves zeros out too), an idle window, no
    capture, and every OTHER cell's configuration with this program's
    stats: None, never an exception."""
    trace = {"program_s": 4.0, "program_runs": 250}
    calls = [_Call(8_000, 128)] * 300
    old0 = {"decodeSteps": 1000, "ticks": 125, "moeExpertsHit": 1000}
    old1 = {"decodeSteps": 3000, "ticks": 375, "moeExpertsHit": 9000}
    old_samples = [{"kvPagesTotal": 32768, "kvPagesInUse": 900}] * 3
    if name != "window_step_roofline":
        assert read(name, old0, old1, trace, calls=calls,
                    samples=old_samples) is None
        assert read(name, old0, old1, None, samples=[]) is None
        assert read(name, {}, {}, None) is None
        assert read(name, STATS0, STATS0, trace) is None or name == (
            "kv_window_pages_used_share")
    else:
        # the bytes are this configuration's: no capture, no steps, no
        # expert counters, or another configuration give None
        assert read(name, STATS0, STATS1, None, calls=calls) is None
        assert read(name, STATS0, dict(STATS1, decodeSteps=1000), trace,
                    calls=calls) is None
        assert read(name, {"decodeSteps": 1000, "ticks": 125},
                    {"decodeSteps": 3000, "ticks": 375}, trace,
                    calls=calls) is None
        assert read(name, {}, {}, {}) is None
    assert len(OLD_CONFIGS) == 5
    for path in OLD_CONFIGS:
        with open(path) as f:
            other = json.load(f)
        got = read(name, old0, old1, trace, config=other, calls=calls,
                   samples=old_samples)
        assert got is None, (name, path)


def test_every_reader_an_old_cell_lists_still_reads_the_parents_stats():
    """ROADMAP B14 / A5(u): with this PR's files laid over the parent's
    checkout, a traced run of a cell the parent had loads every reader
    that cell lists. None of them may raise on the parent's stats, an
    empty capture and no samples."""
    old = [w["name"] for w in BENCH["workloads"] if w["name"] != CELL]
    with open(OLD_CONFIGS[0]) as f:
        config = json.load(f)
    for m in BENCH["per_layer"]:
        if not set(m.get("workloads", old)) & set(old):
            continue
        reader = plugins.load("layer_metrics", m["name"], [BENCH_DIR])
        assert reader.UNIT == m["unit"]
        ctx = {"stats0": {}, "stats1": {"decodeSteps": 10, "ticks": 2},
               "samples": [], "memory": {}, "memory_peak_bytes": 0,
               "trace": None, "calls": [], "window_s": 45.0, "config": config,
               "device": {"kind": "TPU v5 lite", "count": 1},
               "cell": {"name": old[0]}, "sched": None,
               "reader_roots": [BENCH_DIR]}
        reader.read(ctx)  # whatever it returns, it returns


def test_the_checks_sample_takes_a_long_session_first():
    check = plugins.load("checks", "logit_margin_smallthinker", [BENCH_DIR])

    class Call:
        def __init__(self, client, session, turn, prompt, out, done):
            self.client, self.session, self.turn = client, session, turn
            self.prompt, self.output, self.done = prompt, out, done
            self.ok, self.phase = True, "run"
            self.segments = [[len(prompt), len(prompt) + len(out)]]

    def session(client, first, turns, t):
        calls, hist = [], []
        for k in range(turns):
            prompt = hist + [client] * (first if k == 0 else 40)
            calls.append(Call(client, 0, k, prompt, [9] * 100, t + k))
            hist = prompt + [9] * 100
        return calls

    # clients 0 and 1 short (a prompt sum decides where the walk starts),
    # client 2 long; every call completes inside the window
    calls = session(0, 300, 3, 1.0) + session(1, 500, 3, 1.0) + session(
        2, 7000, 3, 1.0)
    for budget in (24576, 8000):
        got = check.sample(calls, 0.0, 10.0, budget, long_over=4608)
        assert len(got[0]["ids"]) == 7000 + 100 + 2 * 140 > 4608
        assert sum(len(s["ids"]) for s in got[1:]) + len(got[0]["ids"]) <= max(
            budget, len(got[0]["ids"]))
    # the rest follow in the walk's order, and fit what is left
    assert [len(s["ids"]) for s in check.sample(
        calls, 0.0, 10.0, 24576, 4608)[1:]] in ([680, 880], [880, 680])
    # without a long session the sample is what the walk gives
    short = session(0, 300, 3, 1.0) + session(1, 500, 3, 1.0)
    assert len(check.sample(short, 0.0, 10.0, 24576, 4608)) == 2
    assert check.sample([], 0.0, 10.0, 24576, 4608) == []


def test_earlier_lists_are_prefixes_of_todays_and_this_cell_follows():
    """Prefix form only: every cell and every list as the parent commit
    had them is still there, first and in order; this PR's cell comes
    after them where ISSUE 53 says, and whatever a later PR appends
    after it breaks nothing here."""
    with open(os.path.join(HERE, "data", "benchmark_at_pr52.json")) as f:
        parent = json.load(f)  # BENCHMARK.json as commit db1153f had it
    cells = [w["name"] for w in BENCH["workloads"]]
    old_cells = [w["name"] for w in parent["workloads"]]
    assert len(old_cells) == 7
    assert cells[:7] == old_cells and cells[7] == CELL
    assert BENCH["workloads"][:7] == parent["workloads"]
    assert BENCH["configs"][: len(parent["configs"])] == parent["configs"]
    assert BENCH["end_to_end"] == parent["end_to_end"]
    assert (BENCH["run_seconds"], BENCH["command"], BENCH["paths"]) == (
        parent["run_seconds"], parent["command"], parent["paths"])
    per_layer = {m["name"]: m for m in BENCH["per_layer"]}
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[: len(parent["per_layer"])] == [
        m["name"] for m in parent["per_layer"]]
    assert names[len(parent["per_layer"]):][:4] == list(NEW)
    for old in parent["per_layer"]:
        new = per_layer[old["name"]]
        assert {k: v for k, v in new.items() if k != "workloads"} == {
            k: v for k, v in old.items() if k != "workloads"}
        assert new["workloads"][: len(old["workloads"])] == old["workloads"]
        want = (all(c in old["workloads"] for c in old_cells)
                or old["name"] in (
                    "prefix_reuse_share", "out_tok_s.decode", "step_ms_mean",
                    "moe_experts_hit_share", "moe_load_max_over_mean",
                    "prefill_device_tok_s"))
        assert (CELL in new["workloads"]) == want, old["name"]
    # not under out_tok_s, whose bound is 1%
    assert CELL not in next(
        m for m in BENCH["end_to_end"] if m["name"] == "out_tok_s")["workloads"]
    for name in NEW:
        assert per_layer[name]["workloads"][0] == CELL
        assert per_layer[name]["moves"] == "call_ms_p50"
        reader = plugins.load("layer_metrics", name, [BENCH_DIR])
        assert (reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) == (
            per_layer[name]["unit"], per_layer[name]["layer"], "call_ms_p50",
            per_layer[name]["source"])
    assert {k: v for k, v in per_layer["window_step_roofline"].items()
            if k != "workloads"} == {
        "name": "window_step_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels", "moves": "call_ms_p50"}
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        MODEL["name"], "mixed-ctx", 1)
    assert len(entry["why"]) <= 200


def test_new_cell_rehearsed_on_the_cpu_prints_its_readers():
    line, out = run_cell(REHEARSAL, "tiny-mixed-ctx.cpu", 1)
    assert out[0].startswith("CPU REHEARSAL") and line["rehearsal"] is True
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 10 and line["device"]["platform"] == "cpu"
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    # a CPU run never reports a device metric: no roofline here
    assert set(metrics) == {
        "queue_ms_mean", "prefix_reuse_share", "kv_pages_used_share",
        "out_tok_s.decode", "compiles_in_window", "moe_experts_hit_share",
        "window_keys_read_share", "kv_window_pages_used_share",
        "window_pages_freed_share"}
    assert metrics["compiles_in_window"] == 0
    # contexts of 100-190 tokens under a window of 32
    assert 15 < metrics["window_keys_read_share"] < 45
    assert 0 < metrics["kv_window_pages_used_share"] <= 100
    assert 0 < metrics["window_pages_freed_share"] <= 100
    assert metrics["prefix_reuse_share"] > 40
    assert any("check logit_margin_smallthinker" in ln
               and "the first is longer than 64" in ln for ln in out)
    assert any("check logit_margin_smallthinker" in ln and "within" in ln
               for ln in out)


@pytest.mark.parametrize("control", ["fp8_kv", "no_window"])
def test_new_cells_controls_come_out_as_not_correct(control):
    """Pages and the admission mini in float8, and the reference
    without its window mask: at this size (float32 served against a
    float32 reference) sound runs read 0.0; either control moves the
    margin over its limit."""
    line, out = run_cell(
        REHEARSAL, "tiny-mixed-ctx.cpu", 0, "--control", control)
    assert line["correct"] is False and line["failed"] == 0, out[-4:]
    assert any("check logit_margin_smallthinker" in ln
               and "mean_margin_sigma" in ln and "OVER" in ln for ln in out)
    if control == "no_window":
        assert any("WITHOUT its window" in ln for ln in out)
