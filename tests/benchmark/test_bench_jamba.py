"""What PR 49 added to the benchmark, on the CPU: the configuration
file against the catalog row and the issue's count; `chat-sessions`
holds the work whatever the seed and is what the issue says; the new
cell's bytes against a hand count; the four new readers on recorded
ServingStats, on an idle window and on a program or configuration
without what they read (the parent commit); the lists in PREFIX form
only (what earlier PRs listed is still there, first and in order, and
this cell follows); and the new cell rehearsed at the family's tiny
preset from `rehearsal_jamba/`, sound and with its two controls."""

import json
import os

import pytest

from benchmark import plugins, roofline_jamba, schedule
from benchmark.run import probe_lengths
from tests.benchmark.test_bench_rehearsal import ROOT, run_cell
from tests.benchmark.test_bench_schedule import calls_of

BENCH_DIR = os.path.join(ROOT, "benchmark")
REHEARSAL = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "rehearsal_jamba")
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
with open(os.path.join(
        BENCH_DIR, "configs", "jamba2-3b-bf16-1chip.json")) as f:
    JAMBA = json.load(f)
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELL = "chat-sessions.jamba2-bf16-1chip"
KEYE_CELL = "agent-deepctx.keye-bf16-1chip"
NEW = ("state_snapshot_hit_share", "state_recompute_share",
       "state_evictions_per_admission", "ssm_step_roofline")


def test_configuration_file_carries_every_published_key_and_the_count():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = next(r for r in rows if r["name"] == "AI21-Jamba2-3B")
    assert JAMBA["source"] == row["source_url"]
    differ = [k for k, v in row["config"].items()
              if JAMBA.get(k, "absent") != v]
    assert differ == JAMBA["reduced"] == []
    entry = next(c for c in BENCH["configs"] if c["name"] == JAMBA["name"])
    assert entry["reduced"] == [] and entry["source"] == row["source_url"]
    assert entry["file"].endswith(JAMBA["name"] + ".json")
    dep = JAMBA["deployment"]
    assert (dep["chips"], dep["replicas"]) == (1, 1)
    assert "one chip holds the whole model" in dep["stated"]
    assumed = JAMBA["assumed"]
    assert assumed["layer_order"]["value"] == "i % 14 == 7"
    assert assumed["inner_norms"]["value"] is True
    assert assumed["rotary"]["value"] == "none"
    assert assumed["head_dim"]["value"] == 128 == (
        JAMBA["hidden_size"] // JAMBA["num_attention_heads"])
    for key, word in (("state_precision", "float32"),
                      ("use_mamba_kernels", "not in the equations"),
                      ("layer_order", "not_given"), ("weights", "A_log")):
        assert word in json.dumps(assumed[key])
    assert "truncated_normal" in assumed["weights"]
    # what is served is the registry's entry, to the number
    from ggrmcp_tpu.models import jamba

    served = jamba.CONFIGS[JAMBA["stack"]["serving"]["model"]]
    assert JAMBA["registry_model"] == served.name == "jamba2-3b"
    assert (served.num_layers, served.hidden_dim, served.num_heads,
            served.num_kv_heads, served.head_dim, served.vocab_size,
            served.ffn_dim, served.d_state, served.d_conv, served.dt_rank,
            served.expand, served.attn_layer_period, served.attn_layer_offset,
            served.norm_eps, served.state_dtype, served.dtype) == (
        JAMBA["num_hidden_layers"], JAMBA["hidden_size"],
        JAMBA["num_attention_heads"], JAMBA["num_key_value_heads"], 128,
        JAMBA["vocab_size"], JAMBA["intermediate_size"],
        JAMBA["mamba_d_state"], JAMBA["mamba_d_conv"], JAMBA["mamba_dt_rank"],
        JAMBA["mamba_expand"], JAMBA["attn_layer_period"],
        JAMBA["attn_layer_offset"], JAMBA["rms_norm_eps"], "float32",
        "bfloat16")
    assert abs(jamba.num_params(served) * 2 / 1e9 - 6.06) < 0.01
    batching = JAMBA["stack"]["serving"]["batching"]
    assert (batching["max_batch_size"], batching["kv_cache_max_seq"],
            batching["prefill_chunk"], batching["paged_kv_page_size"],
            batching["max_pending"], batching["paged_kv"]) == (
        32, 8192, 512, 16, 0, "on")
    assert JAMBA["stack"]["serving"]["grammar"]["arena_states"] == 1025
    assert JAMBA["stack"]["serving"]["mesh"] == {"tensor": 1}
    assert (JAMBA["stack"]["server"]["request_timeout_s"],
            JAMBA["stack"]["grpc"]["call_timeout_s"]) == (600, 600)
    assert set(JAMBA["controls"]) == {"bf16_state", "no_snapshot_state"}
    # the first control serves the same widths with h in bfloat16
    lower = jamba.CONFIGS[
        JAMBA["controls"]["bf16_state"]["stack"]["serving"]["model"]]
    import dataclasses

    assert dataclasses.replace(
        lower, name=served.name, state_dtype="float32") == served
    assert JAMBA["check"]["name"] == "logit_margin_jamba"
    assert lower.state_dtype == "bfloat16"
    assert JAMBA["check"]["limit_read_from"].startswith("my chip runs, PR 49")


def test_the_check_limits_lie_between_their_two_readings():
    check = JAMBA["check"]
    # (sound runs' largest, the control's reading) on the chip: my chip
    # runs, PR 49 (PERF.md section 6). `mean_sq_margin_sigma` is
    # `no_snapshot_state`'s limit. `state_bytes_short_share` is
    # `bf16_state`'s: no margin of served tokens tells a bfloat16 `h`
    # from a sound run (the file says how far apart they read), the
    # bytes an entry of the served pool holds do.
    read_ = check["first_readings"]
    assert set(check["limits"]) == set(read_) == {
        "mean_sq_margin_sigma", "state_bytes_short_share"}
    sound, control = read_["mean_sq_margin_sigma"]
    assert 5 * sound < check["limits"]["mean_sq_margin_sigma"] < control / 5
    sound, control = read_["state_bytes_short_share"]
    assert sound == 0.0 and control == pytest.approx(
        26 * 16 * 5120 * 2 / 9_318_400, abs=1e-4)
    assert sound + 0.15 < check["limits"]["state_bytes_short_share"] < (
        control - 0.15)
    assert "lower_state" not in check


def _said(entries, nbytes):
    return ('{"level":"INFO","logger":"ggrmcp.serving.batching","msg":"row '
            f'states: {entries} entries x {nbytes} B an entry (bfloat16 '
            '[26, 160, 3, 5120], float32 [26, 160, 16, 5120])"}')


@pytest.mark.parametrize("log, short", [
    (_said(160, 9_318_400), 0.0),  # as served: h float32
    (_said(160, 5_058_560), 0.45714),  # control bf16_state: h bfloat16
    # a stack started twice: the last line counts; more than stated is 0
    (_said(160, 5_058_560) + "\n" + _said(192, 9_400_000), 0.0),
    ('{"msg":"sidecar serving jamba2-3b (jamba)"}', None),  # never said
])
def test_the_state_bytes_an_entry_are_read_off_the_stacks_own_line(log, short):
    check = plugins.load("checks", "logit_margin_jamba", [BENCH_DIR])
    got = check.state_bytes_short_share(log, JAMBA)
    assert got == (None if short is None else pytest.approx(short, abs=1e-5))


def test_chat_sessions_is_identical_for_two_seeds():
    a = schedule.load("chat-sessions", 32, BENCH_DIR)
    b = schedule.load("chat-sessions", 32, BENCH_DIR)
    assert a.describe() == b.describe()
    ca = calls_of(a, 7, JAMBA["vocab_size"], sessions=1)
    cb = calls_of(b, 2**31 + 11, JAMBA["vocab_size"], sessions=1)
    shape = lambda cs: [(c, s, t, len(p), o) for c, s, t, p, o in cs]  # noqa: E731
    assert shape(ca) == shape(cb)
    assert [p for *_, p, _ in ca] != [p for *_, p, _ in cb]
    assert probe_lengths(a, 16) == probe_lengths(b, 16)
    assert max(max(p) for *_, p, _ in ca) < JAMBA["vocab_size"]


def test_chat_sessions_is_what_the_issue_says():
    with open(os.path.join(BENCH_DIR, "traffic", "chat-sessions.json")) as f:
        spec = json.load(f)
    assert spec["pair_grid"] == {
        "n": 1024, "prompt": [32, 256], "output": [64, 256],
        "prompt_stride": 397, "output_stride": 611}
    assert (spec["clients_per_slot"], spec["think_time_s"]) == (1, 0)
    sched = schedule.load("chat-sessions", 32, BENCH_DIR)
    assert (sched.clients, sched.session_turns, len(sched.pairs)) == (
        32, 8, 1024)
    assert sched.shared_prefix_tokens == 2048 and sched.think_time_s == 0
    assert (sched.ramp, sched.trace_ms, sched.loop) == ("call", 1000, "closed")
    news = [p for p, _ in sched.pairs]
    outs = [o for _, o in sched.pairs]
    assert (min(news), max(news), sum(news) / 1024) == (32, 256, 144.0)
    assert (min(outs), max(outs), sum(outs) / 1024) == (64, 256, 160.0)
    # client i starts 4 sessions down the list from client i - 1
    assert list(sched.offsets) == [32 * i for i in range(32)]
    assert sched.longest_prompt() == 4651
    totals, suffixes = [], []
    for start in range(0, 1024, 8):
        block = sched.pairs[start:start + 8]
        totals.append(sum(p + o for p, o in block))
        suffixes += [block[k - 1][1] + block[k][0] for k in range(1, 8)]
    assert (min(totals), max(totals)) == (2072, 2694)
    assert (min(suffixes), max(suffixes)) == (103, 506)
    assert round(sum(suffixes) / len(suffixes)) == 304
    # the whole of a session, its last output included, fits a slot
    batching = JAMBA["stack"]["serving"]["batching"]
    assert 2048 + max(totals) + 24 <= batching["kv_cache_max_seq"]
    # the system prompt ends on a multiple of prefill_chunk: a snapshot
    assert sched.shared_prefix_tokens % batching["prefill_chunk"] == 0


def test_step_bytes_by_hand():
    # a Mamba mixer: W_in 2560 x 10240, conv 4 x 5120 + bias, W_x 5120 x
    # 192, the inner norms 192, W_dt 160 x 5120, W_out 5120 x 2560, norm
    narrow = (2560 * 10240 + 4 * 5120 + 5120 + 5120 * 192 + 192
              + 160 * 5120 + 5120 * 2560 + 2560)
    wide = 5120 + 16 * 5120 + 5120  # b_dt, A_log, D: float32
    assert roofline_jamba.mamba_params(JAMBA) == (narrow, wide)
    assert narrow + wide == 41_244_352  # ISSUE 49: 41.24M (the norm here too)
    attn = 2560 * 22 * 128 + 2560 * 2560 + 2560
    assert roofline_jamba.attention_params(JAMBA) == attn == 13_765_120
    mlp = 3 * 2560 * 8192 + 2560
    assert roofline_jamba.mlp_params(JAMBA) == mlp == 62_917_120
    assert roofline_jamba.layer_counts(JAMBA) == (26, 2)
    weights = (26 * narrow + 2 * attn + 28 * mlp + 65536 * 2560 + 2560) * 2 + (
        26 * wide * 4)
    assert roofline_jamba.weight_bytes(JAMBA) == weights
    assert weights == pytest.approx(6.06e9, rel=2e-3)  # ISSUE 49: 6.06 GB
    assert roofline_jamba.state_bytes_per_row(JAMBA) == 26 * (
        3 * 5120 * 2 + 16 * 5120 * 4) == 9_318_400
    assert roofline_jamba.kv_bytes_per_token(JAMBA) == 2 * 2 * 128 * 2 == 1024
    # 32 rows at 3,400 live tokens each
    step = roofline_jamba.step_bytes(JAMBA, 32, 32 * 3400)
    assert step == weights + 2 * 32 * 9_318_400 + 108_800 * 1024
    assert step == pytest.approx(6.77e9, rel=5e-3)  # ISSUE 49: ~6.8 GB
    assert roofline_jamba.step_floor_ms(
        JAMBA, "TPU v5 lite", 32, 108_800) == pytest.approx(
        step / 819e9 * 1000.0)
    with pytest.raises(KeyError):
        roofline_jamba.step_floor_ms(JAMBA, "no such chip", 1, 1)


class _Call:
    def __init__(self, prompt, n):
        self.prompt, self.completion_tokens, self.ok = [0] * prompt, n, True


STATS0 = {"decodeSteps": 1000, "ticks": 1000, "stateSnapshotLookups": 100,
          "stateSnapshotHits": 90, "stateTokensMatched": 200_000,
          "stateTokensRecomputed": 1_000, "stateSnapshotEvictions": 40,
          "statePoolInUse": 60, "statePoolTotal": 128}
STATS1 = {"decodeSteps": 3000, "ticks": 3000, "stateSnapshotLookups": 500,
          "stateSnapshotHits": 480, "stateTokensMatched": 1_400_000,
          "stateTokensRecomputed": 7_000, "stateSnapshotEvictions": 640,
          "statePoolInUse": 128, "statePoolTotal": 128}


def read(name, stats0, stats1, trace=None, config=JAMBA, calls=()):
    roots = [BENCH_DIR]
    ctx = {"stats0": stats0, "stats1": stats1, "reader_roots": roots,
           "calls": list(calls), "window_s": 45.0, "config": config,
           "trace": trace, "device": {"kind": "TPU v5 lite", "count": 1}}
    return plugins.load("layer_metrics", name, roots).read(ctx)


def test_the_new_readers_on_recorded_stats():
    assert read("state_snapshot_hit_share", STATS0, STATS1) == pytest.approx(
        100.0 * 390 / 400)
    assert read("state_recompute_share", STATS0, STATS1) == pytest.approx(
        100.0 * 6_000 / 1_200_000)
    assert read("state_evictions_per_admission", STATS0, STATS1) == 1.5
    # a pool with room drops nothing (zeros are left out of the JSON)
    roomy = {k: v for k, v in STATS1.items() if k != "stateSnapshotEvictions"}
    assert read("state_evictions_per_admission", {}, roomy) == 0.0
    # 2 s of the tick program over 100 launches of one step: 20 ms a step
    trace = {"program_s": 2.0, "program_runs": 100}
    calls = [_Call(3_000, 160)] * 400
    rows = roofline_jamba.rows_per_step(calls, 2000)
    assert rows == 32.0
    live = roofline_jamba.live_tokens_per_step(calls, 2000)
    floor = roofline_jamba.step_floor_ms(JAMBA, "TPU v5 lite", rows, live)
    got = read("ssm_step_roofline", STATS0, STATS1, trace, calls=calls)
    assert got == pytest.approx(100.0 * floor / 20.0) and 0 < got < 100


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_finds_nothing_where_there_is_nothing_to_read(name):
    trace = {"program_s": 2.0, "program_runs": 100}
    calls = [_Call(3_000, 160)] * 400
    # a program without the fields: the parent commit's ServingStats,
    # or a family without a state pool (zeros are left out of the JSON)
    old0 = {"decodeSteps": 1000, "ticks": 1000}
    old1 = {"decodeSteps": 3000, "ticks": 3000}
    assert read(name, old0, old1, trace, calls=calls) is None
    assert read(name, old0, old1, None, calls=calls) is None
    if name == "ssm_step_roofline":
        assert read(name, STATS0, STATS1, None, calls=calls) is None
        assert read(name, STATS0, dict(STATS1, decodeSteps=1000), trace,
                    calls=calls) is None
        # another configuration has no state-space layers
        with open(os.path.join(
                BENCH_DIR, "configs", "mistral-7b-int8-1chip.json")) as f:
            assert read(name, STATS0, STATS1, trace, config=json.load(f),
                        calls=calls) is None
    else:
        assert read(name, STATS0, STATS0, trace) is None


def test_earlier_lists_are_prefixes_of_todays_and_this_cell_follows():
    """Prefix form only: every cell and every list as an earlier commit
    had them is still there, first and in order; this PR's cell comes
    after them where ISSUE 49 says, and whatever a later PR appends
    after it breaks nothing here."""
    with open(os.path.join(DATA, "benchmark_at_pr34.json")) as f:
        parent = json.load(f)  # BENCHMARK.json as commit 98cb803 had it
    cells = [w["name"] for w in BENCH["workloads"]]
    old_cells = [w["name"] for w in parent["workloads"]]
    assert cells[: len(old_cells)] == old_cells and CELL in cells[len(old_cells):]
    assert cells.index(CELL) > cells.index(KEYE_CELL)
    assert BENCH["workloads"][: len(old_cells)] == parent["workloads"]
    assert BENCH["configs"][: len(parent["configs"])] == parent["configs"]
    assert BENCH["end_to_end"] == parent["end_to_end"]
    assert (BENCH["run_seconds"], BENCH["command"], BENCH["paths"]) == (
        parent["run_seconds"], parent["command"], parent["paths"])
    per_layer = {m["name"]: m for m in BENCH["per_layer"]}
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[: len(parent["per_layer"])] == [
        m["name"] for m in parent["per_layer"]]
    for old in parent["per_layer"]:
        new = per_layer[old["name"]]
        assert {k: v for k, v in new.items() if k != "workloads"} == {
            k: v for k, v in old.items() if k != "workloads"}
        assert new["workloads"][: len(old["workloads"])] == old["workloads"]
    # on every list all six earlier cells are on, and on four more
    six = cells[:6]
    for m in BENCH["per_layer"]:
        if m["name"] in NEW:
            continue
        want = (all(c in m["workloads"] for c in six) or m["name"] in (
            "prefix_reuse_share", "step_ms_mean", "out_tok_s.decode",
            "prefill_device_tok_s"))
        assert (CELL in m["workloads"]) == want, m["name"]
    # not under out_tok_s, whose bound is 1%
    assert CELL not in next(
        m for m in BENCH["end_to_end"] if m["name"] == "out_tok_s")["workloads"]
    for name in NEW:
        assert per_layer[name]["workloads"][0] == CELL
        assert per_layer[name]["moves"] == "call_ms_p50"
    assert {k: v for k, v in per_layer["ssm_step_roofline"].items()
            if k != "workloads"} == {
        "name": "ssm_step_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels", "moves": "call_ms_p50"}
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        JAMBA["name"], "chat-sessions", 1)
    assert len(entry["why"]) <= 200


def test_new_cell_rehearsed_on_the_cpu_prints_its_readers():
    line, out = run_cell(REHEARSAL, "tiny-chat-sessions.cpu", 1)
    assert out[0].startswith("CPU REHEARSAL") and line["rehearsal"] is True
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 10 and line["device"]["platform"] == "cpu"
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    # a CPU run never reports a device metric: no roofline here
    assert set(metrics) == {
        "queue_ms_mean", "prefix_reuse_share", "kv_pages_used_share",
        "out_tok_s.decode", "compiles_in_window", "state_snapshot_hit_share",
        "state_recompute_share", "state_evictions_per_admission"}
    assert metrics["compiles_in_window"] == 0
    # every turn finds the shared prompt's snapshot or its session's own
    assert metrics["state_snapshot_hit_share"] > 90
    assert metrics["state_recompute_share"] < 10
    assert metrics["state_evictions_per_admission"] >= 0
    assert metrics["prefix_reuse_share"] > 50
    assert any("check logit_margin_jamba" in ln and "session prefixes" in ln
               for ln in out)
    assert any("check logit_margin_jamba" in ln and "within" in ln for ln in out)


@pytest.mark.parametrize("control", ["bf16_state", "no_snapshot_state"])
def test_new_cells_controls_come_out_as_not_correct(control):
    """h in bfloat16 in the pool, and a restore that leaves the slot's
    state zero: at this size (float32 served against a float32
    reference) sound runs read 0.0 on both statistics; either control
    moves the margin over its limit."""
    line, out = run_cell(
        REHEARSAL, "tiny-chat-sessions.cpu", 0, "--control", control)
    assert line["correct"] is False and line["failed"] == 0, out[-4:]
    assert any("check logit_margin_jamba" in ln and "mean_margin_sigma" in ln
               and "OVER" in ln for ln in out)
    # the pool's bytes tell the first control, and only it
    said = "(limit 0.2: " + ("OVER" if control == "bf16_state" else "within")
    assert any("state_bytes_short_share = 0." in ln and said in ln
               for ln in out), out[-4:]


def test_operations_are_summed_by_shape_inside_the_program_that_ran_them():
    """scripts/trace_ops_by_shape.py on the recorded decode trace: two
    patterns that split every operation between them add up to the
    tick's busy time, an operation counts under the first it matches,
    and the eager programs around the tick come out on lines of their
    own."""
    import re
    import sys

    sys.path.insert(0, os.path.join(ROOT, "scripts"))
    import trace_ops_by_shape
    from benchmark import xplane

    planes = xplane.load(os.path.join(DATA, "decode_tick.xplane.pb"))
    rows = {r["program"]: r for r in trace_ops_by_shape.by_program(
        planes, {"arena": re.compile(r"= bf16\[32,1024,16,8,128\]"),
                 "rest": re.compile(".")})}
    tick = rows["_tick_impl"]
    assert tick["runs"] == 1 and set(rows) > {"_tick_impl", "squeeze"}
    assert 0 < tick["ms"]["arena"] < tick["ms"]["rest"]
    assert sum(tick["ms"].values()) <= tick["program_ms"]
    assert sum(tick["share"].values()) == pytest.approx(1.0, abs=0.01)
    assert all(k.startswith(("arena: ", "rest: ")) for k, _ in tick["longest"])
    assert tick["longest"][0][0].startswith("arena: %copy.136 = bf16[32,1024")
