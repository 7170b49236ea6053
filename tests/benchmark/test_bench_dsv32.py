"""What PR 33 added to the benchmark, on the CPU: the configuration
file against the catalog row and the guide's floors; the two traffic
files hold the work whatever the seed and are what the issue says
(`mixed-queue`'s has no cell yet: PERF.md, section 7); the
new cell's bytes against a hand count; the new readers on recorded
ServingStats, on an idle window and on a program without the counters
(the parent commit); the check's session-prefix sampler; and the new
cell rehearsed at the member's tiny preset from `rehearsal_dsv32/`,
sound and with its two controls."""

import json
import os
import subprocess

import pytest

from benchmark import plugins, roofline_dsv32, schedule
from benchmark.run import probe_lengths
from tests.benchmark.test_bench_rehearsal import ROOT, run_cell
from tests.benchmark.test_bench_schedule import calls_of

BENCH_DIR = os.path.join(ROOT, "benchmark")
REHEARSAL = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "rehearsal_dsv32")
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
with open(os.path.join(
        BENCH_DIR, "configs", "deepseek-v3.2-bf16-ep16-1chip.json")) as f:
    DSV32 = json.load(f)
CELL, MIXED = "agent-longctx.dsv32-bf16-1chip", "mixed-queue.int8-1chip"
KANANA_CELL = "doc-sessions.kanana-bf16-1chip"
NEW = ("sparse_keys_read_share", "sparse_mla_step_roofline")


def test_configuration_file_carries_every_published_key_and_the_floors():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = next(r for r in rows if r["name"] == "DeepSeek-V3.2")
    assert DSV32["source"] == row["source_url"]
    differ = [k for k, v in row["config"].items()
              if DSV32.get(k, "absent") != v]
    assert sorted(differ) == sorted(DSV32["reduced"]) == sorted([
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size"])
    entry = next(c for c in BENCH["configs"] if c["name"] == DSV32["name"])
    assert entry["reduced"] == DSV32["reduced"]
    # the guide's floors: a dense layer counted once and four layers
    # after it, at least 8 experts, at least an eighth of the vocabulary
    pub = row["config"]
    assert DSV32["num_hidden_layers"] - DSV32["first_k_dense_replace"] >= 4
    assert DSV32["first_k_dense_replace"] == 1
    assert DSV32["n_routed_experts"] == 16 >= 8
    assert DSV32["vocab_size"] * 8 == pub["vocab_size"]
    # the deployment is stated, and the router keeps its published width
    dep = DSV32["deployment"]
    assert dep["n_routed_experts"] == pub["n_routed_experts"] == 256
    assert dep["chips_a_layer"] * DSV32["n_routed_experts"] == 256
    assert (dep["experts_first"], dep["vocabulary_slices"]) == (0, 8)
    for word in ("FP8", "Hadamard"):
        assert word in DSV32["assumed"]["indexer"]
    assert "not served" in DSV32["assumed"]["mtp"]
    # what is served is the registry's cut, to the number
    from ggrmcp_tpu.models import mla_moe

    cut = mla_moe.CONFIGS[DSV32["stack"]["serving"]["model"]]
    assert (cut.num_layers, cut.first_dense_layers, cut.experts_held,
            cut.vocab_size, cut.num_experts) == (5, 1, (0, 16), 16160, 256)
    batching = DSV32["stack"]["serving"]["batching"]
    assert (batching["max_batch_size"], batching["kv_cache_max_seq"],
            batching["prefill_chunk"], batching["paged_kv_page_size"]) == (
        8, 32768, 512, 16)
    assert set(DSV32["controls"]) == {"fp8_kv", "no_selection"}


@pytest.mark.parametrize("name, slots, vocab", [
    ("agent-longctx", 8, 16160), ("mixed-queue", 8, 32000)])
def test_new_traffic_is_identical_for_two_seeds(name, slots, vocab):
    a = schedule.load(name, slots, BENCH_DIR)
    b = schedule.load(name, slots, BENCH_DIR)
    assert a.describe() == b.describe()
    ca = calls_of(a, 7, vocab, sessions=1)
    cb = calls_of(b, 2**31 + 11, vocab, sessions=1)
    shape = lambda cs: [(c, s, t, len(p), o) for c, s, t, p, o in cs]  # noqa: E731
    assert shape(ca) == shape(cb)
    assert [p for *_, p, _ in ca] != [p for *_, p, _ in cb]
    assert probe_lengths(a, 16) == probe_lengths(b, 16)
    assert max(max(p) for *_, p, _ in ca) < vocab  # ids inside the slice


def test_agent_longctx_is_what_the_cell_says():
    sched = schedule.load("agent-longctx", 8, BENCH_DIR)
    assert (sched.clients, sched.session_turns, len(sched.pairs)) == (8, 64, 512)
    assert sched.shared_prefix_tokens == 0 and sched.think_time_s == 0
    assert (sched.ramp, sched.trace_ms, sched.loop) == ("call", 1000, "closed")
    docs = sorted(p for p, _ in sched.pairs[0::64])
    assert docs == [8448 + 512 * k for k in range(8)]  # even grid, 8,192-12,288
    follow = [p for i, (p, _) in enumerate(sched.pairs) if i % 64]
    assert len(follow) == 504 and (min(follow), max(follow)) == (32, 128)
    outs = [o for _, o in sched.pairs]
    assert (min(outs), max(outs), sum(outs) / 512) == (64, 256, 160.0)
    assert sorted(sched.offsets) == [64 * k for k in range(8)]  # a session each
    longest = sched.longest_prompt() + max(outs)
    assert 27000 < longest < 28000
    assert longest + 24 <= DSV32["stack"]["serving"]["batching"]["kv_cache_max_seq"]
    # every follow-up sees more than index_topk keys: the selection binds
    assert min(docs) > 4 * DSV32["index_topk"]
    # one cold shape (a 32-chunk grid), three suffix widths
    assert [1 << (n - 1).bit_length() for n in probe_lengths(sched, 16)] == [
        128, 256, 512, 16384]


def test_mixed_queue_is_decode_steady_with_every_eighth_prompt_long():
    base = schedule.load("decode-steady", 8, BENCH_DIR)
    mixed = schedule.load("mixed-queue", 8, BENCH_DIR)
    assert (mixed.clients, mixed.session_turns, mixed.ramp, mixed.trace_ms) == (
        16, 1, "call", 3000)
    assert mixed.shared_prefix_tokens == 0 and len(mixed.pairs) == 64
    for i, ((p0, o0), (p1, o1)) in enumerate(zip(base.pairs, mixed.pairs)):
        assert o1 == o0 and p1 == (1500 if i % 8 == 7 else p0)
    with open(os.path.join(
            BENCH_DIR, "configs", "mistral-7b-int8-1chip.json")) as f:
        mistral = json.load(f)
    need = mixed.longest_prompt() + max(o for _, o in mixed.pairs) + 24
    assert need <= mistral["stack"]["serving"]["batching"]["kv_cache_max_seq"]


def test_step_bytes_by_hand():
    # W_qa 7168 x 1536, W_qb 1536 x 24576, W_kva 7168 x 576,
    # W_kvb 512 x 32768, W_o 16384 x 7168
    attn = 7168 * 1536 + 1536 * 24576 + 7168 * 576 + 512 * 32768 + 16384 * 7168
    assert attn == roofline_dsv32.attention_params(DSV32) == 187_105_280
    idx = 1536 * 8192 + 7168 * 128 + 7168 * 64
    assert idx == roofline_dsv32.indexer_params(DSV32) == 13_959_168
    assert roofline_dsv32.expert_bytes(DSV32) == 3 * 7168 * 2048 * 2 == 88_080_384
    norms = 5 * (2 * 7168 + 512 + 1536 + 2 * 128) + 7168
    fixed = (5 * (attn + idx) + norms + 3 * 7168 * 18432 + 4 * 3 * 7168 * 2048
             + 7168 * 16160) * 2 + 4 * (7168 * 256 + 256) * 4
    assert roofline_dsv32.fixed_weight_bytes(DSV32) == fixed
    assert fixed == pytest.approx(3.417e9, rel=1e-3)
    assert roofline_dsv32.index_key_bytes_per_token(DSV32) == 5 * 128 * 2 == 1280
    assert roofline_dsv32.latent_bytes_per_token(DSV32) == 5 * 576 * 2 == 5760
    # 8 rows at 14,000 keys each, 14 held experts hit over the 4 layers
    step = roofline_dsv32.step_bytes(DSV32, 14, 8 * 14_000, 8 * 2048)
    assert step == fixed + 14 * 88_080_384 + 112_000 * 1280 + 16_384 * 5760
    assert roofline_dsv32.step_floor_ms(
        DSV32, "TPU v5 lite", 14, 112_000, 16_384) == pytest.approx(
        step / 819e9 * 1000.0)
    with pytest.raises(KeyError):
        roofline_dsv32.step_floor_ms(DSV32, "no such chip", 1, 1, 1)


class _Call:
    def __init__(self, prompt, n):
        self.prompt, self.completion_tokens, self.ok = [0] * prompt, n, True


def test_tokens_a_step_count_what_a_row_sees_and_what_it_selects():
    # 3 steps at 2,046 keys: sees 2,047 + 2,048 + 2,049, selects
    # 2,047 + 2,048 + 2,048; a 10-key row sees and selects 11 + 12
    calls = [_Call(2046, 3), _Call(10, 2)]
    visible, selected = roofline_dsv32.tokens_per_step(calls, 4, 2048)
    assert visible * 4 == 2047 + 2048 + 2049 + 11 + 12
    assert selected * 4 == 2047 + 2048 + 2048 + 11 + 12
    assert roofline_dsv32.tokens_per_step(calls, 0, 2048) == (0.0, 0.0)


# ServingStats as the harness holds it (protojson through `numbers`).
STATS0 = {"decodeSteps": 1000, "ticks": 1000, "moeExpertsHit": 14_000,
          "moeLayerSteps": 4000, "sparseKeysSelected": 80_000_000,
          "sparseKeysVisible": 500_000_000, "sparseLayerSteps": 40_000}
STATS1 = {"decodeSteps": 3000, "ticks": 3000, "moeExpertsHit": 42_000,
          "moeLayerSteps": 12_000, "sparseKeysSelected": 240_000_000,
          "sparseKeysVisible": 1_500_000_000, "sparseLayerSteps": 120_000}


def read(name, stats0, stats1, trace=None, config=DSV32, calls=()):
    roots = [BENCH_DIR]
    ctx = {"stats0": stats0, "stats1": stats1, "reader_roots": roots,
           "calls": list(calls), "window_s": 45.0, "config": config,
           "trace": trace, "device": {"kind": "TPU v5 lite", "count": 1}}
    return plugins.load("layer_metrics", name, roots).read(ctx)


def test_new_readers_on_recorded_stats():
    assert read("sparse_keys_read_share", STATS0, STATS1) == pytest.approx(16.0)
    # 2 s of the tick program over 100 launches of one step: 20 ms a step
    trace = {"program_s": 2.0, "program_runs": 100}
    calls = [_Call(14_000, 160)] * 100
    visible, selected = roofline_dsv32.tokens_per_step(calls, 2000, 2048)
    floor = roofline_dsv32.step_floor_ms(
        DSV32, "TPU v5 lite", 28_000 / 2000, visible, selected)
    got = read("sparse_mla_step_roofline", STATS0, STATS1, trace, calls=calls)
    assert got == pytest.approx(100.0 * floor / 20.0) and 0 < got < 100
    assert read("sparse_mla_step_roofline", STATS0, STATS1, None) is None


@pytest.mark.parametrize("name", NEW)
def test_new_readers_find_nothing_on_the_parent_or_in_an_idle_window(name):
    trace = {"program_s": 2.0, "program_runs": 100}
    assert read(name, STATS0, STATS0, trace) is None  # nothing happened
    # a program without the fields (the parent commit's ServingStats)
    old = {"decodeSteps": 1000, "ticks": 1000}
    new = dict(old, decodeSteps=3000, ticks=3000)
    assert read(name, old, new, trace) is None
    # another member's configuration has no index_topk
    with open(os.path.join(
            BENCH_DIR, "configs", "kanana-2-30b-a3b-bf16-1chip.json")) as f:
        kanana = json.load(f)
    if name == "sparse_mla_step_roofline":
        assert read(name, STATS0, STATS1, trace, config=kanana) is None


def _call(client, session, turn, prompt, output, done, ok=True, phase="run"):
    call = type("Call", (), {})()
    call.client, call.session, call.turn, call.phase = client, session, turn, phase
    call.prompt, call.output, call.done, call.ok = prompt, output, done, ok
    call.segments = [[len(prompt), len(prompt) + len(output)]]
    return call


def test_the_sampler_takes_session_prefixes_not_whole_sessions():
    """Client 0: the document turn completed before the window, turns 1
    and 2 inside it, turn 3 after it: the prefix is turns 0-2. Client 1
    completed nothing inside the window; client 2's history is not one
    growing history; a probe is never sampled."""
    sample = plugins.load("checks", "logit_margin_dsv32", [BENCH_DIR]).sample
    h0 = [5, 6, 7]
    t0 = _call(0, 0, 0, h0, [8], 0.5, phase="ramp")
    t1 = _call(0, 0, 1, h0 + [8, 9], [10, 11], 1.5)
    t2 = _call(0, 0, 2, t1.prompt + t1.output + [12], [13], 2.5)
    t3 = _call(0, 0, 3, t2.prompt + t2.output + [14], [15], 3.5)
    other = _call(1, 0, 0, [1, 2], [3], 0.2)
    broken = [_call(2, 0, 0, [4, 4], [4], 1.2), _call(2, 0, 1, [9, 9, 9, 9], [4], 1.4)]
    probe = _call(0, -1, 0, [1], [2], 1.5, phase="probe")
    seqs = sample([t3, t1, probe, t0, other, t2] + broken, 1.0, 3.0, 10_000)
    assert len(seqs) == 1 and seqs[0]["turns"] == 3
    assert seqs[0]["ids"] == t2.prompt + t2.output
    # every turn of the prefix is compared, the ramp's document turn too
    assert seqs[0]["compare"] == [[3, 4], [5, 7], [8, 9]]
    # a failed turn inside the prefix drops the session
    t1.ok = False
    assert sample([t0, t1, t2], 1.0, 3.0, 10_000) == []
    t1.ok = True
    # max_tokens bounds the sample but always leaves one sequence
    again = [t0, t1, t2, _call(3, 0, 0, [7] * 9, [8], 1.1)]
    assert len(sample(again, 1.0, 3.0, 10_000)) == 2
    assert len(sample(again, 1.0, 3.0, 12)) == 1


def test_the_sampler_starts_at_a_client_the_runs_token_ids_name():
    """Four clients of one completed turn each and room for two: the
    first client's first prompt sums to 4k + r, so the walk starts at
    client r and wraps; over the seeds every slot is sampled."""
    sample = plugins.load("checks", "logit_margin_dsv32", [BENCH_DIR]).sample
    seen = set()
    for r in range(4):
        calls = [_call(c, 0, 0, [8 + r, 12] if c == 0 else [3, 3], [c], 1.5)
                 for c in range(4)]
        seqs = sample(calls, 1.0, 3.0, 6)
        got = [s["ids"][2] for s in seqs]
        assert got == [r, (r + 1) % 4]
        assert sample(list(reversed(calls)), 1.0, 3.0, 6) == seqs
        seen.update(got)
    assert seen == {0, 1, 2, 3}


def test_the_check_holds_every_limit_and_passes_no_selection_on(
        tmp_path, monkeypatch):
    check = plugins.load("checks", "logit_margin_dsv32", [BENCH_DIR])
    result = {"tokens": 8, "flip_share": 0.2, "mean_sq_margin_sigma": 0.1,
              "max_margin_sigma": 1.0, "mean_margin_sigma": 0.05, "finite": True,
              "selection": True,
              "per_sequence": [{"tokens": 8, "flip_share": 0.2,
                                "mean_sq_margin_sigma": 0.1}],
              "seconds": 1.0, "platform": "cpu", "kind": "cpu"}
    monkeypatch.setattr(check.subprocess, "run", lambda *a, **k: (
        subprocess.CompletedProcess(a, 0, json.dumps(result), "")))
    ctx = {"all_calls": [_call(0, 0, 0, [1, 2], [3], 1.0)], "t0": 0.0, "t1": 2.0,
           "out_dir": str(tmp_path), "config_path": "x", "cpu": True,
           "root": ROOT, "harness_dir": BENCH_DIR, "check_timeout_s": 5.0}
    verdicts = {}
    for flips in (0.3, 0.15):
        ctx["config"] = {"check": {"no_selection": flips == 0.15, "limits": {
            "mean_sq_margin_sigma": 0.2, "flip_share": flips}}}
        out = check.run(ctx)
        verdicts[flips] = out["correct"]
        assert ("flip_share = 0.2 (limit 0.15: OVER)" in out["lines"][1]) == (
            flips == 0.15)
        with open(os.path.join(str(tmp_path), "reference_job.json")) as f:
            assert json.load(f)["no_selection"] is (flips == 0.15)
    assert verdicts == {0.3: True, 0.15: False}


def test_the_check_limits_lie_between_their_two_readings():
    check = DSV32["check"]
    assert check["name"] == "logit_margin_dsv32"
    # (sound runs' largest, fp8_kv control's reading) on the chip, the
    # first readings the limits were set from: my chip runs, PR 33
    # (PERF.md section 6)
    read_ = {"mean_sq_margin_sigma": (0.0416, 0.1866),
             "flip_share": (0.393, 0.609)}
    assert set(check["limits"]) == set(read_)
    for name, (sound, control) in read_.items():
        assert sound < check["limits"][name] < control


def test_the_lists_pr_28_pinned_are_a_prefix_of_todays():
    """`test_bench_mla_moe.py::test_new_metrics_and_cells_are_named_
    where_the_issue_says` pins every list to exactly PR 28's cells, so
    it FAILS from the first cell a later PR appends, and a PR that only
    adds may not reword it (PERF.md, section 7: the next `benchmark`
    PR's). Everything it says, for a benchmark that grows: what PR 28
    listed is still there, first and in order, the kanana cell's three
    readers and its absence from `decode_step_roofline` included, and
    this PR's one cell follows, where ISSUE 33 says. `mixed-queue` is
    held back (its median spreads past its bound until the client can
    ask for no early stop): its traffic file is there, no cell runs it."""
    per_layer = {m["name"]: m for m in BENCH["per_layer"]}
    assert "mla_moe_step_roofline" not in per_layer
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells == [
        "decode-steady.int8-1chip", "agent-shared.int8-1chip", KANANA_CELL,
        "long-prefill.int8-1chip", CELL]
    assert all(w["chips"] == 1 for w in BENCH["workloads"])
    assert MIXED not in json.dumps(BENCH)
    was = {n: [c for c in m["workloads"] if c != CELL]
           for n, m in per_layer.items() if n not in NEW}
    for name in ("moe_experts_hit_share", "moe_load_max_over_mean"):
        assert per_layer[name]["workloads"] == [KANANA_CELL, CELL]
        del was[name]
    assert per_layer["cold_prefill_tok_s"]["workloads"] == [
        KANANA_CELL, "long-prefill.int8-1chip"]
    del was["cold_prefill_tok_s"]
    roof = was.pop("decode_step_roofline")
    assert KANANA_CELL not in roof
    assert per_layer["decode_step_roofline"]["workloads"] == roof
    assert was.pop("step_ms_mean")[-2:] == [
        "agent-shared.int8-1chip", "long-prefill.int8-1chip"]
    assert per_layer["step_ms_mean"]["workloads"][-1] == CELL
    assert "long-prefill.int8-1chip" not in was.pop("prefix_reuse_share")
    assert per_layer["prefix_reuse_share"]["workloads"][-1] == CELL
    for name, old in was.items():
        assert old[-2:] == [KANANA_CELL, "long-prefill.int8-1chip"], name
        # appended, nothing moved: the old list, then this PR's cell
        assert per_layer[name]["workloads"] == old + [CELL], name
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL]
    out_tok_s = next(m for m in BENCH["end_to_end"] if m["name"] == "out_tok_s")
    assert out_tok_s["workloads"] == ["agent-shared.int8-1chip"]


def test_the_cells_capture_fits_a_traced_runs_time_limit():
    """The driver stops a run at 360 s and the profiler writes 36-40 s
    for a second captured in the latent family's cells (PERF.md section
    6, PR 28): one second, never the default 3."""
    assert schedule.load("agent-longctx", 8, BENCH_DIR).trace_ms == 1000


def test_new_cell_rehearsed_on_the_cpu_prints_its_readers():
    line, out = run_cell(REHEARSAL, "tiny-agent-longctx.cpu", 1)
    assert out[0].startswith("CPU REHEARSAL") and line["rehearsal"] is True
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 10 and line["device"]["platform"] == "cpu"
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    # a CPU run never reports a device metric: no roofline here
    assert set(metrics) == {
        "queue_ms_mean", "prefix_reuse_share", "kv_pages_used_share",
        "out_tok_s.decode", "compiles_in_window", "moe_experts_hit_share",
        "moe_load_max_over_mean", "sparse_keys_read_share"}
    assert metrics["compiles_in_window"] == 0
    # 8 held of 16 experts, 4 of them a token at most
    assert 10 < metrics["moe_experts_hit_share"] <= 100
    # 16 of 150-330 visible keys
    assert 4 < metrics["sparse_keys_read_share"] < 12
    assert metrics["prefix_reuse_share"] > 20  # follow-ups reuse the document
    assert any("check logit_margin_dsv32" in ln and "session prefixes" in ln
               for ln in out)
    assert any("check logit_margin_dsv32" in ln and "within" in ln for ln in out)


@pytest.mark.parametrize("control, says", [
    # float8 planes: at this size (float32 served against a float32
    # reference) sound runs read mean_margin_sigma 0.0
    ("fp8_kv", "with its selection"),
    # the served tokens against a reference that attends every key:
    # the check sees the mechanism
    ("no_selection", "WITHOUT its selection"),
])
def test_new_cells_controls_come_out_as_not_correct(control, says):
    line, out = run_cell(
        REHEARSAL, "tiny-agent-longctx.cpu", 0, "--control", control)
    assert line["correct"] is False, out[-4:]
    assert any("OVER" in ln for ln in out) and any(says in ln for ln in out)
    assert set(line["metrics"]) == {"call_ms_p50", "setup_s"}
