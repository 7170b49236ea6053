"""What PR 37 added to the benchmark, on the CPU: the configuration
file against the catalog row and the issue's count; `agent-deepctx`
holds the work whatever the seed and is what the issue says; the new
cell's bytes against a hand count; the new reader on recorded
ServingStats, on an idle window and on a program or configuration
without what it reads (the parent commit); the check's limits between
their readings; the lists in PREFIX form only (what earlier PRs listed
is still there, first and in order, and this cell follows: no `==` on a
tail or on the list of cells, so the next cell breaks nothing here);
and the new cell rehearsed at the family's tiny preset from
`rehearsal_keye/`, sound and with its two controls."""

import json
import os

import pytest

from benchmark import plugins, roofline_keye, schedule
from benchmark.run import probe_lengths
from tests.benchmark.test_bench_rehearsal import ROOT, run_cell
from tests.benchmark.test_bench_schedule import calls_of

BENCH_DIR = os.path.join(ROOT, "benchmark")
REHEARSAL = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "rehearsal_keye")
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
with open(os.path.join(
        BENCH_DIR, "configs", "keye-vl-2.0-30b-a3b-bf16-1chip.json")) as f:
    KEYE = json.load(f)
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELL = "agent-deepctx.keye-bf16-1chip"
DSV32_CELL = "agent-longctx.dsv32-bf16-1chip"
NEW = "sparse_gqa_step_roofline"


def test_configuration_file_carries_every_published_key_and_the_count():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = next(r for r in rows if r["name"] == "Keye-VL-2.0-30B-A3B")
    assert KEYE["source"] == row["source_url"]
    differ = [k for k, v in row["config"].items()
              if KEYE.get(k, "absent") != v]
    assert differ == KEYE["reduced"] == ["num_hidden_layers"]
    # the nested blocks whole
    assert KEYE["sa_config"] == row["config"]["sa_config"]
    assert KEYE["rope_scaling"] == row["config"]["rope_scaling"]
    entry = next(c for c in BENCH["configs"] if c["name"] == KEYE["name"])
    assert entry["reduced"] == KEYE["reduced"]
    assert entry["file"].endswith(KEYE["name"] + ".json")
    assert (KEYE["num_hidden_layers"], row["config"]["num_hidden_layers"]) == (6, 48)
    # the readers' alias is the published count under another name
    assert (KEYE["n_routed_experts"] == KEYE["num_experts"]
            == KEYE["num_local_experts"] == 128)
    assert "NOT a published key" in KEYE["n_routed_experts_is"]
    assert "n_routed_experts" not in row["config"]
    dep = KEYE["deployment"]
    assert dep["pipeline_stages"] * KEYE["num_hidden_layers"] == 48
    assert "each layer whole on one chip" in dep["stated"]
    # the four assumptions and the weights recipe, one key each
    assumed = KEYE["assumed"]
    assert assumed["qk_norm"]["value"] is True
    assert assumed["indexer_rope_dim"]["value"] == 32
    for key, word in (("indexer_form", "Hadamard"), ("indexer_form", "FP8"),
                      ("indexer_tiles", "q_chunk_size"),
                      ("vision_tower", "not served")):
        assert word in json.dumps(assumed[key])
    assert "truncated_normal" in assumed["weights"]
    # what is served is the registry's cut, to the number
    from ggrmcp_tpu.models import keye

    cut = keye.CONFIGS[KEYE["stack"]["serving"]["model"]]
    assert KEYE["registry_model"] == cut.name
    sa = KEYE["sa_config"]
    assert (cut.num_layers, cut.hidden_dim, cut.num_heads, cut.num_kv_heads,
            cut.head_dim, cut.vocab_size, cut.num_experts,
            cut.experts_per_token, cut.expert_ffn_dim, cut.index_heads,
            cut.index_head_dim, cut.index_topk, cut.index_rope_dim,
            cut.qk_norm, cut.rope_theta, cut.norm_eps) == (
        6, KEYE["hidden_size"], KEYE["num_attention_heads"],
        KEYE["num_key_value_heads"], KEYE["head_dim"], KEYE["vocab_size"],
        KEYE["num_experts"], KEYE["num_experts_per_tok"],
        KEYE["moe_intermediate_size"], sa["indexer_num_heads"],
        sa["indexer_head_dim"], sa["topk"], 32, True, KEYE["rope_theta"],
        KEYE["rms_norm_eps"])
    assert abs(keye.num_params(cut) * 2 / 1e9 - 8.75) < 0.01
    batching = KEYE["stack"]["serving"]["batching"]
    assert (batching["max_batch_size"], batching["kv_cache_max_seq"],
            batching["prefill_chunk"], batching["paged_kv_page_size"],
            batching["max_pending"]) == (8, 32768, 512, 16, 0)
    assert (KEYE["stack"]["server"]["request_timeout_s"],
            KEYE["stack"]["grpc"]["call_timeout_s"]) == (600, 600)
    assert set(KEYE["controls"]) == {"fp8_kv", "no_selection"}
    assert KEYE["check"]["name"] == "logit_margin_keye"
    assert KEYE["check"]["limit_read_from"].startswith("my chip runs, PR 37")


def test_the_reference_and_the_program_share_one_recipe():
    """The reference's own copy of the list of drawn leaves names the
    program's leaves, shapes, scales and dtypes, in draw order."""
    from benchmark import reference_keye
    from ggrmcp_tpu.models import keye

    cut = keye.CONFIGS[KEYE["registry_model"]]
    mine = [(".".join(path), shape, scale, dtype)
            for path, shape, scale, dtype in keye.leaf_recipe(cut)]
    assert mine == [(n, s, sc, dt) for n, s, sc, dt in
                    reference_keye.leaf_recipe(KEYE)]


def test_agent_deepctx_is_identical_for_two_seeds():
    a = schedule.load("agent-deepctx", 8, BENCH_DIR)
    b = schedule.load("agent-deepctx", 8, BENCH_DIR)
    assert a.describe() == b.describe()
    ca = calls_of(a, 7, KEYE["vocab_size"], sessions=1)
    cb = calls_of(b, 2**31 + 11, KEYE["vocab_size"], sessions=1)
    shape = lambda cs: [(c, s, t, len(p), o) for c, s, t, p, o in cs]  # noqa: E731
    assert shape(ca) == shape(cb)
    assert [p for *_, p, _ in ca] != [p for *_, p, _ in cb]
    assert probe_lengths(a, 16) == probe_lengths(b, 16)
    assert max(max(p) for *_, p, _ in ca) < KEYE["vocab_size"]


def test_agent_deepctx_is_what_the_issue_says():
    sched = schedule.load("agent-deepctx", 8, BENCH_DIR)
    assert (sched.clients, sched.session_turns, len(sched.pairs)) == (8, 48, 384)
    assert sched.shared_prefix_tokens == 0 and sched.think_time_s == 0
    assert (sched.ramp, sched.trace_ms, sched.loop) == ("call", 1000, "closed")
    docs = sorted(p for p, _ in sched.pairs[0::48])
    assert docs == [13312 + 1024 * k for k in range(8)]  # even 8-point grid
    follow = [p for i, (p, _) in enumerate(sched.pairs) if i % 48]
    assert len(follow) == 376 and (min(follow), max(follow)) == (32, 128)
    assert abs(sum(follow) / 376 - 80) < 0.5  # an even grid
    outs = [o for _, o in sched.pairs]
    assert (min(outs), max(outs), sum(outs) / 384) == (64, 192, 128.0)
    assert sorted(sched.offsets) == [48 * k for k in range(8)]  # a session each
    longest = sched.longest_prompt() + max(outs)
    assert 30000 < longest < 31000
    assert longest + 24 <= KEYE["stack"]["serving"]["batching"]["kv_cache_max_seq"]
    for start in range(0, 384, 48):  # each session, outputs included
        assert sum(p + o for p, o in sched.pairs[start:start + 48]) < 32768 - 24
    # every follow-up sees 6.5 x the indexer's topk keys and more
    assert min(docs) >= 6.5 * KEYE["sa_config"]["topk"]
    # two cold shapes (a 32-chunk and a 64-chunk grid), three suffix widths
    assert [1 << (n - 1).bit_length() for n in probe_lengths(sched, 16)] == [
        128, 256, 512, 16384, 32768]


def test_step_bytes_by_hand():
    # W_q 2048 x 4096, W_k and W_v 2048 x 512, W_o 4096 x 2048
    attn = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048
    assert attn == roofline_keye.attention_params(KEYE) == 18_874_368
    idx = 2048 * 1024 + 2048 * 64 + 2048 * 16
    assert idx == roofline_keye.indexer_params(KEYE) == 2_260_992
    assert roofline_keye.expert_bytes(KEYE) == 3 * 2048 * 768 * 2 == 9_437_184
    norms = 6 * (2 * 2048 + 2 * 128 + 2 * 64) + 2048
    fixed = (6 * (attn + idx) + norms + 2048 * 151936) * 2 + 6 * 2048 * 128 * 4
    assert roofline_keye.fixed_weight_bytes(KEYE) == fixed
    assert fixed == pytest.approx(0.882e9, rel=2e-3)  # ISSUE 37: 0.88 GB
    assert roofline_keye.index_key_bytes_per_token(KEYE) == 6 * 64 * 2 == 768
    assert roofline_keye.kv_bytes_per_token(KEYE) == 6 * 2 * 4 * 128 * 2 == 12288
    # 8 rows at 18,000 keys each, 240 experts hit over the 6 layers
    step = roofline_keye.step_bytes(KEYE, 240, 8 * 18_000, 8 * 2048)
    assert step == fixed + 240 * 9_437_184 + 144_000 * 768 + 16_384 * 12288
    assert roofline_keye.step_floor_ms(
        KEYE, "TPU v5 lite", 240, 144_000, 16_384) == pytest.approx(
        step / 819e9 * 1000.0)
    with pytest.raises(KeyError):
        roofline_keye.step_floor_ms(KEYE, "no such chip", 1, 1, 1)


class _Call:
    def __init__(self, prompt, n):
        self.prompt, self.completion_tokens, self.ok = [0] * prompt, n, True


STATS0 = {"decodeSteps": 1000, "ticks": 1000, "moeExpertsHit": 240_000,
          "moeLayerSteps": 6000}
STATS1 = {"decodeSteps": 3000, "ticks": 3000, "moeExpertsHit": 720_000,
          "moeLayerSteps": 18_000}


def read(name, stats0, stats1, trace=None, config=KEYE, calls=()):
    roots = [BENCH_DIR]
    ctx = {"stats0": stats0, "stats1": stats1, "reader_roots": roots,
           "calls": list(calls), "window_s": 45.0, "config": config,
           "trace": trace, "device": {"kind": "TPU v5 lite", "count": 1}}
    return plugins.load("layer_metrics", name, roots).read(ctx)


def test_the_new_reader_on_recorded_stats():
    # 2 s of the tick program over 100 launches of one step: 20 ms a step
    trace = {"program_s": 2.0, "program_runs": 100}
    calls = [_Call(18_000, 128)] * 100
    visible, selected = roofline_keye.tokens_per_step(calls, 2000, 2048)
    floor = roofline_keye.step_floor_ms(
        KEYE, "TPU v5 lite", 480_000 / 2000, visible, selected)
    got = read(NEW, STATS0, STATS1, trace, calls=calls)
    assert got == pytest.approx(100.0 * floor / 20.0) and 0 < got < 100
    # the expert-layer readers find the count under the alias
    assert read("moe_experts_hit_share", STATS0, STATS1) == pytest.approx(
        100.0 * 480_000 / 12_000 / 128)


def test_the_new_reader_finds_nothing_where_there_is_nothing_to_read():
    trace = {"program_s": 2.0, "program_runs": 100}
    assert read(NEW, STATS0, STATS1, None) is None  # no tick in the capture
    assert read(NEW, STATS0, STATS0, trace) is None  # nothing happened
    # a program without the fields (the parent commit's ServingStats
    # has them; an older one's, or a dense family's, has not)
    old = {"decodeSteps": 1000, "ticks": 1000}
    assert read(NEW, old, dict(old, decodeSteps=3000, ticks=3000), trace) is None
    # another configuration has no sa_config
    for other in ("kanana-2-30b-a3b-bf16-1chip", "deepseek-v3.2-bf16-ep16-1chip"):
        with open(os.path.join(BENCH_DIR, "configs", other + ".json")) as f:
            assert read(NEW, STATS0, STATS1, trace, config=json.load(f)) is None


def test_the_check_limits_lie_between_their_two_readings():
    check = KEYE["check"]
    # (sound runs' largest, the control's reading that the limit is
    # for) on the chip, the first readings the limits were set from:
    # my chip runs, PR 37 (PERF.md section 6). `sq_margin_vs_lower`,
    # the paired statistic, is the float8 control's limit;
    # `mean_sq_margin_sigma` is `no_selection`'s (it cannot tell
    # float8 from bf16 on every seed: the file says how far it reads).
    # flip_share is printed and no limit.
    read_ = check["first_readings"]
    assert set(check["limits"]) == set(read_) == {
        "mean_sq_margin_sigma", "sq_margin_vs_lower"}
    for name, (sound, control) in read_.items():
        assert sound < check["limits"][name] < control, name
    assert check["lower_planes"] == "float8_e4m3fn"
    # the control the paired limit is for stores the planes in that dtype
    assert KEYE["controls"]["fp8_kv"]["stack"]["serving"] == {
        "kv_cache_dtype": "fp8"}


@pytest.mark.parametrize("above, below, ratio", [
    (0.0217, 0.0418, 0.0217 / 0.0418),  # an ordinary sound prefix: as read
    (0.0572, 0.0419, 0.0572 / 0.0419),  # the same under float8
    (1e-4, 1e-4, 0.05),  # 4 flipped tokens of 1,700: over the floor
    (1.2, 0.0, 600.0),  # float32 arithmetic served from float8 planes
    (0.0, 0.0, 0.0),
    (None, None, None),  # nothing compared
])
def test_the_paired_ratio_is_counted_over_a_floor(above, below, ratio):
    from benchmark import reference_keye

    got = reference_keye.sq_ratio(
        {"mean_sq_margin_sigma": above}, {"mean_sq_margin_sigma": below})
    assert got == (None if ratio is None else pytest.approx(ratio))


def test_earlier_lists_are_prefixes_of_todays_and_this_cell_follows():
    """Prefix form only: every cell and every list as the parent commit
    had them is still there, first and in order; this PR's cell comes
    after them where ISSUE 37 says, and whatever a later PR appends
    after it breaks nothing here."""
    with open(os.path.join(DATA, "benchmark_at_pr34.json")) as f:
        parent = json.load(f)  # BENCHMARK.json as commit 98cb803 had it
    cells = [w["name"] for w in BENCH["workloads"]]
    old_cells = [w["name"] for w in parent["workloads"]]
    assert cells[: len(old_cells)] == old_cells and CELL in cells[len(old_cells):]
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
        # on every list the dsv32 cell is on but its own roofline
        listed = CELL in new["workloads"][len(old["workloads"]):]
        assert listed == (DSV32_CELL in old["workloads"]
                          and old["name"] != "sparse_mla_step_roofline"), old["name"]
    assert per_layer[NEW]["workloads"][0] == CELL
    assert {k: v for k, v in per_layer[NEW].items() if k != "workloads"} == {
        "name": NEW, "unit": "%", "better": "higher", "source": "device_trace",
        "layer": "kernels", "moves": "call_ms_p50"}
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        KEYE["name"], "agent-deepctx", 1)
    assert "attention sees over its share" in entry["why"]


def test_the_cells_capture_fits_a_traced_runs_time_limit():
    assert schedule.load("agent-deepctx", 8, BENCH_DIR).trace_ms == 1000


def test_new_cell_rehearsed_on_the_cpu_prints_its_readers():
    line, out = run_cell(REHEARSAL, "tiny-agent-deepctx.cpu", 1)
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
    # 16 experts, 4 of them a token, one or two rows a step
    assert 20 < metrics["moe_experts_hit_share"] <= 100
    # 16 of 150-330 visible keys
    assert 4 < metrics["sparse_keys_read_share"] < 12
    assert metrics["prefix_reuse_share"] > 20  # follow-ups reuse the document
    assert any("check logit_margin_keye" in ln and "session prefixes" in ln
               for ln in out)
    assert any("check logit_margin_keye" in ln and "within" in ln for ln in out)


@pytest.mark.parametrize("control, says, over", [
    # float8 planes, all three: at this size (float32 served against a
    # float32 reference) sound runs read 0.0 on both statistics, and
    # the float8 run's tokens are the lower reference's to the token
    # (its mean square margin there is 0: the ratio is counted over
    # the floor, `reference_keye.LOWER_FLOOR`)
    ("fp8_kv", "with its selection", "sq_margin_vs_lower"),
    # the served tokens against references that attend every key: the
    # check sees the mechanism, by the margin's own limit
    ("no_selection", "WITHOUT its selection", "mean_margin_sigma"),
])
def test_new_cells_controls_come_out_as_not_correct(control, says, over):
    line, out = run_cell(
        REHEARSAL, "tiny-agent-deepctx.cpu", 0, "--control", control)
    assert line["correct"] is False, out[-4:]
    # "<statistic> = <value> (limit <limit>: OVER)"
    assert any(f"{over} = " in ln
               and ln.split(f"{over} = ")[1].split(")")[0].endswith("OVER")
               for ln in out), out[-4:]
    assert any(says in ln for ln in out)
    assert any("sq_margin_vs_lower (float8_e4m3fn planes) a prefix" in ln
               for ln in out)
    assert set(line["metrics"]) == {"call_ms_p50", "setup_s"}
