"""The trace reduction: on planes built by hand, and on a small trace
recorded on a TPU v5e (tests/benchmark/data/decode_tick.xplane.pb, cut
by benchmark/tools/cut_trace.py from a traced run of
decode-steady.int8-1chip)."""

import os

import pytest

from benchmark import trace, xplane
from benchmark.xplane import Event, Line, Plane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 10**9  # picoseconds


def planes():
    ops = [Event("fusion.1", 0 * MS, 4 * MS), Event("copy.2", 4 * MS, 2 * MS),
           Event("fusion.1", 10 * MS, 4 * MS), Event("all-reduce.3", 14 * MS, 1 * MS),
           Event("copy.2", 18 * MS, 2 * MS)]
    modules = [Event("jit__tick_impl(123)", 0, 6 * MS),
               Event("jit__admit_single_impl(9)", 10 * MS, 5 * MS),
               Event("jit__tick_impl(123)", 18 * MS, 2 * MS)]
    ops.append(Event("while.9", 0, 6 * MS))  # holds fusion.1 and copy.2
    host = [Event("dispatch", 5 * MS, 6 * MS), Event("short", 6 * MS, 1 * MS),
            Event("overlapping", 14 * MS, 3 * MS)]
    return [
        Plane("/device:TPU:0", [Line("XLA Modules", modules), Line("XLA Ops", ops)]),
        Plane("/host:CPU", [Line("python3", host)]),
    ]


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


def test_reduce_by_hand():
    r = trace.reduce(planes())
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.020)
    assert r["busy_s"] == pytest.approx(0.013)  # 6 + 5 + 2 ms
    assert r["program_s"] == pytest.approx(0.008) and r["program_runs"] == 2
    assert r["collective_s"] == pytest.approx(0.001)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(0.008)]
    assert r["device_ops"][1] == ["copy.2", pytest.approx(0.004)]
    assert ["while.9", 0.0] in r["device_ops"]  # a container has no self time
    # gaps: 6-10 ms (covered whole by `dispatch`), 15-18 ms (overlapped)
    assert r["idle_gaps"] == [["dispatch", pytest.approx(0.004)],
                              ["overlapping", pytest.approx(0.003)]]


def test_the_runs_the_capture_cut_short_are_left_out():
    p = planes()
    p[0].lines[0].events[:] = [Event("jit__tick_impl(1)", t * MS, d * MS)
                               for t, d in ((0, 1), (2, 4), (7, 4), (12, 2))]
    r = trace.reduce(p)
    assert r["program_runs"] == 2 and r["program_s"] == pytest.approx(0.008)


def test_a_gap_nobody_covers_is_unattributed():
    p = planes()
    p[1].lines[0].events.clear()
    p[1].lines[0].events.append(Event("elsewhere", 0, 1 * MS))
    r = trace.reduce(p)
    assert r["idle_gaps"][0][0] == "unattributed_0.006000s"


def test_no_device_plane_reads_nothing():
    assert trace.reduce(planes()[1:]) is None


def test_the_wire_format_round_trips():
    again = xplane.parse(xplane.dump(planes()))
    assert again == planes()


def test_the_reader_agrees_with_jaxs_own():
    """Same events, names and times as jax.profiler.ProfileData."""
    ProfileData = pytest.importorskip("jax.profiler").ProfileData
    raw = xplane.dump(planes())
    theirs = ProfileData.from_serialized_xspace(raw)
    for mine, ref in zip(xplane.parse(raw), theirs.planes):
        assert mine.name == ref.name
        for ml, rl in zip(mine.lines, ref.lines):
            assert ml.name == rl.name
            for a, b in zip(ml.events, rl.events):
                assert a.name == b.name
                assert a.start_ps / 1000 == pytest.approx(b.start_ns)
                assert a.duration_ps / 1000 == pytest.approx(b.duration_ns)


def test_short_name_is_safe_for_one_line():
    name = trace.short_name("%copy.137 = bf16[32,1024]{4,3:T(8,128)} copy(x)")
    assert " " not in name and "," not in name and len(name) <= 64


def test_reduce_a_trace_recorded_on_the_chip():
    """0.28 s of decode-steady.int8-1chip on a TPU v5 lite: one whole
    8-step decode tick of mistral-7b int8 at 8 rows and what surrounds
    it (my chip run, PR 25)."""
    r = trace.reduce(xplane.load(os.path.join(DATA, "decode_tick.xplane.pb")))
    assert r["devices"] == 1 and r["collective_s"] == 0.0
    assert r["program_runs"] == 1
    assert r["program_s"] == pytest.approx(0.21813, abs=1e-4)  # 8 steps
    assert r["program_s"] / 8 * 1000 == pytest.approx(27.27, abs=0.01)
    assert 0.0 < r["busy_s"] <= r["window_s"] < 0.28
    assert 1 - r["busy_s"] / r["window_s"] < 0.02  # the device is busy
    # the whole-arena K and V copies lead, ahead of the FFN matmuls
    assert sorted(name.split("_bf16")[0] for name, _ in r["device_ops"][:2]) == [
        "_copy.136", "_copy.137"]
    assert "1024_16_8_128" in r["device_ops"][0][0]
    assert all(s > 0 for _, s in r["device_ops"]) and len(r["device_ops"]) == 10
    assert len(r["idle_gaps"]) == 5
