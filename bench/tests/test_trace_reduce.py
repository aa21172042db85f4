"""The trace reduction on hand-made events and on a recorded chip trace."""

from pathlib import Path

import pytest

from harness import trace_reduce as T

E = T.Event
RECORDED = (Path(__file__).resolve().parents[1] / "testdata"
            / "characters_tiny.xplane.pb")


def test_union_merges_overlapping_and_touching_ops():
    evs = [E("a", 0, 10), E("b", 5, 12), E("c", 12, 15), E("d", 20, 30)]
    assert T.union(evs) == [(0, 15), (20, 30)]


def test_gaps_include_both_edges_of_the_window():
    busy = [(10, 20), (30, 40)]
    assert T.gaps(busy, 0, 50) == [(0, 10), (20, 30), (40, 50)]
    assert T.gaps([], 0, 5) == [(0, 5)]


def test_ops_crossing_the_window_are_clipped():
    dev = {"/device:TPU:0": [E("x", -5, 5), E("y", 8, 12), E("z", 95, 130)]}
    red = T.reduce(dev, 0, 100)
    assert red["busy_s"] == pytest.approx((5 + 4 + 5) / 1e9)
    assert red["window_s"] == pytest.approx(100 / 1e9)
    assert red["idle_share"] == pytest.approx(1 - 14 / 100)


def test_four_device_planes_average():
    dev = {f"/device:TPU:{i}": [E("op", 0, 10 * (i + 1))] for i in range(4)}
    red = T.reduce(dev, 0, 100)
    assert red["devices"] == 4
    assert red["busy_s"] == pytest.approx((10 + 20 + 30 + 40) / 4 / 1e9)
    assert red["device_ops"] == [["op", pytest.approx(25 / 1e9)]]


def test_a_program_on_one_of_four_chips_keeps_its_own_time():
    dev = {f"/device:TPU:{i}": [E("op", 0, 10)] for i in range(4)}
    mods = {f"/device:TPU:{i}": [E("jit_sweep(1)", 0, 40)] for i in range(4)}
    mods["/device:TPU:0"] += [E("jit_csim_kernel(2)", 50, 60),
                              E("jit_csim_kernel(2)", 70, 75)]
    red = T.reduce(dev, 0, 100, (), mods,
                   {"csim": r"^jit_csim_kernel\(", "sweep": r"^jit_sweep\("})
    assert red["program_s"] == {"csim": pytest.approx(15 / 1e9),
                                "sweep": pytest.approx(40 / 1e9)}
    assert red["program_runs"] == {"csim": 2, "sweep": 1}


def test_programs_and_idle_gaps_are_attributed():
    dev = {"/device:TPU:0": [E("%l0_rows.3 = custom-call", 0, 10),
                             E("fusion", 10, 20), E("copy", 60, 70)]}
    mods = {"/device:TPU:0": [E("jit_csim_kernel(1)", 0, 20),
                              E("jit_other(2)", 60, 70),
                              E("jit_csim_kernel(1)", 90, 120)]}
    spans = [T.Span("sweep", 0, 100, 0), T.Span("compile", 25, 55, 2),
             T.Span("store", 80, 100, 1)]
    red = T.reduce(dev, 0, 100, spans, mods, {"csim": r"^jit_csim_kernel\("})
    assert red["program_s"]["csim"] == pytest.approx(30 / 1e9)
    assert red["program_runs"]["csim"] == 2
    idle = dict(red["idle_gaps"])
    assert idle == {"compile": pytest.approx(30 / 1e9),
                    "store": pytest.approx(20 / 1e9),
                    "sweep": pytest.approx(20 / 1e9)}


def test_a_gap_is_split_across_the_spans_it_overlaps():
    dev = {"/device:TPU:0": [E("a", 0, 10), E("b", 90, 100)]}
    spans = [T.Span("lower", 5, 60, 2), T.Span("compile", 60, 95, 2)]
    idle = dict(T.reduce(dev, 0, 120, spans)["idle_gaps"])
    assert idle == {"lower": pytest.approx(50 / 1e9),
                    "compile": pytest.approx(30 / 1e9),
                    "none": pytest.approx(20 / 1e9)}


def test_host_timeline_picks_the_innermost_span():
    tl = T.HostTimeline([T.Span("sweep", 0, 100, 0), T.Span("job", 10, 50, 1),
                         T.Span("compile", 20, 30, 3)])
    assert list(tl.split(-5, 100)) == [
        ("none", 5), ("sweep", 10), ("job", 10), ("compile", 10),
        ("job", 20), ("sweep", 50)]
    assert list(tl.split(-5, 12)) == [("none", 5), ("sweep", 10),
                                      ("job", 2)]
    assert list(tl.split(95, 110)) == [("sweep", 5), ("none", 10)]


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        T.reduce({}, 0, 1)


def test_clock_offset_from_the_sync_annotation():
    host = [E("other", 5, 6), E("bench_sync", 1_000, 1_001)]
    assert T.clock_offset(host, "bench_sync", 400) == 600
    with pytest.raises(KeyError):
        T.clock_offset(host, "missing", 0)


def test_recorded_chip_trace():
    """The characters programs (C_sim and the batch similarity over a
    512 x 28 array, under a ``characters`` annotation) traced on one TPU
    v5e (bench/testdata)."""
    data = T.load(str(RECORDED))
    assert list(data["devices"]) == ["/device:TPU:0"]
    evs = data["devices"]["/device:TPU:0"]
    lo, hi = min(e.start for e in evs), max(e.end for e in evs)
    programs = {"csim": r"^jit_csim_kernel\(",
                "pairwise_l0": r"^jit__pairwise_l0_means\("}
    red = T.reduce(data["devices"], lo, hi, (), data["modules"], programs)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["program_runs"] == {"csim": 1, "pairwise_l0": 1}
    assert 0 < red["program_s"]["csim"] <= red["busy_s"]
    # the Pallas kernel runs once per shift: 8 for C_sim, 7 in-batch
    kernel = [e for e in evs if e.name.startswith("%l0_rows")]
    assert len(kernel) == 15
    assert T.clock_offset(data["host"], "bench_sync", 0) > 0
