"""The trace reduction on a small synthetic trace."""
import _paths  # noqa: F401
import pytest

from tracing import DeviceTrace, clip, gaps, label_gaps, union


def test_union_merges_overlaps_and_drops_empty():
    assert union([(3, 4), (0, 1), (0.5, 2), (2, 2.5), (5, 5)]) == [(0, 2.5), (3, 4)]


def test_gaps_inside_window():
    busy = [(0.0, 1.0), (2.0, 3.0), (3.5, 6.0)]
    assert gaps(busy, 0.5, 5.0) == [(1.0, 2.0), (3.0, 3.5)]
    assert gaps([], 0.0, 1.0) == [(0.0, 1.0)]
    assert clip(busy, 2.5, 4.0) == [(2.5, 3.0), (3.5, 4.0)]


def test_gap_takes_innermost_covering_annotation():
    host = [("tick", 0.0, 10.0), ("sample", 1.0, 2.0), ("admit", 5.0, 6.0),
            ("tick", 10.5, 20.0), ("sample", 12.0, 12.5)]
    assert label_gaps([(1.2, 1.6), (3.0, 4.0), (10.1, 10.3), (5.2, 5.4),
                       (12.1, 12.2), (13.0, 14.0)], host) == [
        "sample", "tick", "other", "admit", "sample", "tick"]


def _trace():
    # two chips; window [1, 9]; chip 0 busy 1-3 and 4-6 (ops overlap),
    # chip 1 busy 2-8
    ops0 = [(0.0, 3.0), (2.0, 2.5), (4.0, 6.0)]
    ops1 = [(2.0, 8.0)]
    return DeviceTrace(
        window=(1.0, 9.0), n_chips=2, ops=[ops0, ops1],
        op_time={"absparse_fused_decode.1": 1.5, "fusion.2": 2.0,
                 "absparse_fused_decode.3": 0.5},
        op_count={"absparse_fused_decode.1": 3, "fusion.2": 4,
                  "absparse_fused_decode.3": 1},
        module_time={"jit_decode_step(7)": 4.0}, module_count={"jit_decode_step(7)": 8},
        host=[("tick", 0.0, 9.0), ("sample", 3.0, 3.9)])


def test_busy_is_interval_union_averaged_over_chips():
    tr = _trace()
    # chip 0: [1,3] + [4,6] = 4 s; chip 1: [2,8] = 6 s
    assert tr.busy_s() == pytest.approx(5.0)
    assert tr.window_s == pytest.approx(8.0)


def test_kernel_time_sums_every_matching_op():
    tr = _trace()
    assert tr.time_matching("absparse_fused_decode") == (pytest.approx(2.0), 4)
    assert tr.time_matching("decode_step", modules=True) == (pytest.approx(4.0), 8)


def test_breakdown_sums_idle_time_of_chip0_by_host_activity():
    bd = _trace().breakdown()
    assert bd["device_ops"][0] == ["fusion.2", 2.0]
    # chip 0 gaps in [1, 9]: (3, 4) under "sample", (6, 9) under "tick"
    assert bd["idle_gaps"] == [["tick", 3.0], ["sample", 1.0]]


def test_enclosing_op_is_left_out_of_op_times(tmp_path):
    class Ev:
        def __init__(self, name, s, d):
            self.name, self.start_ns, self.duration_ns = name, s, d

    class Line:
        def __init__(self, name, events):
            self.name, self.events = name, events

    class Plane:
        def __init__(self, name, lines):
            self.name, self.lines = name, lines

    class PD:
        planes = [
            Plane("/device:TPU:0", [
                Line("XLA Ops", [Ev("%while.1 = (s32[]) while(...)", 0, 100),
                                 Ev("%absparse_fused_decode.3 = f(x)", 10, 30),
                                 Ev("%fusion.2 = g(y)", 50, 20)]),
                Line("XLA Modules", [Ev("jit_decode_step(7)", 0, 100)])]),
            Plane("/host:CPU", [Line("python", [Ev("bench.window", 0, 200),
                                                Ev("bench.tick", 0, 150)])]),
        ]

    import jax

    orig = jax.profiler.ProfileData.from_file
    jax.profiler.ProfileData.from_file = staticmethod(lambda p: PD)
    try:
        from tracing import read_xplane

        tr = read_xplane(tmp_path / "x.xplane.pb")
    finally:
        jax.profiler.ProfileData.from_file = orig
    assert tr.op_time == pytest.approx(
        {"%absparse_fused_decode.3": 30e-9, "%fusion.2": 20e-9})
    assert tr.busy_s() == pytest.approx(100e-9)
    assert tr.window == pytest.approx((0.0, 200e-9))
    assert tr.module_count == {"jit_decode_step(7)": 1}
    assert [h[0] for h in tr.host] == ["tick"]
