"""The program's spans: the reductions on synthetic spans and a synthetic
trace, one CPU capture of the engine read back, and the tool on a tiny
run."""
import _paths
import math
import sys

import pytest

import spans
from spans import Span
from tracing import DeviceTrace

sys.path.insert(0, str(_paths.HERE / "tools"))
import program_spans  # noqa: E402


def _spans():
    # two ticks in the window [0, 10], one after it
    return [
        Span("engine.tick", 0.0, 4.0, {"step_num": 0, "prefill_blocks": 30,
                                       "prefill_candidates": 100}),
        Span("sched.plan", 0.1, 0.2),
        Span("prefill.chunk", 0.2, 1.0, {"seq": 3, "offset": 0, "tokens": 64}),
        Span("engine.decode", 1.0, 3.9, {"slots": 2, "seqs": "1 2",
                                         "live_tokens": 200, "pages": 40}),
        Span("engine.sample", 1.2, 1.3),
        Span("engine.wait", 1.3, 3.5),
        Span("engine.commit", 3.5, 3.8),
        Span("engine.tick", 5.0, 6.0, {"step_num": 1}),
        Span("engine.decode", 5.1, 5.9, {"slots": 1, "seqs": "2",
                                         "live_tokens": 120, "pages": 24}),
        Span("engine.wait", 5.2, 5.7),
        Span("engine.tick", 11.0, 12.0, {"step_num": 2, "prefill_blocks": 99,
                                         "prefill_candidates": 99}),
    ]


WINDOW = (0.0, 10.0)


def test_host_tick_time_leaves_out_the_blocking_reads():
    # tick 0: 4.0 - 2.2 s waiting; tick 1: 1.0 - 0.5 s
    assert spans.host_tick_ms(_spans(), WINDOW) == pytest.approx(
        1e3 * (1.8 + 0.5) / 2)


def test_attended_shares_sum_the_window_metadata():
    sp = _spans()
    # (40 + 24) pages x 16 tokens / ((200 + 120) live x 2 heads x 4 layers)
    assert spans.decode_attended_fraction(sp, WINDOW, 16, 2, 4) == (
        pytest.approx(100.0 * 64 * 16 / (320 * 8)))
    assert spans.prefill_attended_fraction(sp, WINDOW) == pytest.approx(30.0)


def test_a_program_without_spans_reads_nothing():
    tr = DeviceTrace(window=WINDOW, n_chips=1, ops=[[(0.0, 1.0)]])
    assert spans.host_tick_ms([], WINDOW) is None
    assert spans.decode_attended_fraction([], WINDOW, 16, 2, 4) is None
    assert spans.prefill_attended_fraction([], WINDOW) is None
    assert spans.idle_by_span(tr, []) == []
    assert spans.device_between([], 0.0, 1.0) == {}


def test_idle_time_goes_to_the_innermost_program_span():
    # chip 0 busy 0-1.3 and 3.5-5.2 and 5.7-10: gaps (1.3, 3.5) inside a
    # wait inside a decode inside a tick, (5.2, 5.7) inside a wait
    tr = DeviceTrace(window=WINDOW, n_chips=1,
                     ops=[[(0.0, 1.3), (3.5, 5.2), (5.7, 10.0)]],
                     host=[("decode", 1.0, 4.0)])
    assert spans.idle_by_span(tr, _spans()) == [
        ["engine.wait", pytest.approx(2.7)]]
    # the harness's own labels stay as they were
    assert tr.breakdown()["idle_gaps"] == [
        ["decode", pytest.approx(2.2)], ["other", pytest.approx(0.5)]]


def test_tick_anatomy_sums_inner_spans_and_what_none_covers():
    sp = _spans()
    a = spans.tick_anatomy(sp, sp[0])
    assert a["tick_s"] == pytest.approx(4.0)
    assert a["spans_s"]["engine.decode"] == pytest.approx(2.9)
    assert a["spans_s"]["engine.wait"] == pytest.approx(2.2)
    # covered: 0.1-0.2, 0.2-1.0, 1.0-3.9
    assert a["uncovered_s"] == pytest.approx(4.0 - 3.8)
    assert a["stats"]["step_num"] == 0


def test_device_between_clips_chip_time_to_the_interval():
    events = [("XLA Modules", "jit_decode_step(3)", 0.0, 4.0),
              ("XLA Ops", "fusion.1 = f32[2]", 0.5, 1.5),
              ("XLA Ops", "absparse_fused_decode", 1.0, 3.0),
              ("XLA Ops", "copy.2", 3.5, 5.0),
              ("Steps", "ignored", 0.0, 9.0)]
    got = spans.device_between(events, 1.0, 4.0)
    # busy: 1.0-3.0 and 3.5-4.0 (the fusion's 1.0-1.5 lies inside)
    assert got["busy_s"] == pytest.approx(2.5)
    assert got["programs"] == [["jit_decode_step(3)", pytest.approx(3.0)]]
    assert got["ops"] == [["absparse_fused_decode", pytest.approx(2.0)],
                          ["fusion.1", pytest.approx(0.5)],
                          ["copy.2", pytest.approx(0.5)]]


def test_cpu_capture_of_the_engine_reads_back(tmp_path):
    from repro.config import ServeConfig
    from repro.serving import Engine

    import tracing
    from drive import Driver
    from model import init_params, model_config
    from tiny import tiny_cell
    from traffic import generate

    cell = tiny_cell(sessions=4)
    tr = cell.traffic
    cfg = model_config(cell.config)
    eng = Engine(cfg, init_params(cfg, 5),
                 ServeConfig(max_batch=tr["max_batch"],
                             max_context=tr["max_context"], temperature=0.0,
                             **tr["serve"]))
    d = Driver(eng, generate(tr, 5), cfg.vocab_size)
    with tracing.capture(tmp_path):
        d.setup()
        d.run(0.5)
    names = program_spans.program_span_names()
    sp, dev = program_spans.read(tracing.newest_xplane(tmp_path), names)
    assert dev == []                           # no TPU plane on the CPU
    window = (-math.inf, math.inf)
    assert {s.name for s in sp} >= {"engine.tick", "engine.decode",
                                    "prefill.chunk", "engine.wait"}
    dec = spans.decode_attended_fraction(
        sp, window, cfg.sparse.page_size, cfg.n_kv_heads, cfg.n_layers)
    assert 0 < dec <= 100
    assert 0 < spans.prefill_attended_fraction(sp, window) <= 100
    assert spans.host_tick_ms(sp, window) > 0


def test_program_spans_tool_reads_a_tiny_run(monkeypatch):
    import gc

    import run
    import tracing
    from tiny import on_cpu, tiny_cell

    on_cpu(monkeypatch)
    cell = tiny_cell(sessions=4)
    monkeypatch.setattr(run, "load_cell", lambda root, name: cell)
    # the tool rewires the reduction for its own process; undo it after
    monkeypatch.setattr(tracing, "read_xplane", tracing.read_xplane)
    before = list(gc.callbacks)
    try:
        line = program_spans.one("tiny", 7, 1.0, 1.0, mark_gc=True)
    finally:
        gc.callbacks[:] = before
    ps = line["program_spans"]
    assert line["correct"]                     # the run's own comparison
    assert ps["ticks"] > 0 and ps["host_tick_ms"] > 0
    assert 0 < ps["decode_attended_fraction"] <= 100
    assert ps["slowest_tick"]["excess_s"] >= 0
    assert ps["gc"]["count"] > 0               # collections are marked
