"""The driver: the window counts the prompt tokens prefilled inside it, and
the counting pass runs after it and leaves the window's program as it
was."""
import _paths  # noqa: F401
from contextlib import nullcontext

import pytest

from drive import work_by
from tiny import tiny_cell


def test_work_by_counts_the_straddling_tick_for_its_share():
    ticks = [(0.0, 1.0, 100), (1.0, 1.1, 0), (1.1, 4.1, 300)]
    assert work_by(ticks, 2.6) == pytest.approx(100 + 150)
    assert work_by(ticks, 4.1) == pytest.approx(400)
    assert work_by(ticks[:2], 1.05) == pytest.approx(100)


def test_window_counts_prefill_and_the_counting_pass_stays_outside_it():
    from repro.config import ServeConfig
    from repro.serving import Engine

    from drive import Driver
    from model import init_params, model_config
    from traffic import generate

    cell = tiny_cell(sessions=64)
    tr = cell.traffic
    cfg = model_config(cell.config)
    eng = Engine(cfg, init_params(cfg, 3),
                 ServeConfig(max_batch=tr["max_batch"], max_context=tr["max_context"],
                             temperature=0.0, **tr["serve"]))
    d = Driver(eng, generate(tr, 3), cfg.vocab_size, lambda name: nullcontext())
    d.setup()
    window_cache = set(eng.cache)
    before = eng.metrics.prefill_tokens_computed
    win = d.run(3.0)
    assert 0 < win.prefill_tokens <= eng.metrics.prefill_tokens_computed - before
    assert win.seconds == 3.0 and win.t_end >= win.t0 + 3.0
    assert 0 < win.longest_tick_s <= win.t_end - win.t0
    assert d.log is None and d.trace_log is None  # no tracer: nothing logged

    counted = d.count(3)
    assert len(counted.decode_steps) == 3 and all(counted.decode_steps)
    assert counted.telemetry_pages > 0
    assert set(eng.cache) == window_cache         # counters gone again
