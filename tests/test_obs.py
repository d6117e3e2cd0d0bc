"""Observability stack: trace recorder, validator, sparsity telemetry.

Unit layer: ring/span/counter semantics on a virtual clock, Chrome-export
schema via the shipped validator, deferred counter flush hooks, lifecycle
span stack discipline on :class:`ServingMetrics`, and the
:func:`selection_telemetry` counter math against the selection path it
mirrors.  Engine layer: one traced serve smoke (spans + deferred sparsity
counters end-to-end), live ``set_tracing`` toggling, fused-vs-staged
decode counter parity, the engine's spans in a profiler capture, the
same compiled steps traced or not, the always-on decode page counter
against the opt-in telemetry, and one compile span per step signature.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ServeConfig
from repro.configs import get_config, smoke_variant
from repro.core.ragged import RaggedLayout
from repro.core.selection import (
    rank_blocks,
    select_page_table,
    selection_telemetry,
)
from repro.models import Transformer
from repro.obs import (
    BLOCKS,
    BUDGET,
    FORCED,
    PAGES,
    SparsityAggregate,
    TraceRecorder,
    prefill_block_candidates,
    validate_chrome_trace,
)
from repro.obs.trace import PID_ENGINE, PID_SEQ, SPAN_NAMES, Span
from repro.serving import Engine, Request
from repro.serving.metrics import ServingMetrics


class FakeClock:
    """Deterministic monotonic clock: every read advances by ``step``."""

    def __init__(self, step=1.0):
        self.t = 0.0
        self.step = step

    def __call__(self):
        self.t += self.step
        return self.t


@pytest.fixture(scope="module")
def setup():
    cfg = smoke_variant(get_config("llama3.2-3b"))
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, params


# ---------------------------------------------------------------------------
# TraceRecorder unit layer
# ---------------------------------------------------------------------------


def test_span_records_complete_event_on_virtual_clock():
    rec = TraceRecorder(clock=FakeClock())
    with rec.span("outer", PID_ENGINE, args={"tick": 3}):
        with rec.span("inner", PID_ENGINE):
            pass
    evs = rec.events()
    # spans record ONE "X" event at exit -> inner lands before outer.
    assert [e.name for e in evs] == ["inner", "outer"]
    inner, outer = evs
    assert inner.ph == outer.ph == "X"
    # the virtual clock ticks once per read: outer opened first, closed last.
    assert outer.ts < inner.ts
    assert outer.ts + outer.dur > inner.ts + inner.dur
    assert outer.args == {"tick": 3}


def test_span_metadata_set_inside_reaches_the_ring():
    rec = TraceRecorder(clock=FakeClock())
    with Span("engine.tick", rec, step=4, tokens=2) as sp:
        sp.set(pages=7)
        sp.set(pages=9, slots=1)
    with Span("engine.decode"):          # no recorder: annotation only
        pass
    (ev,) = rec.events()
    assert ev.name == "engine.tick" and ev.ph == "X"
    assert ev.args == {"step_num": 4, "tokens": 2, "pages": 9, "slots": 1}


def test_ring_eviction_counts_dropped_and_export_stays_valid():
    rec = TraceRecorder(capacity=8, clock=FakeClock())
    for i in range(20):
        rec.instant(f"ev{i}", PID_ENGINE)
    assert len(rec) == 8
    assert rec.dropped == 12
    # oldest-first eviction: only the most recent events survive.
    assert [e.name for e in rec.events()] == [f"ev{i}" for i in range(12, 20)]
    trace = rec.to_chrome()
    assert validate_chrome_trace(trace) == []
    assert trace["otherData"]["dropped_events"] == 12


def test_validator_accepts_good_and_rejects_corrupt_traces():
    rec = TraceRecorder(clock=FakeClock())
    rec.begin("seq.decode", PID_SEQ, 1)
    rec.counter("pool", {"used_pages": 3, "free_pages": 5})
    rec.end("seq.decode", PID_SEQ, 1)
    trace = rec.to_chrome()
    assert validate_chrome_trace(
        trace, require_spans=["seq.decode"], require_counters=["pool"]
    ) == []
    # a trace is JSON all the way down (Perfetto loads the dump verbatim).
    json.loads(json.dumps(trace))

    # a dangling "B" is LEGAL (mid-run dumps leave lifecycle spans open);
    # an "E" with no matching "B" on an unevicted ring is not.
    bad = TraceRecorder(clock=FakeClock())
    bad.end("seq.decode", PID_SEQ, 1)
    assert validate_chrome_trace(bad.to_chrome()) != []
    # stack discipline: an "E" must close the innermost open span.
    crossed = TraceRecorder(clock=FakeClock())
    crossed.begin("seq.prefill", PID_SEQ, 1)
    crossed.begin("seq.stall", PID_SEQ, 1)
    crossed.end("seq.prefill", PID_SEQ, 1)
    assert validate_chrome_trace(crossed.to_chrome()) != []
    # missing required span names must be flagged too.
    assert validate_chrome_trace(trace, require_spans=["nope"]) != []


def test_flush_hook_defers_counter_materialization():
    clock = FakeClock()
    rec = TraceRecorder(clock=clock)
    rec.instant("tick", PID_ENGINE)
    pending = [(clock(), {"blocks_attended": 7})]

    def flush():
        for ts, values in pending:
            rec.counter_at("sparsity", values, ts, pid=PID_ENGINE)
        pending.clear()

    rec.add_flush_hook(flush)
    # nothing materialized until export...
    assert all(e.name != "sparsity" for e in rec.events())
    trace = rec.to_chrome()
    assert pending == []  # hook ran exactly once, drained the queue
    cs = [e for e in trace["traceEvents"] if e["ph"] == "C"]
    assert len(cs) == 1 and cs[0]["args"] == {"blocks_attended": 7}
    # the deferred sample keeps its ORIGINAL timestamp (after the instant).
    inst = next(e for e in trace["traceEvents"] if e["name"] == "tick")
    assert cs[0]["ts"] > inst["ts"]
    assert validate_chrome_trace(trace, require_counters=["sparsity"]) == []


# ---------------------------------------------------------------------------
# ServingMetrics
# ---------------------------------------------------------------------------


def test_empty_metrics_snapshot_is_zero_and_serializable():
    snap = ServingMetrics().snapshot()
    json.dumps(snap)  # never NaN / missing keys on an empty run
    for key in ("ttft_mean", "ttft_p95", "tpot_mean", "queue_time_mean",
                "requests_finished", "prefix_hit_rate"):
        assert snap[key] == 0.0


def test_lifecycle_spans_balance_through_preemption():
    clock = FakeClock()
    rec = TraceRecorder(clock=clock)
    m = ServingMetrics(clock=clock)
    m.trace = rec
    m.on_submit(7, prompt_tokens=100)
    m.on_admit(7, prefix_hit_tokens=32)
    m.on_first_token(7)
    m.on_preempt(7)                      # decode -> back to queued
    m.on_admit(7)
    m.on_first_token(7)
    m.on_decode_token(7)
    m.on_finish(7)
    trace = rec.to_chrome()
    assert validate_chrome_trace(
        trace,
        require_spans=["seq.queued", "seq.prefill", "seq.decode"],
        require_instants=["seq.preempt", "prefix.hit"],
    ) == []
    # every phase begin closed: the full round trip visits queued twice.
    names = [e["name"] for e in trace["traceEvents"] if e["ph"] == "B"]
    assert names.count("seq.queued") == 2
    r = m.requests[7]
    assert r.preemptions == 1 and r.prefix_hit_tokens == 32
    assert m.snapshot()["requests_finished"] == 1


# ---------------------------------------------------------------------------
# sparsity telemetry math
# ---------------------------------------------------------------------------


def test_selection_telemetry_matches_selection_path():
    layout = RaggedLayout(
        block_sizes=(32, 64), context_len=256, page_size=16, token_budget=128
    )
    scores = jax.random.normal(jax.random.PRNGKey(1), (2, 2, layout.max_blocks))
    tel = np.asarray(selection_telemetry(scores, layout))
    assert tel.shape == (2, 4) and tel.dtype == np.int32

    # budget: sum of per-head top-k (128/32 + 128/64); full context -> every
    # budget slot fills, so blocks == budget.
    assert (tel[:, BUDGET] == 6).all()
    assert (tel[:, BLOCKS] == 6).all()
    # pages: per-head gathers = blocks * pages_per_block (2 and 4 here) ->
    # must equal what select_page_table actually marks valid.
    _, page_valid = select_page_table(scores, layout)
    assert (tel[:, PAGES] == np.asarray(page_valid).sum(axis=(1, 2))).all()
    assert (tel[:, PAGES] == 4 * 2 + 2 * 4).all()
    # forced: sink (1 block/head) + local-window pins (2 for B=32, 1 for
    # B=64 with the default 4-page window) — score-independent.
    assert (tel[:, FORCED] == 5).all()

    # sharing the ranking with the selection path must not change counts.
    ranked = rank_blocks(scores, layout, None, 1, 4)
    tel2 = np.asarray(selection_telemetry(scores, layout, ranked=ranked))
    np.testing.assert_array_equal(tel, tel2)

    # a short live context masks blocks -> fewer selected than budget.
    tel_short = np.asarray(
        selection_telemetry(scores, layout, seq_len=jnp.int32(64))
    )
    assert (tel_short[:, BLOCKS] < tel_short[:, BUDGET]).all()
    assert (tel_short[:, BLOCKS] >= 1).all()


def test_sparsity_aggregate_folds_live_slots_only():
    agg = SparsityAggregate(n_layers=2)
    tel = np.zeros((2, 3, 4), dtype=np.int32)
    tel[:, 0] = [4, 8, 2, 6]             # live slot
    tel[:, 2] = [99, 99, 99, 99]         # stale slot — must not count
    agg.update_decode(tel, slots=[0])
    agg.update_decode(tel, slots=[0])
    snap = agg.snapshot()
    assert snap["sparsity_steps"] == 2
    assert snap["blocks_per_step"] == 8.0          # 2 layers x 4
    assert snap["pages_per_step"] == 16.0
    assert snap["budget_utilization"] == pytest.approx(4 / 6)
    assert snap["forced_frac"] == pytest.approx(2 / 4)
    # deciles over (step, slot) pairs: util 4/6 -> bin 6, twice.
    assert agg.util_hist[6] == 2 and agg.util_hist.sum() == 2


def test_prefill_block_candidates_monotone():
    layout = RaggedLayout(
        block_sizes=(32, 64), context_len=256, page_size=16, token_budget=128
    )
    first = prefill_block_candidates([layout], 0, 128, block_q=64)
    later = prefill_block_candidates([layout], 128, 128, block_q=64)
    assert first.shape == (1,) and (first > 0).all()
    # later chunks see causally more key blocks per query block.
    assert (later >= first).all()


def test_kernel_cost_model_sane():
    from repro.obs.cost import decode_kernel_cost, prefill_kernel_cost

    cfg = get_config("llama3.2-3b")
    for ctx in (4096, 65536):
        d = decode_kernel_cost(cfg, ctx)
        p = prefill_kernel_cost(cfg, ctx, chunk_tokens=512)
        for c in (d, p):
            assert c["flops"] > 0 and c["dense_flops"] > 0
            assert c["hbm_bytes"] > 0 and c["dense_hbm_bytes"] > 0
            assert 0 < c["realized_sparsity_frac"] <= 1.0
    # at long context the budget cap dominates: sparse must beat dense on
    # both axes (at short context scoring overhead may legally exceed the
    # savings — budget ~ context there).
    for c in (decode_kernel_cost(cfg, 65536),
              prefill_kernel_cost(cfg, 65536, chunk_tokens=512)):
        assert 0 < c["flops_vs_dense"] < 1.0
        assert 0 < c["bytes_vs_dense"] < 1.0
    # and sparsity bites harder as context grows.
    assert (
        decode_kernel_cost(cfg, 65536)["bytes_vs_dense"]
        < decode_kernel_cost(cfg, 4096)["bytes_vs_dense"]
    )


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------


def _run_batch(eng, cfg, n=4, prompt=96, new_tokens=8, seed=3, base_rid=0):
    rng = np.random.default_rng(seed)
    reqs = [
        Request(
            base_rid + i,
            rng.integers(0, cfg.vocab_size, prompt).astype(np.int32),
            max_new_tokens=new_tokens,
        )
        for i in range(n)
    ]
    for r in reqs:
        eng.submit(r)
    eng.run_until_done(max_ticks=500)
    assert all(r.done and len(r.output) == new_tokens for r in reqs)
    return reqs


def test_traced_engine_produces_valid_trace_and_telemetry(setup, tmp_path):
    cfg, params = setup
    # sparse prefill on, so the chunk launches emit per-layer counters too.
    cfg = dataclasses.replace(
        cfg, sparse=dataclasses.replace(cfg.sparse, sparse_prefill=True)
    )
    rec = TraceRecorder()
    eng = Engine(
        cfg, params, ServeConfig(max_batch=2, max_context=512), trace=rec,
        telemetry=True,
    )
    assert "_telemetry" in eng.cache          # telemetry is opt-in
    _run_batch(eng, cfg)

    # sparsity counters are DEFERRED: queued on the hot path, materialized
    # only by the export-time flush hook.
    assert all(e.name != "sparsity" for e in rec.events())
    path = rec.dump(str(tmp_path / "trace.json"))
    with open(path) as f:
        trace = json.load(f)
    assert validate_chrome_trace(
        trace,
        require_spans=["engine.tick", "engine.decode", "seq.queued",
                       "seq.prefill", "seq.decode"],
        require_counters=["pool", "queue", "sparsity"],
        require_instants=["sched.admit"],
    ) == []
    spars = [e for e in trace["traceEvents"]
             if e["ph"] == "C" and e["name"] == "sparsity"]
    assert spars, "deferred sparsity counters must land in the export"
    for e in spars:
        assert e["args"]["blocks_attended"] > 0
        assert e["args"]["pages_dma"] >= e["args"]["blocks_attended"]
        assert 0 < e["args"]["budget_util_pct"] <= 100.0

    snap = eng.metrics.snapshot()
    json.dumps(snap)
    assert snap["sparsity_steps"] > 0
    assert snap["blocks_per_step"] > 0
    assert 0 < snap["budget_utilization"] <= 1.0
    assert 0 <= snap["forced_frac"] <= 1.0
    # the sparse prefill block counter, read at the token reads, folded in
    # every chunk: the same totals the tick spans carry.
    assert snap["prefill_chunks"] > 0
    assert 0 < snap["prefill_blocks_frac"] <= 1.0
    ticks = [e.args for e in rec.events() if e.name == "engine.tick"]
    assert sum(a.get("prefill_blocks", 0) for a in ticks) == (
        snap["prefill_blocks_attended"])


def test_set_tracing_toggles_live_engine(setup):
    cfg, params = setup
    eng = Engine(cfg, params, ServeConfig(max_batch=2, max_context=512))
    # default OFF: no recorder, no telemetry entries in the decode cache.
    assert eng.trace is None and "_telemetry" not in eng.cache
    _run_batch(eng, cfg, n=2, base_rid=0)
    decode = eng.steps["decode"]
    n_programs = len(decode.compiled_texts())

    rec = TraceRecorder()
    eng.set_tracing(rec)
    # a recorder leaves the decode cache, and so the program, as it was
    assert "_telemetry" not in eng.cache
    _run_batch(eng, cfg, n=2, base_rid=10)
    assert len(decode.compiled_texts()) == n_programs
    assert len(rec) > 0
    assert validate_chrome_trace(rec.to_chrome()) == []
    # export ran the flush hook, so deferred counters are in the ring now.
    traced_len = len(rec)

    eng.set_tracing(None, telemetry=True)      # the opt-in counters
    assert "_telemetry" in eng.cache and eng.metrics.trace is None
    _run_batch(eng, cfg, n=2, base_rid=20)
    assert eng.metrics.snapshot()["sparsity_steps"] > 0
    eng.set_tracing(None)
    assert "_telemetry" not in eng.cache
    _run_batch(eng, cfg, n=2, base_rid=30)
    assert len(rec) == traced_len          # detached recorder stays frozen


def test_fused_and_staged_decode_report_identical_counters(setup):
    cfg, params = setup
    fused_cfg = dataclasses.replace(
        cfg, sparse=dataclasses.replace(cfg.sparse, fused_decode=True)
    )
    snaps = []
    for c in (cfg, fused_cfg):
        eng = Engine(
            c, params,
            ServeConfig(max_batch=2, max_context=512, temperature=0.0),
            telemetry=True,
        )
        _run_batch(eng, c, n=2, prompt=80, new_tokens=6)
        snaps.append(eng.metrics.snapshot())
    staged, fused = snaps
    # the fused single-launch kernel recomputes the same ranked selection
    # the staged pipeline materializes — counters must agree exactly.
    for key in ("sparsity_steps", "blocks_per_step", "pages_per_step",
                "budget_utilization", "forced_frac"):
        assert staged[key] == pytest.approx(fused[key]), key


def _sparse_prefill_cfg(cfg, **sparse):
    return dataclasses.replace(
        cfg, sparse=dataclasses.replace(
            cfg.sparse, sparse_prefill=True, **sparse)
    )


def _host_spans(log_dir):
    """(name, start_ns, end_ns, stats) of every engine span in the newest
    capture under ``log_dir``."""
    from pathlib import Path

    path = sorted(Path(log_dir).rglob("*.xplane.pb"))[-1]
    pd = jax.profiler.ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in SPAN_NAMES:
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns, dict(ev.stats)))
    return out


def test_profiler_capture_holds_engine_spans_nested_with_metadata(
        setup, tmp_path):
    cfg, params = setup
    cfg = _sparse_prefill_cfg(cfg)
    # no recorder: the spans reach the capture as profiler annotations
    eng = Engine(cfg, params, ServeConfig(max_batch=2, max_context=512))
    jax.profiler.start_trace(str(tmp_path))
    try:
        _run_batch(eng, cfg, n=3, new_tokens=4)
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(tmp_path)
    by = {n: [s for s in spans if s[0] == n] for n in SPAN_NAMES}
    assert all(by[n] for n in SPAN_NAMES), {n: len(v) for n, v in by.items()}

    def inside(span, parents):
        return any(p[1] <= span[1] and span[2] <= p[2] for p in parents)

    nesting = {
        "sched.plan": ("engine.tick",),
        "prefill.chunk": ("engine.tick",),
        "engine.decode": ("engine.tick",),
        "prefill.finish": ("prefill.chunk",),
        "engine.sample": ("engine.decode", "prefill.finish"),
        "engine.wait": ("engine.decode", "prefill.finish"),
        "engine.commit": ("engine.decode",),
        "engine.compile": ("engine.tick",),
    }
    for child, parents in nesting.items():
        outer = [s for p in parents for s in by[p]]
        assert all(inside(s, outer) for s in by[child]), child

    ticks = by["engine.tick"]
    assert sorted(t[3]["step_num"] for t in ticks) == list(range(len(ticks)))
    assert sum(t[3]["prefill_tokens"] for t in ticks) == 3 * 96
    assert sum(t[3].get("prefill_blocks", 0) for t in ticks) > 0
    assert all(t[3].get("prefill_blocks", 0) <= t[3].get(
        "prefill_candidates", 0) for t in ticks)
    plans = by["sched.plan"]
    assert sum(p[3]["admitted"] for p in plans) == 3
    chunks = by["prefill.chunk"]
    assert {c[3]["seq"] for c in chunks} == {0, 1, 2}
    assert sum(c[3]["tokens"] for c in chunks) == 3 * 96
    assert {f[3]["seq"] for f in by["prefill.finish"]} == {0, 1, 2}
    decodes = [d[3] for d in by["engine.decode"] if "slots" in d[3]]
    assert decodes and all(
        d["pages"] > 0 and d["live_tokens"] > 96 and
        len(str(d["seqs"]).split()) == d["slots"] for d in decodes)
    assert {c[3]["program"] for c in by["engine.compile"]} >= {
        "decode", "prefill_chunk", "sample"}


def test_traced_and_untraced_engines_compile_identical_programs(setup):
    cfg, params = setup
    cfg = _sparse_prefill_cfg(cfg)
    texts = []
    for rec in (None, TraceRecorder()):
        eng = Engine(cfg, params, ServeConfig(max_batch=2, max_context=512),
                     trace=rec)
        _run_batch(eng, cfg, n=2, new_tokens=4)
        texts.append({name: step.compiled_texts()
                      for name, step in eng.steps.items()})
    untraced, traced = texts
    for name in ("decode", "prefill_chunk"):
        assert untraced[name], name
        assert traced[name] == untraced[name], name
    assert traced == untraced


@pytest.mark.parametrize("rung", ["fused", "staged"])
def test_decode_page_counter_matches_telemetry_pages(setup, rung):
    cfg, params = setup
    cfg = dataclasses.replace(cfg, sparse=dataclasses.replace(
        cfg.sparse, backend="pallas", fused_decode=rung == "fused"))
    rec = TraceRecorder()
    eng = Engine(cfg, params,
                 ServeConfig(max_batch=2, max_context=512, temperature=0.0),
                 trace=rec, telemetry=True)
    assert eng._ladder[0][0] == rung
    _run_batch(eng, cfg, n=2, prompt=80, new_tokens=4)
    # the opt-in telemetry's PAGES column over the live slots, per tick
    tel_pages = [int(tel[:, slots, PAGES].sum())
                 for _, tel, slots in eng._tel_pending]
    span_pages = [e.args["pages"] for e in rec.events()
                  if e.name == "engine.decode" and e.args
                  and "pages" in e.args]
    assert tel_pages and span_pages == tel_pages


def test_compile_span_fires_once_per_new_signature(setup):
    cfg, params = setup
    rec = TraceRecorder()
    eng = Engine(cfg, params, ServeConfig(max_batch=2, max_context=512),
                 trace=rec)
    _run_batch(eng, cfg, n=2, new_tokens=4)
    compiles = [e.args for e in rec.events() if e.name == "engine.compile"]
    for name, step in eng.steps.items():
        mine = [a["signatures"] for a in compiles if a["program"] == name]
        assert mine == list(range(1, len(step.compiled_texts()) + 1)), name
    assert {a["program"] for a in compiles} == {
        n for n, s in eng.steps.items() if s.compiled_texts()}
    # the same shapes again: nothing new compiles, so no new span
    _run_batch(eng, cfg, n=2, new_tokens=4, base_rid=10)
    assert sum(e.name == "engine.compile" for e in rec.events()) == len(
        compiles)
