"""Fault-injection harness + failure-domain hardening (repro.resilience).

The load-bearing property throughout: a request that recovers from an
injected fault (restore-from-checkpoint, degraded re-run, watchdog
preemption) must produce a token stream IDENTICAL to a fault-free run of
the same seed — sampling is keyed by (seq_id, position) and KV rewrites
are idempotent, so recovery is invisible in the output.
"""
import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.config import ServeConfig
from repro.configs import get_config, smoke_variant
from repro.models import Transformer
from repro.resilience import (
    FaultInjector,
    FaultSpec,
    HostIOError,
    InjectedDeviceError,
    default_storm,
    dump_plan,
    load_plan,
)
from repro.serving import Engine, EngineStalled, Request
from repro.serving.sampler import SamplerAnomaly, guarded_sample


@pytest.fixture(scope="module")
def setup():
    cfg = smoke_variant(get_config("llama3.2-3b"))
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, params


def _run(cfg, params, injector=None, n_requests=2, prompt_tokens=80,
         new_tokens=8, max_ticks=400, **serve_kw):
    serve_kw.setdefault("max_batch", 2)
    serve_kw.setdefault("max_context", 512)
    eng = Engine(cfg, params, ServeConfig(**serve_kw))
    if injector is not None:
        eng.set_fault_injector(injector)
    rng = np.random.default_rng(3)
    reqs = [
        Request(i, rng.integers(0, cfg.vocab_size, prompt_tokens)
                .astype(np.int32), max_new_tokens=new_tokens)
        for i in range(n_requests)
    ]
    for r in reqs:
        eng.submit(r)
    eng.run_until_done(max_ticks=max_ticks)
    return eng, reqs


# -- injector plumbing -------------------------------------------------------


def test_injector_firing_is_deterministic():
    specs = [FaultSpec("decode", from_tick=0, until_tick=50, p=0.3),
             FaultSpec("host_io", from_tick=5, every=2, p=0.5, seq_id=1)]

    def record(seed):
        inj = FaultInjector([dataclasses.replace(s) for s in specs],
                            seed=seed)
        return [
            (t, sid, inj.fires(site, t, sid))
            for t in range(40)
            for site in ("decode", "host_io")
            for sid in (None, 1)
        ]

    assert record(7) == record(7), "same seed must fire identically"
    assert record(7) != record(8), "seed must actually vary the rolls"


def test_spec_window_and_count():
    sp = FaultSpec("decode", from_tick=4, until_tick=10, every=3, count=2)
    inj = FaultInjector([sp])
    fired_at = [t for t in range(20) if inj.fires("decode", t)]
    assert fired_at == [4, 7], "window/stride/count must all bind"
    assert inj.snapshot()["fired"] == {"decode": 2}


def test_plan_roundtrip(tmp_path):
    plan = tmp_path / "plan.json"
    dump_plan(default_storm(), str(plan))
    loaded = load_plan(str(plan))
    assert [s.site for s in loaded] == [s.site for s in default_storm()]
    assert all(s.fired == 0 for s in loaded)
    with pytest.raises(ValueError, match="JSON list"):
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps({"site": "decode"}))
        load_plan(str(bad))


def test_unknown_site_rejected():
    with pytest.raises(ValueError, match="unknown fault site"):
        FaultSpec("gamma_ray")


# -- sampler hardening (satellite regression) --------------------------------


def test_guarded_sample_raises_on_poisoned_logits():
    """Regression: NaN/Inf logits used to sail through top-p softmax and
    ``categorical`` still returned *a* token — silently corrupt output."""
    key = jax.random.PRNGKey(0)
    logits = np.zeros((3, 8), np.float32)
    logits[1, 3] = np.nan
    with pytest.raises(SamplerAnomaly) as ei:
        guarded_sample(key, jax.numpy.asarray(logits), seq_ids=[10, 11, 12])
    assert ei.value.seq_ids == [11]
    # clean rows sample fine
    clean = guarded_sample(key, jax.numpy.asarray(np.zeros((3, 8))))
    assert clean.shape == (3,)
    # Inf is just as poisoned as NaN
    logits[1, 3] = np.inf
    with pytest.raises(SamplerAnomaly):
        guarded_sample(key, jax.numpy.asarray(logits))


# -- zero-overhead / parity with no faults -----------------------------------


def test_empty_injector_is_invisible(setup):
    """An installed injector with no specs (and detaching one) must leave
    the engine's behaviour exactly as if none was ever installed."""
    cfg, params = setup
    eng_b, reqs_b = _run(cfg, params)
    eng_i, reqs_i = _run(cfg, params, injector=FaultInjector([]))
    assert [r.output for r in reqs_i] == [r.output for r in reqs_b]
    assert all(r.status == "ok" for r in reqs_i)
    snap = eng_i.metrics.snapshot()
    assert snap["retries"] == 0 and snap["requests_failed"] == 0
    eng_i.set_fault_injector(None)
    assert eng_i.pool.fault_hook is None


# -- failure domains, one per injected fault class ---------------------------


def test_nan_poison_restores_token_identical(setup):
    """decode_nan -> SamplerAnomaly -> restore-from-checkpoint: the
    poisoned sequence re-admits and regenerates BYTE-IDENTICAL output
    (keyed sampling), the peer never notices."""
    cfg, params = setup
    _, reqs_b = _run(cfg, params, new_tokens=10)
    inj = FaultInjector([
        FaultSpec("decode_nan", from_tick=2, until_tick=6, seq_id=0,
                  count=1),
    ])
    eng, reqs = _run(cfg, params, injector=inj, new_tokens=10)
    assert inj.fired.get("decode_nan") == 1, "fault must actually fire"
    assert [r.output for r in reqs] == [r.output for r in reqs_b]
    assert all(r.status == "ok" and r.done for r in reqs)
    snap = eng.metrics.snapshot()
    assert snap["sampler_anomalies"] >= 1
    assert snap["checkpoints_restored"] >= 1
    assert snap["retries"] >= 1


def test_injected_device_error_restores_identical(setup):
    cfg, params = setup
    _, reqs_b = _run(cfg, params, new_tokens=8)
    inj = FaultInjector([FaultSpec("decode", tick=3, count=1)])
    eng, reqs = _run(cfg, params, injector=inj, new_tokens=8)
    assert inj.fired.get("decode") == 1
    assert [r.output for r in reqs] == [r.output for r in reqs_b]
    assert all(r.status == "ok" for r in reqs)
    assert eng.metrics.snapshot()["retries"] >= 1


def test_prefill_fault_restores_identical(setup):
    cfg, params = setup
    _, reqs_b = _run(cfg, params, new_tokens=6)
    inj = FaultInjector([FaultSpec("prefill", tick=0, count=1)])
    eng, reqs = _run(cfg, params, injector=inj, new_tokens=6)
    assert inj.fired.get("prefill") == 1
    assert [r.output for r in reqs] == [r.output for r in reqs_b]
    assert all(r.status == "ok" for r in reqs)


def test_pool_exhaustion_burst_recovers_identical(setup):
    """Injected transient PoolExhausted out of the allocator: absorbed by
    admission control / preemption, everything still completes identically."""
    cfg, params = setup
    kw = dict(n_requests=3, prompt_tokens=96, new_tokens=8, max_batch=3)
    _, reqs_b = _run(cfg, params, **kw)
    inj = FaultInjector([
        FaultSpec("pool_alloc", from_tick=0, until_tick=30, every=2,
                  count=3),
    ])
    _, reqs = _run(cfg, params, injector=inj, **kw)
    assert inj.fired.get("pool_alloc", 0) >= 1
    assert [r.output for r in reqs] == [r.output for r in reqs_b]
    assert all(r.status == "ok" for r in reqs)


def test_failure_budget_retires_request_as_failed(setup):
    """A persistent per-sequence fault exhausts the failure budget: the
    request retires as FAILED with a structured reason; its peer is
    untouched and token-identical to the fault-free run."""
    cfg, params = setup
    _, reqs_b = _run(cfg, params, new_tokens=6)
    inj = FaultInjector([
        FaultSpec("decode_nan", from_tick=0, until_tick=10_000, seq_id=0),
    ])
    eng, reqs = _run(cfg, params, injector=inj, new_tokens=6)
    bad, ok = reqs[0], reqs[1]
    assert bad.done and bad.status == "failed"
    assert bad.failure["reason"] == "sampler_anomaly"
    assert bad.failure["retries"] > eng.resilience.failure_budget
    assert ok.status == "ok" and ok.output == reqs_b[1].output
    snap = eng.metrics.snapshot()
    assert snap["requests_failed"] == 1
    assert snap["failed_by_reason"] == {"sampler_anomaly": 1}
    # failed requests carry no t_finish: latency aggregates stay clean
    assert eng.metrics.requests[0].t_finish is None
    # pool accounting is clean after a budget-exhausted retirement
    known = eng.prefix_cache.pages() if eng.prefix_cache else set()
    assert eng.pool.assert_consistent(known_pins=known) == []


def test_tick_stuck_window_trips_watchdog(setup):
    """A stuck-clock window longer than ``watchdog_ticks``: the watchdog
    must fire, break the stall by preemption, and the run must still end
    token-identical to fault-free."""
    cfg, params = setup
    _, reqs_b = _run(cfg, params, new_tokens=8)
    inj = FaultInjector([
        FaultSpec("tick_stuck", from_tick=2, until_tick=14),
    ])
    eng, reqs = _run(cfg, params, injector=inj, new_tokens=8)
    assert inj.fired.get("tick_stuck", 0) >= eng.resilience.watchdog_ticks
    snap = eng.metrics.snapshot()
    assert snap["watchdog_fires"] >= 1
    assert [r.output for r in reqs] == [r.output for r in reqs_b]
    assert all(r.status == "ok" for r in reqs)


def test_engine_stalled_carries_diagnostics(setup):
    cfg, params = setup
    eng = Engine(cfg, params, ServeConfig(max_batch=1, max_context=512))
    rng = np.random.default_rng(5)
    for i in range(2):
        eng.submit(Request(i, rng.integers(0, cfg.vocab_size, 80)
                           .astype(np.int32), max_new_tokens=50))
    with pytest.raises(EngineStalled) as ei:
        eng.run_until_done(max_ticks=3)
    d = ei.value.diagnostics
    assert d["tick"] == 3 and d["waiting"] + d["running"] >= 1
    assert "rung" in d and "pool" in d and "last_snapshot" in d
    assert set(d["sequences"]) <= {0, 1}
    assert ei.value.retired == []        # nothing finished in 3 ticks
    # diagnostics() is also callable on a healthy engine
    eng2 = Engine(cfg, params, ServeConfig(max_batch=1, max_context=512))
    assert eng2.diagnostics()["running"] == 0


def test_host_io_fault_types():
    """HostIOError must be absorbable by every PoolExhausted catch site
    and carry the tier_bound short-circuit."""
    from repro.cache.paged_kv import PoolExhausted

    assert issubclass(HostIOError, PoolExhausted)
    assert HostIOError.tier_bound is True
    assert issubclass(InjectedDeviceError, RuntimeError)


# -- degradation ladder (pallas rungs; interpret mode -> slow lane) ----------


@pytest.mark.slow
def test_ladder_degrades_and_repromotes(setup):
    """Pallas staged backend: an injected device error degrades the tick
    to the reference rung (instead of charging the failure budget), the
    rung sticks, and ``repromote_after`` clean ticks promote back up."""
    cfg, _ = setup
    cfg2 = dataclasses.replace(
        cfg, sparse=dataclasses.replace(cfg.sparse, backend="pallas"),
    )
    model = Transformer(cfg2)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(cfg2, params, ServeConfig(
        max_batch=1, max_context=320, temperature=0.0,
    ))
    assert [name for name, _ in eng._ladder] == ["staged", "reference"]
    inj = FaultInjector([FaultSpec("decode", tick=2, count=1)])
    eng.set_fault_injector(inj)
    rng = np.random.default_rng(9)
    req = Request(0, rng.integers(0, cfg2.vocab_size, 160).astype(np.int32),
                  max_new_tokens=14)
    eng.submit(req)
    eng.run_until_done(max_ticks=100)
    assert req.done and req.status == "ok"
    snap = eng.metrics.snapshot()
    assert snap["degradations_by_rung"] == {"reference": 1}
    assert snap["retries"] == 0, "the ladder absorbed the fault"
    assert snap["repromotions"] == 1 and eng._rung == 0


def test_compile_failure_raises_instead_of_degrading(setup):
    """A step that fails to COMPILE (here: a simulated compile-time HBM
    exhaustion, the error type a real run-time device fault also has) is a
    program error: it propagates as StepCompileError instead of being
    charged to the ladder or the failure budget."""
    from jax.errors import JaxRuntimeError

    from repro.serving.engine import StepCompileError

    cfg, params = setup

    class _Refused:
        def lower(self, *args):
            raise JaxRuntimeError("RESOURCE_EXHAUSTED: simulated at compile")

    eng = Engine(cfg, params, ServeConfig(max_batch=1, max_context=256))
    eng.steps["decode"]._jit = _Refused()
    eng.submit(Request(0, np.arange(40, dtype=np.int32), max_new_tokens=4))
    with pytest.raises(StepCompileError, match="'decode' failed to compile"):
        eng.run_until_done(max_ticks=20)
    snap = eng.metrics.snapshot()
    assert snap["degradations"] == 0 and snap["retries"] == 0
    assert snap["requests_failed"] == 0
