"""Serving scenario suite: mixed-traffic patterns through the async
front-end, gated in CI.

Each scenario drives :class:`repro.serving.AsyncFrontend` over a real
engine with a traffic pattern the continuous-batching stack must survive:

- ``poisson_burst``     — bursty Poisson arrivals of mixed SLO classes
- ``longtail_mix``      — long batch-class prompts mixed with interactive
                          chat traffic (chunked prefill must keep chat
                          TTFT low while the long prompts stream in)
- ``preemption_storm``  — an oversubscribed page pool forcing repeated
                          deadline-aware preemption mid-decode
- ``prefix_churn``      — adversarial interleaving of shared-prefix
                          groups churning the radix cache under a small
                          pool

Every scenario ALSO runs the identical request set through the synchronous
``run_until_done`` drain on a twin engine and asserts per-token identity
(``token_mismatches == 0``) — the async path must be invisible in the
output.  Latency is measured on a **virtual tick clock** (1 unit per
engine tick), so the per-class p99 TTFT/TPOT numbers are deterministic
scheduling properties, not wall-clock noise, and the committed floors in
``check_regression.py`` can gate tightly.

    PYTHONPATH=src python benchmarks/scenarios.py [--trace OUT.JSON]

Writes ``BENCH_scenarios.json`` at the repo root (provenance-stamped).
"""
from __future__ import annotations

import argparse
import asyncio
import json
import pathlib
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent.parent


@dataclass
class Arrival:
    """One request's template: built fresh for the async run and its
    synchronous token-identity twin."""

    tick: int
    rid: int
    prompt: np.ndarray
    new_tokens: int
    slo_class: str = "interactive"
    deadline_s: Optional[float] = None


@dataclass
class Scenario:
    name: str
    serve_kw: Dict
    arrivals: List[Arrival]
    max_ticks: int = 4000
    #: structural expectations asserted after the run (e.g. the storm
    #: scenario must actually preempt).
    expect: Dict[str, int] = field(default_factory=dict)


def _mkreq(a: Arrival):
    from repro.serving import Request

    return Request(a.rid, a.prompt.copy(), max_new_tokens=a.new_tokens,
                   slo_class=a.slo_class, deadline_s=a.deadline_s)


def _tick_engine(cfg, params, serve_kw, trace=None):
    """Engine on a virtual tick clock: the metrics clock reads the engine's
    own tick counter, so TTFT/TPOT/deadlines are measured in ticks."""
    from repro.config import ServeConfig
    from repro.serving import Engine

    state = {}
    eng = Engine(
        cfg, params, ServeConfig(**serve_kw),
        clock=lambda: float(state["eng"].metrics.ticks) if state else 0.0,
        trace=trace, telemetry=trace is not None,
    )
    state["eng"] = eng
    return eng


async def _drive(frontend, arrivals: List[Arrival]):
    """Submit each arrival once the engine reaches its tick; when the
    engine idles before the next arrival, time fast-forwards (nothing else
    advances the tick clock).  -> req_id -> streamed tokens."""
    pending: Dict[int, List[Arrival]] = {}
    for a in arrivals:
        pending.setdefault(a.tick, []).append(a)
    streams = {}
    task = asyncio.create_task(frontend.run())
    while pending:
        t = min(pending)
        if frontend.ticks >= t or not frontend.engine.scheduler.has_work:
            for a in pending.pop(t):
                streams[a.rid] = frontend.submit(_mkreq(a))
        await asyncio.sleep(0)
    await frontend.drain()
    frontend.shutdown()
    await task
    return {rid: await s.collect() for rid, s in streams.items()}


def run_scenario(sc: Scenario, cfg, params, trace=None) -> Dict:
    from repro.serving import AsyncFrontend

    # -- async continuous-batching run ------------------------------------
    eng = _tick_engine(cfg, params, sc.serve_kw, trace=trace)
    fe = AsyncFrontend(eng, max_ticks=sc.max_ticks)
    streamed = asyncio.run(_drive(fe, sc.arrivals))

    # -- synchronous drain twin (token-identity reference) ----------------
    eng_sync = _tick_engine(cfg, params, sc.serve_kw)
    sync_reqs = [_mkreq(a) for a in sc.arrivals]
    for r in sync_reqs:
        eng_sync.submit(r)
    eng_sync.run_until_done(max_ticks=sc.max_ticks)
    sync_out = {r.req_id: list(r.output) for r in sync_reqs}

    token_mismatches = sum(
        1 for rid, toks in sync_out.items() if streamed.get(rid) != toks
    )
    finished = {r.req_id for r in eng.finished if r.status == "ok"}
    lost = len(sc.arrivals) - len(
        {r.req_id for r in eng.finished}
    )

    snap = eng.metrics.snapshot()
    for key, floor in sc.expect.items():
        assert snap[key] >= floor, (
            f"{sc.name}: expected {key} >= {floor}, got {snap[key]} — the "
            "scenario no longer exercises what it claims to"
        )
    per_class = {
        cls: {
            "finished": int(m["finished"]),
            "ttft_p99_ticks": m["ttft_p99"],
            "tpot_p99_ticks": m["tpot_p99"],
            "deadline_miss_rate": m["deadline_miss_rate"],
        }
        for cls, m in snap["per_class"].items()
    }
    return {
        "requests": len(sc.arrivals),
        "finished_ok": len(finished),
        "requests_lost": lost,
        "token_mismatches": token_mismatches,
        "ticks": int(snap["ticks"]),
        "preemptions": int(snap["preemptions"]),
        "prefix_deferrals": int(snap["prefix_deferrals"]),
        "prefix_hit_rate": round(snap["prefix_hit_rate"], 3),
        "deadline_miss_rate": snap["deadline_miss_rate"],
        "per_class": per_class,
    }


# -- scenario definitions -----------------------------------------------------
#
# SLO targets are in TICKS under the virtual clock (ServeConfig documents
# the clock-unit semantics).  Sizes are CI-scale: interpret-mode engines
# are slow, and the numbers these floors gate are deterministic anyway.

_BASE = dict(
    max_batch=4, max_context=512,
    prefill_tokens_per_tick=256, prefill_chunk=128,
    interactive_ttft_slo=60.0, batch_ttft_slo=600.0,
)


def _prompt(rng, cfg, n):
    return rng.integers(0, cfg.vocab_size, n).astype(np.int32)


def poisson_burst(cfg) -> Scenario:
    """Bursty Poisson arrivals, mixed interactive / batch / deadline."""
    rng = np.random.default_rng(11)
    gaps = rng.exponential(2.0, 10)
    ticks = np.floor(np.cumsum(gaps)).astype(int)
    classes = ["interactive", "interactive", "batch", "interactive",
               "deadline", "interactive", "batch", "interactive",
               "deadline", "interactive"]
    arrivals = [
        Arrival(
            tick=int(t), rid=i,
            prompt=_prompt(rng, cfg, int(rng.integers(48, 96))),
            new_tokens=6, slo_class=c,
            deadline_s=300.0 if c == "deadline" else None,
        )
        for i, (t, c) in enumerate(zip(ticks, classes))
    ]
    return Scenario("poisson_burst", dict(_BASE), arrivals)


def longtail_mix(cfg) -> Scenario:
    """Two long batch-class prompts streaming in via chunked prefill while
    short interactive chat traffic arrives on top: EDF admission must keep
    chat TTFT low instead of head-of-line blocking behind the long tail."""
    rng = np.random.default_rng(12)
    arrivals = [
        Arrival(0, 0, _prompt(rng, cfg, 400), 4, slo_class="batch"),
        Arrival(1, 1, _prompt(rng, cfg, 384), 4, slo_class="batch"),
    ]
    for i in range(6):
        arrivals.append(Arrival(
            tick=2 + 2 * i, rid=2 + i,
            prompt=_prompt(rng, cfg, 48), new_tokens=6,
            slo_class="interactive",
        ))
    kw = dict(_BASE, prefill_tokens_per_tick=128)
    return Scenario("longtail_mix", kw, arrivals)


def preemption_storm(cfg) -> Scenario:
    """Oversubscribed pool: decode reservations repeatedly exhaust pages,
    forcing deadline-aware preemption; every request must still finish
    with the sync path's exact tokens."""
    rng = np.random.default_rng(13)
    arrivals = [
        Arrival(
            tick=(0 if i < 4 else 2), rid=i,
            prompt=_prompt(rng, cfg, 64), new_tokens=12,
            slo_class="batch" if i % 3 == 0 else "interactive",
        )
        for i in range(6)
    ]
    kw = dict(_BASE, pool_pages=18)
    return Scenario(
        "preemption_storm", kw, arrivals, expect={"preemptions": 1},
    )


def prefix_churn(cfg) -> Scenario:
    """Adversarial prefix-cache churn: three shared-prefix groups arrive
    round-robin interleaved under a pool too small to keep every group's
    prefix cached — eviction and admission grouping fight it out."""
    rng = np.random.default_rng(14)
    prefixes = [_prompt(rng, cfg, 128) for _ in range(3)]
    arrivals = []
    for i in range(9):
        g = i % 3
        prompt = np.concatenate([prefixes[g], _prompt(rng, cfg, 32)])
        arrivals.append(Arrival(
            tick=i, rid=i, prompt=prompt, new_tokens=4,
            slo_class="interactive",
        ))
    kw = dict(_BASE, pool_pages=48, prefix_wait_ticks=8)
    return Scenario("prefix_churn", kw, arrivals)


SCENARIOS = [poisson_burst, longtail_mix, preemption_storm, prefix_churn]


def main():
    from repro.configs import get_config, smoke_variant
    from repro.models import Transformer
    from repro.obs import TraceRecorder

    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", default=None, metavar="OUT.JSON",
                    help="export a Perfetto timeline of the first scenario")
    ap.add_argument("--out", default=str(ROOT / "BENCH_scenarios.json"))
    args = ap.parse_args()

    cfg = smoke_variant(get_config("llama3.2-3b"))
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))

    trace = TraceRecorder() if args.trace else None
    results = {}
    for i, make in enumerate(SCENARIOS):
        sc = make(cfg)
        res = run_scenario(
            sc, cfg, params, trace=trace if i == 0 else None
        )
        results[sc.name] = res
        print(f"{sc.name}: finished_ok={res['finished_ok']}/"
              f"{res['requests']} lost={res['requests_lost']} "
              f"mismatches={res['token_mismatches']} "
              f"preempt={res['preemptions']} ticks={res['ticks']}")
        for cls, m in res["per_class"].items():
            print(f"  {cls}: ttft_p99={m['ttft_p99_ticks']:.0f}t "
                  f"tpot_p99={m['tpot_p99_ticks']:.2f}t "
                  f"miss_rate={m['deadline_miss_rate']:.2f}")
    if trace is not None:
        trace.dump(args.trace)
        print(f"trace: {len(trace)} events -> {args.trace}")

    from provenance import provenance

    out = {
        "name": "serving_scenarios",
        "scenarios": results,
        "provenance": provenance({
            "scenarios": [make.__name__ for make in SCENARIOS],
            "clock": "virtual-tick",
        }),
    }
    path = pathlib.Path(args.out)
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
