"""Serving-scheduler benchmark: Poisson arrivals over shared-prefix
(system-prompt-style) traffic through the full engine.

Measures what the scheduler subsystem is for: TTFT/TPOT percentiles under
load, prefix-cache hit rate (requests within a group share a page-aligned
prompt prefix, so only the first in each group pays for it), chunked
prefill interleaving, and preemption behaviour when the page pool is
oversubscribed.  Ends with a page-leak audit (``owner_map``/refcount
accounting must be clean at drain).

Also measures the tracing-overhead fraction (traced vs untraced
throughput on a deterministic all-requests-upfront workload, steady-state
— each engine is warmed on an identical batch first so jit compile time
cancels out) and writes ``BENCH_serving.json`` at the repo root with the
full config + git SHA for the CI bench-gate's tracing-overhead ceiling.

    PYTHONPATH=src python benchmarks/serving_bench.py
"""
from __future__ import annotations

import gc
import json
import pathlib
import time

import jax
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent.parent


def run(
    n_requests=12,
    rate_hz=2.0,
    prefix_groups=3,
    prefix_len=128,
    suffix_max=128,
    new_tokens=8,
    max_batch=4,
    max_context=512,
    pool_frac=0.75,
    seed=0,
):
    from repro.config import ServeConfig
    from repro.configs import get_config, smoke_variant
    from repro.models import Transformer
    from repro.serving import Engine, Request

    cfg = smoke_variant(get_config("llama3.2-3b"))
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))
    full_pool = max_batch * (max_context // 16)
    eng = Engine(cfg, params, ServeConfig(
        max_batch=max_batch,
        max_context=max_context,
        # oversubscribed pool: admission must lean on prefix sharing /
        # cache eviction, and decode bursts can trigger preemption.
        pool_pages=int(full_pool * pool_frac),
        prefill_tokens_per_tick=256,
        prefill_chunk=128,
    ))

    rng = np.random.default_rng(seed)
    prefixes = [
        rng.integers(0, cfg.vocab_size, prefix_len).astype(np.int32)
        for _ in range(prefix_groups)
    ]
    requests = []
    for rid in range(n_requests):
        suffix = rng.integers(
            0, cfg.vocab_size, int(rng.integers(16, suffix_max))
        ).astype(np.int32)
        prompt = np.concatenate([prefixes[rid % prefix_groups], suffix])
        requests.append(Request(rid, prompt, max_new_tokens=new_tokens))
    # Poisson process: exponential inter-arrival gaps at ``rate_hz``.
    arrivals = np.cumsum(rng.exponential(1.0 / rate_hz, n_requests))

    t0 = time.monotonic()
    pending = list(zip(arrivals, requests))
    while pending or eng.scheduler.has_work:
        now = time.monotonic() - t0
        while pending and pending[0][0] <= now:
            eng.submit(pending.pop(0)[1])
        if eng.scheduler.has_work:
            eng.step()
        elif pending:
            time.sleep(min(0.01, pending[0][0] - now))
    dt = time.monotonic() - t0

    assert all(r.done and len(r.output) == new_tokens for r in requests), (
        "every request must complete"
    )
    # owner_map clean at drain: only prefix-cache pins survive, and every
    # pool pin must be accounted for by a live radix-cache node.
    leaks = eng.pool.assert_consistent(known_pins=eng.prefix_cache.pages())
    assert not leaks, f"leaked pages at drain: {leaks}"
    owner = eng.pool.owner_map()
    assert ((owner == -1) | (owner == -2)).all(), "stale sequence owns pages"
    assert eng.pool.used_pages == eng.prefix_cache.n_pages

    snap = eng.metrics.snapshot()
    shared_tokens = (n_requests - prefix_groups) * (prefix_len // 16) * 16
    derived = {
        "tokens_per_s": round(snap["decode_tokens"] / dt, 1),
        "ttft_p50_ms": round(snap.get("ttft_p50", 0.0) * 1e3, 1),
        "ttft_p95_ms": round(snap.get("ttft_p95", 0.0) * 1e3, 1),
        "tpot_mean_ms": round(snap.get("tpot_mean", 0.0) * 1e3, 2),
        "queue_mean_ms": round(snap.get("queue_time_mean", 0.0) * 1e3, 1),
        "prefix_hit_rate": round(snap["prefix_hit_rate"], 3),
        "prefix_hit_tokens": int(snap["prefix_hit_tokens"]),
        "prefix_hit_ceiling": shared_tokens,
        "prefill_computed": int(snap["prefill_tokens_computed"]),
        "preemptions": int(snap["preemptions"]),
        "ticks": int(snap["ticks"]),
        "peak_pool_pages": int(eng.pool.peak_used_pages),
        "pool_pages": int(eng.pool.total_pages),
    }
    return {
        "name": "serving_scheduler_poisson",
        "us_per_call": dt * 1e6,
        "derived": derived,
        "config": {
            "n_requests": n_requests, "rate_hz": rate_hz,
            "prefix_groups": prefix_groups, "prefix_len": prefix_len,
            "suffix_max": suffix_max, "new_tokens": new_tokens,
            "max_batch": max_batch, "max_context": max_context,
            "pool_frac": pool_frac, "seed": seed,
        },
    }


def trace_overhead(
    n_requests=8,
    prefix_groups=2,
    prefix_len=128,
    suffix_max=128,
    # 64 decode steps/request: short runs are scheduler-jitter-dominated
    # and the overhead fraction won't resolve below the CI ceiling.
    new_tokens=64,
    max_batch=4,
    max_context=512,
    seed=0,
    reps=10,
):
    """Traced-vs-untraced serving throughput on a deterministic workload.

    All requests are submitted up front (no Poisson wall-clock dependence).
    ONE engine serves both modes via ``Engine.set_tracing`` — separate
    engine instances pick up persistent per-engine bias (allocation
    placement of their cache arrays) that no amount of repetition averages
    out.  Each mode first drains two identical warm-up batches (the first
    compiles that mode's cold-prefill path / seeds the prefix cache, the
    second compiles the prefix-hit shapes the measured batches run) so jit
    compile time is excluded.  The workload is deterministic, so every rep
    of one mode replays the *identical* tick sequence; the noise-robust
    floor estimate is the sum over tick positions of the per-position
    minimum across reps (machine-load jitter lands on different ticks in
    different reps and is filtered out, which a whole-run best-of-N cannot
    do).  The traced mode is what ``serve --trace`` runs: the recorder (the
    engine's spans, metadata and counter reads) plus the opt-in
    device-side telemetry and its readback, so the floor ratio is the
    per-tick cost of both.  -> dict with ``trace_overhead_frac``
    (traced slowdown; the CI ceiling is 5%).
    """
    from repro.config import ServeConfig
    from repro.configs import get_config, smoke_variant
    from repro.models import Transformer
    from repro.obs import TraceRecorder
    from repro.serving import Engine, Request

    cfg = smoke_variant(get_config("llama3.2-3b"))
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))
    serve = ServeConfig(
        max_batch=max_batch, max_context=max_context,
        prefill_tokens_per_tick=256, prefill_chunk=128,
    )

    def make_requests(base_rid):
        rng = np.random.default_rng(seed)
        prefixes = [
            rng.integers(0, cfg.vocab_size, prefix_len).astype(np.int32)
            for _ in range(prefix_groups)
        ]
        reqs = []
        for rid in range(n_requests):
            suffix = rng.integers(
                0, cfg.vocab_size, int(rng.integers(16, suffix_max))
            ).astype(np.int32)
            prompt = np.concatenate([prefixes[rid % prefix_groups], suffix])
            reqs.append(
                Request(base_rid + rid, prompt, max_new_tokens=new_tokens)
            )
        return reqs

    # ONE engine, two modes: warm each mode (traced last, so its recorder
    # state is live when the loop starts) with two batches — the first
    # compiles that mode's cold-prefill/decode variants and seeds the
    # prefix cache, the second compiles the prefix-HIT prefill shapes the
    # measured batches will actually run.
    recorder = TraceRecorder()
    eng = Engine(cfg, params, serve, trace=recorder)
    modes = {"untraced": None, "traced": recorder}
    for label, trace in modes.items():
        eng.set_tracing(trace, telemetry=trace is not None)
        for _ in range(2):
            warm = make_requests(0)
            for r in warm:
                eng.submit(r)
            eng.run_until_done()
    # per-tick timing, mode order alternating each rep (keeps machine-load
    # drift from landing entirely on one mode).  GC is paused for the timed
    # section (pyperf-style): collection pauses scale with the accumulated
    # trace-event objects and would otherwise bill the recorder for GC
    # time the serving path never sees per tick.
    tick_ns = {label: [] for label in modes}
    traces = {}
    gc.collect()
    gc.disable()
    try:
        for rep in range(reps):
            order = list(modes.items())
            if rep % 2:
                order.reverse()
            for label, trace in order:
                eng.set_tracing(trace, telemetry=trace is not None)
                measured = make_requests(n_requests)
                for r in measured:
                    eng.submit(r)
                durs = []
                while eng.scheduler.has_work:
                    t0 = time.perf_counter_ns()
                    eng.step()
                    durs.append(time.perf_counter_ns() - t0)
                assert all(
                    r.done and len(r.output) == new_tokens for r in measured
                )
                tick_ns[label].append(durs)
                if trace is not None:
                    traces["trace_events"] = len(trace)
    finally:
        gc.enable()
    toks = n_requests * new_tokens
    results = {}
    for label, rep_durs in tick_ns.items():
        # deterministic replay: tick position i is the same scheduler
        # decision in every rep, so min-across-reps per position is that
        # tick's noise-free cost and the sum is the idealized run time.
        n = min(len(d) for d in rep_durs)
        assert n == max(len(d) for d in rep_durs), "non-deterministic replay"
        floor = np.asarray(
            [d[:n] for d in rep_durs], dtype=np.int64
        ).min(axis=0).sum() / 1e9
        results[label] = {"wall_s": floor, "tokens_per_s": toks / floor}
    overhead = (
        results["traced"]["wall_s"] / results["untraced"]["wall_s"] - 1.0
    )
    return {
        "untraced_tokens_per_s": round(results["untraced"]["tokens_per_s"], 1),
        "traced_tokens_per_s": round(results["traced"]["tokens_per_s"], 1),
        "trace_overhead_frac": round(overhead, 4),
        **traces,
        "config": {
            "n_requests": n_requests, "prefix_groups": prefix_groups,
            "prefix_len": prefix_len, "suffix_max": suffix_max,
            "new_tokens": new_tokens, "max_batch": max_batch,
            "max_context": max_context, "seed": seed, "reps": reps,
        },
    }


if __name__ == "__main__":
    from provenance import provenance

    out = run()
    print(out["name"])
    for k, v in out["derived"].items():
        print(f"  {k}: {v}")
    ovh = trace_overhead()
    print("trace_overhead")
    for k in ("untraced_tokens_per_s", "traced_tokens_per_s",
              "trace_overhead_frac", "trace_events"):
        print(f"  {k}: {ovh.get(k)}")
    result = {
        "name": out["name"],
        "derived": out["derived"],
        "trace_overhead": {
            k: v for k, v in ovh.items() if k != "config"
        },
        "trace_overhead_frac": ovh["trace_overhead_frac"],
        "provenance": provenance(
            {"poisson": out["config"], "trace_overhead": ovh["config"]}
        ),
    }
    path = ROOT / "BENCH_serving.json"
    with open(path, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print(f"wrote {path}")
