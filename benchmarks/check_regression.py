"""CI bench-gate: fail when a committed performance floor regresses.

Reads the benchmark artifacts written by ``benchmarks/decode_latency.py``
(``BENCH_decode.json``), ``benchmarks/prefill_latency.py``
(``BENCH_prefill.json``), ``benchmarks/memory_bench.py``
(``BENCH_memory.json``), ``benchmarks/serving_bench.py``
(``BENCH_serving.json``), ``benchmarks/chaos_bench.py``
(``BENCH_chaos.json``), ``benchmarks/scenarios.py``
(``BENCH_scenarios.json``) and the contract-verifier report written by
``python -m repro.analysis.contracts`` (``BENCH_analysis.json``) and checks
them against the floors below.

Floors are deliberately conservative where wall clock is involved
(interpret mode on shared CI runners is noisy), and exact where the metric
is deterministic: structural counts, token identity, and everything the
scenario suite measures on its virtual tick clock.

A floor whose key is MISSING from the measured JSON is a hard failure —
a renamed metric must break the gate loudly, not skip it silently.  On any
failure the full floors-vs-measured table is printed.

Usage: python benchmarks/check_regression.py [--decode PATH] [--scenarios PATH] ...
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
from typing import Any, List, Tuple

ROOT = pathlib.Path(__file__).resolve().parent.parent

_MISSING = object()

#: committed floors — raise them deliberately, never lower them casually.
#: Each entry: (check name, artifact key, dotted path into that artifact's
#: JSON, op, floor).  ``op`` is ">=" for floors and "<=" for ceilings.
CHECKS: List[Tuple[str, str, str, str, float]] = [
    # fused single-launch decode must stay meaningfully faster than the
    # staged three-kernel pipeline (measured ~300x in interpret mode).
    ("decode.fused_speedup", "decode", "fused_speedup", ">=", 3.0),
    # the fused path must remain a single launch per layer.
    ("decode.launches_per_layer_fused", "decode",
     "launches_per_layer_fused", "<=", 1),
    # sparse prefill must skip a real fraction of causal KV blocks at the
    # largest benchmarked context (deterministic, hardware-independent).
    ("prefill.blocks_attended_frac", "prefill",
     "blocks_attended_frac", "<=", 0.75),
    # and must stay meaningfully faster than the dense flash kernel it
    # replaces (measured 2-4x in interpret mode; floor leaves >3x margin
    # for runner noise — the tight gate is the deterministic block frac).
    ("prefill.speedup", "prefill", "speedup", ">=", 1.2),
    # hierarchical KV memory: the tiered pool must sustain at least 2x the
    # concurrent sequences of a flat all-HBM pool at the same HBM budget
    # (the subsystem's whole point; deterministic given the workload).
    ("memory.concurrency_gain", "memory", "concurrency_gain", ">=", 2.0),
    # overcommit must exercise real HBM<->host migration, not degenerate
    # into an all-resident run.
    ("memory.demotions", "memory", "demotions", ">=", 1),
    # if the selection drifts into the host tier, the margin-rank
    # prefetcher must stage most of them ahead of time (1.0 when no
    # demand lookup happened at all — nothing drifted, nothing missed).
    ("memory.prefetch_hit_rate", "memory", "prefetch_hit_rate", ">=", 0.5),
    # observability must stay near-free: traced serving throughput (the
    # recorder plus the opt-in telemetry, as `serve --trace` runs) within
    # 5% of untraced on the same engine (noise-hardened estimator;
    # measured -0.3% and -0.2% on an otherwise idle CPU).
    ("serving.trace_overhead", "serving", "trace_overhead_frac", "<=", 0.05),
    # resilience: the seeded fault storm must never lose a request and
    # every within-budget request's token stream must match the fault-free
    # run byte-for-byte.  Both deterministic: exact-zero gates.
    ("chaos.requests_lost", "chaos", "requests_lost", "<=", 0),
    ("chaos.token_mismatches", "chaos", "token_mismatches", "<=", 0),
    # the storm must actually exercise the failure domains — a silently
    # disarmed injector would green-light a broken recovery path.
    ("chaos.faults_injected", "chaos",
     "faults_injected.total_fired", ">=", 5),
    # -- scenario suite (benchmarks/scenarios.py): continuous-batching
    # async serving under mixed traffic.  Everything below is measured on
    # the virtual tick clock and fully deterministic, so the latency
    # ceilings sit close to the committed BENCH_scenarios.json values
    # (roughly +50% headroom for benign scheduling drift) and the
    # identity/loss gates are exact zeros.
    ("scenarios.poisson_burst.requests_lost", "scenarios",
     "scenarios.poisson_burst.requests_lost", "<=", 0),
    ("scenarios.poisson_burst.token_mismatches", "scenarios",
     "scenarios.poisson_burst.token_mismatches", "<=", 0),
    ("scenarios.poisson_burst.interactive_ttft_p99", "scenarios",
     "scenarios.poisson_burst.per_class.interactive.ttft_p99_ticks",
     "<=", 30),
    ("scenarios.poisson_burst.interactive_tpot_p99", "scenarios",
     "scenarios.poisson_burst.per_class.interactive.tpot_p99_ticks",
     "<=", 8),
    ("scenarios.poisson_burst.deadline_miss_rate", "scenarios",
     "scenarios.poisson_burst.deadline_miss_rate", "<=", 0.0),
    ("scenarios.longtail_mix.requests_lost", "scenarios",
     "scenarios.longtail_mix.requests_lost", "<=", 0),
    ("scenarios.longtail_mix.token_mismatches", "scenarios",
     "scenarios.longtail_mix.token_mismatches", "<=", 0),
    # EDF admission must keep chat TTFT low while 100k-style long prompts
    # stream through chunked prefill.
    ("scenarios.longtail_mix.interactive_ttft_p99", "scenarios",
     "scenarios.longtail_mix.per_class.interactive.ttft_p99_ticks",
     "<=", 30),
    ("scenarios.longtail_mix.interactive_tpot_p99", "scenarios",
     "scenarios.longtail_mix.per_class.interactive.tpot_p99_ticks",
     "<=", 8),
    ("scenarios.longtail_mix.deadline_miss_rate", "scenarios",
     "scenarios.longtail_mix.deadline_miss_rate", "<=", 0.0),
    ("scenarios.preemption_storm.requests_lost", "scenarios",
     "scenarios.preemption_storm.requests_lost", "<=", 0),
    ("scenarios.preemption_storm.token_mismatches", "scenarios",
     "scenarios.preemption_storm.token_mismatches", "<=", 0),
    # the storm must actually preempt — a quietly right-sized pool would
    # green-light a broken preemption path.
    ("scenarios.preemption_storm.preemptions", "scenarios",
     "scenarios.preemption_storm.preemptions", ">=", 1),
    ("scenarios.preemption_storm.deadline_miss_rate", "scenarios",
     "scenarios.preemption_storm.deadline_miss_rate", "<=", 0.5),
    ("scenarios.prefix_churn.requests_lost", "scenarios",
     "scenarios.prefix_churn.requests_lost", "<=", 0),
    ("scenarios.prefix_churn.token_mismatches", "scenarios",
     "scenarios.prefix_churn.token_mismatches", "<=", 0),
    # churn or not, the radix cache must still convert a real fraction of
    # the shared-prefix traffic into hits.
    ("scenarios.prefix_churn.prefix_hit_rate", "scenarios",
     "scenarios.prefix_churn.prefix_hit_rate", ">=", 0.3),
    ("scenarios.prefix_churn.interactive_ttft_p99", "scenarios",
     "scenarios.prefix_churn.per_class.interactive.ttft_p99_ticks",
     "<=", 30),
    # -- static-analysis lane (repro.analysis.contracts): the abstract
    # kernel-contract verifier must keep covering the full backend registry
    # x at least two zoo configs — coverage can't silently shrink — and the
    # committed report must be violation-free.
    ("analysis.backends_covered", "analysis", "backends_covered", ">=", 3),
    ("analysis.configs_covered", "analysis", "configs_covered", ">=", 2),
    ("analysis.n_failures", "analysis", "n_failures", "<=", 0),
]


def _load(path: pathlib.Path) -> dict:
    if not path.exists():
        sys.exit(f"bench-gate: missing artifact {path} — run the benchmark first")
    with open(path) as f:
        return json.load(f)


def _lookup(blob: Any, dotted: str) -> Any:
    """Walk ``a.b.c`` through nested dicts; -> _MISSING on any absent key
    (the gate treats that as a hard failure, never a silent skip)."""
    cur = blob
    for part in dotted.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return _MISSING
        cur = cur[part]
    return cur


def _fmt(value: Any) -> str:
    if value is _MISSING:
        return "MISSING"
    if isinstance(value, float):
        return f"{value:.4g}"
    return str(value)


def _print_table(rows) -> None:
    """Floors-vs-measured table, printed in full on any failure."""
    headers = ("check", "measured", "op", "floor", "status")
    cols = [
        [h] + [str(r[i]) for r in rows] for i, h in enumerate(headers)
    ]
    widths = [max(len(x) for x in col) for col in cols]
    line = "  ".join(h.ljust(w) for h, w in zip(headers, widths))
    print(line)
    print("  ".join("-" * w for w in widths))
    for r in rows:
        print("  ".join(str(x).ljust(w) for x, w in zip(r, widths)))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--decode", default=str(ROOT / "BENCH_decode.json"))
    ap.add_argument("--prefill", default=str(ROOT / "BENCH_prefill.json"))
    ap.add_argument("--memory", default=str(ROOT / "BENCH_memory.json"))
    ap.add_argument("--serving", default=str(ROOT / "BENCH_serving.json"))
    ap.add_argument("--chaos", default=str(ROOT / "BENCH_chaos.json"))
    ap.add_argument("--scenarios",
                    default=str(ROOT / "BENCH_scenarios.json"))
    ap.add_argument("--analysis",
                    default=str(ROOT / "BENCH_analysis.json"))
    args = ap.parse_args()

    artifacts = {
        name: _load(pathlib.Path(getattr(args, name)))
        for name in ("decode", "prefill", "memory", "serving",
                     "chaos", "scenarios", "analysis")
    }

    rows = []
    failed = []
    for name, artifact, dotted, op, floor in CHECKS:
        value = _lookup(artifacts[artifact], dotted)
        if value is _MISSING:
            ok = False       # a renamed metric must fail LOUDLY
        elif op == ">=":
            ok = value >= floor
        else:
            ok = value <= floor
        status = "ok" if ok else "FAIL"
        rows.append((name, _fmt(value), op, _fmt(floor), status))
        print(f"{'ok  ' if ok else 'FAIL'} {name} = {_fmt(value)} "
              f"(must be {op} {floor})")
        if not ok:
            failed.append(
                f"{name} (MISSING from artifact)" if value is _MISSING
                else name
            )
    if failed:
        print("\nbench-gate failure — floors vs measured:")
        _print_table(rows)
        sys.exit(f"bench-gate: regression in {', '.join(failed)}")
    print("bench-gate: all floors hold")


if __name__ == "__main__":
    main()
