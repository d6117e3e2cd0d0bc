#!/usr/bin/env python3
"""Run a cell traced and read the program's own spans from the capture.

    python3 benchmarks/chip/tools/program_spans.py --workload <cell> \\
        --seed <n> --seconds 51 [--trace-seconds 51] [--gc]

One run of ``run.run_cell`` with ``--trace 1``, whose line is printed with
one more key, ``program_spans``.  The harness's trace reduction is wrapped
so that the engine's spans (``repro.obs.trace.SPAN_NAMES``) and chip 0's
device events are read from the same ``.xplane.pb`` before it is deleted,
in one pass beside the harness's own.  ``program_spans`` holds the host's
own time per tick (``host_tick_ms``), the attended shares of decode and
sparse prefill from the engine's counters, chip 0's idle time by the
innermost program span over it, and the anatomy of the window's slowest
tick (the one longest beyond the median of its kind, prefill or
decode-only) beside a typical one of that kind, with what the chip ran
meanwhile.  Two numbers need no program span, so they compare a checkout
that has none: the median decode-only tick from the harness's own
annotations, and each jitted step's device time per call.

``--trace-seconds`` overrides the traffic's traced stretch (the whole
window, to catch a stall); ``--gc`` marks each garbage collection as a
``tool.gc`` span.  To look at several seeds, run the tool once per seed.
"""
from __future__ import annotations

import argparse
import gc
import json
import statistics
import sys
from pathlib import Path
from typing import List, Tuple

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

from spans import Span  # noqa: E402


def program_span_names() -> Tuple[str, ...]:
    """The engine's span names, or none where the program has no list."""
    try:
        from repro.obs.trace import SPAN_NAMES
    except ImportError:
        return ()
    return tuple(SPAN_NAMES)


def read(path, names) -> Tuple[List[Span], List[Tuple[str, str, float, float]]]:
    """One pass over ``path``: the host events whose name is in ``names``
    (by start), and chip 0's ``(line, name, start, end)`` events of its
    ``XLA Ops`` and ``XLA Modules`` lines.  Times in seconds."""
    import jax

    keep = set(names)
    pd = jax.profiler.ProfileData.from_file(str(path))
    chips = sorted(p.name for p in pd.planes
                   if p.name.startswith("/device:TPU:") and "Core" not in p.name)
    sp: List[Span] = []
    dev: List[Tuple[str, str, float, float]] = []
    for plane in pd.planes:
        host = plane.name.startswith("/host:")
        if not (host and keep) and not (chips and plane.name == chips[0]):
            continue
        for line in plane.lines:
            if not host and line.name not in ("XLA Ops", "XLA Modules"):
                continue
            for ev in line.events:
                if host and ev.name not in keep:
                    continue
                s = ev.start_ns * 1e-9
                e = s + ev.duration_ns * 1e-9
                if host:
                    sp.append(Span(ev.name, s, e, dict(ev.stats)))
                else:
                    dev.append((line.name, ev.name, s, e))
    sp.sort(key=lambda x: x.start)
    return sp, dev


def _mark_collections():
    """A profiler annotation around every garbage collection."""
    from jax.profiler import TraceAnnotation

    open_ = []

    def cb(phase, info):
        if phase == "start":
            ann = TraceAnnotation("tool.gc", generation=info["generation"])
            ann.__enter__()
            open_.append(ann)
        elif open_:
            open_.pop().__exit__(None, None, None)

    gc.callbacks.append(cb)


def one(workload: str, seed: int, seconds: float, trace_seconds: float,
        mark_gc: bool) -> dict:
    import run
    import spans
    import tracing
    from model import model_config, sparse_layers

    cell = run.load_cell(ROOT, workload)
    if trace_seconds:
        cell.traffic["trace_seconds"] = trace_seconds
    cfg = model_config(cell.config)
    names = program_span_names() + (("tool.gc",) if mark_gc else ())
    got: dict = {}
    reduce = tracing.read_xplane

    def read_xplane(path, window_ns=None):
        tr = reduce(path, window_ns)
        sp, dev = read(path, names)
        win = tr.window
        ticks = spans.inside(sp, win, "engine.tick")
        chunks = [(s, e) for n, s, e in tr.host if n == "prefill_chunk"]
        decode_only = [e - s for n, s, e in tr.host if n == "tick" and not any(
            s <= a and b <= e for a, b in chunks)]
        if decode_only:
            got["decode_only_tick_ms"] = 1e3 * statistics.median(decode_only)
        for step in ("decode_step", "prefill_chunk"):
            t, n = tr.time_matching(step, modules=True)
            if n:
                got[f"{step}_program_ms"] = 1e3 * t / n
        got.update(
            host_tick_ms=spans.host_tick_ms(sp, win),
            decode_attended_fraction=spans.decode_attended_fraction(
                sp, win, cfg.sparse.page_size, cfg.n_kv_heads,
                len(sparse_layers(cfg))),
            prefill_attended_fraction=spans.prefill_attended_fraction(sp, win),
            idle_by_span=spans.idle_by_span(tr, sp),
            ticks=len(ticks),
            spans_in_window=len(spans.inside(sp, win)),
        )
        if ticks:
            def kind(t):
                return "prefill" if t.stats.get("prefill_tokens") else "decode"

            med = {k: statistics.median(t.dur for t in ticks if kind(t) == k)
                   for k in {kind(t) for t in ticks}}
            worst = max(ticks, key=lambda t: t.dur - med[kind(t)])
            usual = min((t for t in ticks if kind(t) == kind(worst)),
                        key=lambda t: abs(t.dur - med[kind(t)]))
            got["slowest_tick"] = dict(
                spans.tick_anatomy(sp, worst), kind=kind(worst),
                excess_s=worst.dur - med[kind(worst)],
                device=spans.device_between(dev, worst.start, worst.end))
            got["typical_tick"] = dict(
                spans.tick_anatomy(sp, usual),
                device=spans.device_between(dev, usual.start, usual.end))
            got["tick_median_s"] = med
        if mark_gc:
            coll = spans.inside(sp, win, "tool.gc")
            got["gc"] = {"count": len(coll),
                         "seconds": sum(c.dur for c in coll),
                         "longest": max((c.dur for c in coll), default=0.0)}
        return tr

    tracing.read_xplane = read_xplane
    if mark_gc:
        _mark_collections()
    line = run.run_cell(cell, seed, seconds, True)
    line["program_spans"] = got
    return line


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-seconds", type=float, default=0.0)
    ap.add_argument("--gc", action="store_true")
    args = ap.parse_args()
    line = one(args.workload, args.seed, args.seconds, args.trace_seconds,
               args.gc)
    print(json.dumps(line))


if __name__ == "__main__":
    main()
