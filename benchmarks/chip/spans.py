"""What the program's own spans in a profiler trace measure.

The engine marks its work with profiler annotations named in
``repro.obs.trace.SPAN_NAMES`` (``engine.tick``, ``engine.decode``,
``engine.wait``, ...), each carrying its metadata as event stats: the
tick's number and prompt tokens, a decode step's live tokens and the KV
pages its attention read, the sparse prefill blocks attended and their
causal candidates.  Everything here is arithmetic on those spans (and on
chip 0's device events), kept apart from the capture so that it can be
checked on synthetic spans; ``tools/program_spans.py`` reads them.

A program without these spans (an older checkout) gives an empty list,
and every function below then returns None.
"""
from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from tracing import DeviceTrace, gaps, label_gaps, union

Interval = Tuple[float, float]


@dataclass
class Span:
    """One host event; times in seconds on the trace's clock."""

    name: str
    start: float
    end: float
    stats: Dict[str, object] = field(default_factory=dict)

    @property
    def dur(self) -> float:
        return self.end - self.start


def inside(spans: Sequence[Span], window: Interval,
           name: Optional[str] = None) -> List[Span]:
    """The spans (of ``name``) that lie wholly inside ``window``."""
    lo, hi = window
    return [s for s in spans if (name is None or s.name == name)
            and s.start >= lo and s.end <= hi]


def host_tick_ms(spans: Sequence[Span], window: Interval) -> Optional[float]:
    """Mean over the window's ticks of the tick's time less its blocking
    device->host reads (``engine.wait``): the host's own time per tick."""
    ticks = inside(spans, window, "engine.tick")
    if not ticks:
        return None
    waits = inside(spans, window, "engine.wait")
    own = 0.0
    for t in ticks:
        own += t.dur - sum(w.dur for w in waits
                           if w.start >= t.start and w.end <= t.end)
    return 1e3 * own / len(ticks)


def decode_attended_fraction(spans: Sequence[Span], window: Interval,
                             page_size: int, n_kv_heads: int,
                             n_layers: int) -> Optional[float]:
    """KV pages decode attention read (summed over heads, layers, slots)
    times the page size, over the live tokens of the same steps times
    heads and layers; from the window's ``engine.decode`` metadata."""
    steps = [s.stats for s in inside(spans, window, "engine.decode")
             if "pages" in s.stats and "live_tokens" in s.stats]
    live = sum(int(s["live_tokens"]) for s in steps)
    if not live:
        return None
    pages = sum(int(s["pages"]) for s in steps)
    return 100.0 * pages * page_size / (live * n_kv_heads * n_layers)


def prefill_attended_fraction(spans: Sequence[Span],
                              window: Interval) -> Optional[float]:
    """Sparse prefill blocks attended over their causal candidates, summed
    over the window's ``engine.tick`` metadata (each tick carries the
    counts of the chunks read at its token reads)."""
    ticks = [s.stats for s in inside(spans, window, "engine.tick")]
    cands = sum(int(t.get("prefill_candidates", 0)) for t in ticks)
    if not cands:
        return None
    return 100.0 * sum(int(t.get("prefill_blocks", 0)) for t in ticks) / cands


def idle_by_span(trace: DeviceTrace, spans: Sequence[Span],
                 top: int = 10) -> List[list]:
    """Chip 0's idle time inside the window, summed by the innermost
    program span that covers each gap's middle (``other`` where none
    does), longest first."""
    if not trace.ops or not spans:
        return []
    lo, hi = trace.window
    found = gaps(union(trace.ops[0]), lo, hi)
    labels = label_gaps(found, [(s.name, s.start, s.end) for s in spans])
    idle: Dict[str, float] = defaultdict(float)
    for label, (a, b) in zip(labels, found):
        idle[label] += b - a
    return [[k, v] for k, v in sorted(idle.items(), key=lambda kv: -kv[1])[:top]]


def tick_anatomy(spans: Sequence[Span], tick: Span) -> dict:
    """What one tick spent its time in: its length, its metadata, the
    summed time of each kind of span inside it, and the time no inner
    span covers."""
    inner = [s for s in spans if s is not tick and s.start >= tick.start
             and s.end <= tick.end]
    by: Dict[str, float] = defaultdict(float)
    for s in inner:
        by[s.name] += s.dur
    covered = sum(e - s for s, e in union((s.start, s.end) for s in inner))
    return {"tick_s": tick.dur, "stats": dict(tick.stats),
            "spans_s": dict(sorted(by.items(), key=lambda kv: -kv[1])),
            "uncovered_s": tick.dur - covered}


def device_between(events: Sequence[Tuple[str, str, float, float]],
                   lo: float, hi: float, top: int = 8) -> dict:
    """What chip 0 ran between ``lo`` and ``hi`` (seconds on the trace's
    clock), from its ``(line, name, start, end)`` events of the ``XLA
    Ops`` and ``XLA Modules`` lines: its busy time there, and the programs
    and ops that took most of it."""
    if not events:
        return {}
    busy: List[Interval] = []
    by: Dict[str, Dict[str, float]] = {"XLA Modules": defaultdict(float),
                                       "XLA Ops": defaultdict(float)}
    for line, name, s, e in events:
        a, b = max(s, lo), min(e, hi)
        if b <= a or line not in by:
            continue
        by[line][name.split(" = ", 1)[0]] += b - a
        if line == "XLA Ops":
            busy.append((a, b))

    def most(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"busy_s": sum(e - s for s, e in union(busy)),
            "programs": most(by["XLA Modules"]), "ops": most(by["XLA Ops"])}
