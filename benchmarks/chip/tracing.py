"""Profiler capture and the reduction from a trace to numbers.

The reduction reads the ``.xplane.pb`` the JAX profiler writes, through
``jax.profiler.ProfileData``, and keeps only what the per-layer readers
need:

- device-op intervals of each chip (the ``XLA Ops`` line of every
  ``/device:TPU:n`` plane), from which busy time is their union;
- the summed device time and count of each op name, and of each jitted
  program (the ``XLA Modules`` line);
- the harness's own host annotations (``bench.*``), which label the idle
  gaps between device ops by what the host was doing.

Everything here is plain arithmetic on (start, end) pairs, kept apart
from the capture so that it can be checked on a small synthetic trace.
"""
from __future__ import annotations

import bisect
import contextlib
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

#: host annotations the harness writes; an idle gap takes the name of the
#: innermost one that covers its middle, and ``other`` where none does.
HOST_PREFIX = "bench."


@contextlib.contextmanager
def capture(log_dir: Path):
    """Trace the enclosed block: host annotations and device ops, without
    the Python tracer (which would slow every host call)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(HOST_PREFIX + name)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping (start, end) intervals into a sorted disjoint list."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def covered(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of a disjoint sorted interval list inside [lo, hi]."""
    out, cur = [], lo
    for s, e in clip(busy, lo, hi):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def label_gaps(gap_list: Sequence[Interval],
               host: Sequence[Tuple[str, float, float]]) -> List[str]:
    """For each gap, the name of the innermost host annotation covering its
    middle ("other" where none does).  Annotations of one name do not
    overlap each other; a shorter mean span is taken as the inner one."""
    spans: Dict[str, List[Interval]] = defaultdict(list)
    for name, s, e in host:
        spans[name].append((s, e))
    order = sorted(spans, key=lambda n: covered(spans[n]) / len(spans[n]))
    merged = {n: union(spans[n]) for n in order}
    starts = {n: [s for s, _ in merged[n]] for n in order}
    out = []
    for g in gap_list:
        mid = 0.5 * (g[0] + g[1])
        label = "other"
        for n in order:
            i = bisect.bisect_right(starts[n], mid) - 1
            if i >= 0 and merged[n][i][1] >= mid:
                label = n
                break
        out.append(label)
    return out


@dataclass
class DeviceTrace:
    """What the readers take from one trace; times in seconds."""

    window: Interval
    n_chips: int
    #: per chip, device-op intervals (unmerged)
    ops: List[List[Interval]] = field(default_factory=list)
    op_time: Dict[str, float] = field(default_factory=dict)
    op_count: Dict[str, int] = field(default_factory=dict)
    module_time: Dict[str, float] = field(default_factory=dict)
    module_count: Dict[str, int] = field(default_factory=dict)
    host: List[Tuple[str, float, float]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_s(self) -> float:
        """Union of device-op time inside the window, averaged over chips."""
        if not self.ops:
            return 0.0
        lo, hi = self.window
        return sum(covered(clip(union(c), lo, hi)) for c in self.ops) / len(
            self.ops)

    def idle_gaps(self) -> List[Tuple[str, float]]:
        """(label, seconds) of every gap in chip 0's busy union."""
        if not self.ops:
            return []
        lo, hi = self.window
        found = gaps(union(self.ops[0]), lo, hi)
        return [(label, g[1] - g[0])
                for label, g in zip(label_gaps(found, self.host), found)]

    def time_matching(self, needle: str, modules: bool = False) -> Tuple[float, int]:
        """Summed seconds and count of ops (or programs) whose name holds
        ``needle``."""
        times = self.module_time if modules else self.op_time
        counts = self.module_count if modules else self.op_count
        keys = [k for k in times if needle in k]
        return sum(times[k] for k in keys), sum(counts[k] for k in keys)

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most time, and the idle time of chip 0
        summed by what the host was doing."""
        ops = sorted(self.op_time.items(), key=lambda kv: -kv[1])[:top]
        idle: Dict[str, float] = defaultdict(float)
        for label, sec in self.idle_gaps():
            idle[label] += sec
        longest = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in longest]}


def newest_xplane(log_dir: Path) -> Path:
    files = sorted(Path(log_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def read_xplane(path: Path, window_ns: Optional[Interval] = None) -> DeviceTrace:
    """Reduce one ``.xplane.pb``.  ``window_ns`` bounds the window in the
    trace's own clock; by default it spans the harness's ``bench.window``
    annotation."""
    import jax

    pd = jax.profiler.ProfileData.from_file(str(path))
    ops: List[List[Interval]] = []
    op_time: Dict[str, float] = defaultdict(float)
    op_count: Dict[str, int] = defaultdict(int)
    mod_time: Dict[str, float] = defaultdict(float)
    mod_count: Dict[str, int] = defaultdict(int)
    host: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            chip: List[Tuple[float, float, str]] = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        s = ev.start_ns * 1e-9
                        chip.append((s, s + ev.duration_ns * 1e-9,
                                     ev.name.split(" = ", 1)[0]))
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        mod_time[ev.name] += ev.duration_ns * 1e-9
                        mod_count[ev.name] += 1
            chip.sort()
            # an op that encloses the next one (a loop around its body) is
            # left out of the per-op times; its body's ops count instead
            for i, (s, e, name) in enumerate(chip):
                if i + 1 < len(chip) and chip[i + 1][0] < e:
                    continue
                op_time[name] += e - s
                op_count[name] += 1
            ops.append([(s, e) for s, e, _ in chip])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        s = ev.start_ns * 1e-9
                        host.append((ev.name[len(HOST_PREFIX):], s,
                                     s + ev.duration_ns * 1e-9))
    if window_ns is not None:
        window = (window_ns[0] * 1e-9, window_ns[1] * 1e-9)
    else:
        spans = [(s, e) for name, s, e in host if name == "window"]
        if not spans:
            raise ValueError(f"{path}: no bench.window annotation")
        window = spans[0]
    host = [h for h in host if h[0] != "window"]
    return DeviceTrace(window, len(ops), ops, dict(op_time), dict(op_count),
                       dict(mod_time), dict(mod_count), host)
