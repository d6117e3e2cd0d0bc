"""Low-overhead execution tracing for the serving stack.

A :class:`TraceRecorder` is a bounded ring buffer of timeline events —
duration spans, begin/end pairs for spans whose end is not known at entry
(sequence lifecycle phases, host-tier stalls), instant markers and counter
samples.  The clock is injectable (the engine shares its metrics clock, so
tests drive a deterministic virtual timeline); production uses
``time.monotonic``.

Recording is cheap on purpose: one dataclass append per event, no
serialization, no device interaction.  When the buffer is full the oldest
events are evicted (``dropped`` counts them) — a trace of the *recent* past
is always available without unbounded memory.

Export (:meth:`TraceRecorder.to_chrome` / :meth:`TraceRecorder.dump`)
produces Chrome trace-event JSON loadable in Perfetto (ui.perfetto.dev) or
``chrome://tracing``.  Track layout:

- pid ``scheduler``: admission / preemption instants, queue-depth counters,
- pid ``engine``: per-tick spans with admit / prefill-chunk / decode
  sub-spans,
- pid ``sequences``: ONE thread per request (tid == request id) carrying
  its lifecycle phase spans (``seq.queued -> seq.prefill -> seq.decode``,
  ``seq.stall`` nested inside decode, ``seq.preempt`` instants),
- pid ``memory``: migration / prefetch instants plus ``pool`` and
  ``residency`` counter tracks,
- pid ``kernels``: per-step sparsity counter tracks (blocks attended,
  pages gathered, budget utilization).

:class:`Span` and :func:`instant` are the one way the serving stack marks
its timeline.  Each always opens a ``jax.profiler.TraceAnnotation``, so a
profiler capture holds the span on the device trace's clock, with its
metadata as event stats; given a recorder, the same call also records
into the ring.  :data:`SPAN_NAMES` lists the engine's spans.
"""
from __future__ import annotations

import json
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

from jax.profiler import StepTraceAnnotation, TraceAnnotation

#: Perfetto process-group ids, one per subsystem.
PID_SCHED = 1
PID_ENGINE = 2
PID_MEMORY = 3
PID_SEQ = 4
PID_KERNEL = 5

#: the engine's spans, outermost first (see ``repro.serving.engine``):
#: a tick; the scheduler's plan; one prefill chunk; a prompt's completion
#: (store refresh, first token); the batched decode step; a sampling
#: dispatch; a blocking device->host read; the host loop that commits a
#: decode step's tokens; one compile of an engine step.
SPAN_NAMES = (
    "engine.tick",
    "sched.plan",
    "prefill.chunk",
    "prefill.finish",
    "engine.decode",
    "engine.sample",
    "engine.wait",
    "engine.commit",
    "engine.compile",
)

PROCESS_NAMES = {
    PID_SCHED: "scheduler",
    PID_ENGINE: "engine",
    PID_MEMORY: "memory",
    PID_SEQ: "sequences",
    PID_KERNEL: "kernels",
}


@dataclass(slots=True)
class TraceEvent:
    """One timeline event (times in recorder-clock seconds).

    Slotted: a full ring holds ``capacity`` of these, and slots keep both
    the per-event footprint and GC scan cost down."""

    name: str
    ph: str                       # "X" | "B" | "E" | "i" | "C"
    ts: float
    pid: int
    tid: int
    dur: Optional[float] = None   # "X" only
    args: Optional[Dict[str, Any]] = None


class TraceRecorder:
    """Ring-buffered span/instant/counter recorder with Chrome export."""

    def __init__(
        self,
        capacity: int = 65536,
        clock: Callable[[], float] = time.monotonic,
    ):
        assert capacity > 0, capacity
        self.capacity = capacity
        self.clock = clock
        self._events: deque = deque(maxlen=capacity)
        #: events evicted from the ring (oldest-first) since creation.
        self.dropped = 0
        # (pid, tid) -> display name; kept OUTSIDE the ring so eviction
        # never loses track naming (emitted as metadata at export time).
        self._thread_names: Dict[tuple, str] = {}
        self._flush_hooks: list = []

    def __len__(self) -> int:
        return len(self._events)

    def events(self):
        """Current ring contents, oldest first (a snapshot list)."""
        return list(self._events)

    # -- recording -----------------------------------------------------------

    def _push(self, ev: TraceEvent):
        if len(self._events) == self.capacity:
            self.dropped += 1
        self._events.append(ev)

    def span(self, name: str, pid: int, tid: int = 0,
             args: Optional[dict] = None) -> "Span":
        """Scoped duration span (a :class:`Span` on this recorder): records
        ONE complete ("X") event at exit, so ring eviction can never leave
        a dangling half-span."""
        return Span(name, self, pid=pid, tid=tid, **(args or {}))

    def begin(self, name: str, pid: int, tid: int = 0,
              args: Optional[dict] = None):
        """Open span whose end is not known at entry (lifecycle phases,
        stalls).  Pair with :meth:`end` on the same (pid, tid) — spans on
        one track close innermost-first (stack discipline)."""
        self._push(TraceEvent(name, "B", self.clock(), pid, tid, args=args))

    def end(self, name: str, pid: int, tid: int = 0):
        self._push(TraceEvent(name, "E", self.clock(), pid, tid))

    def instant(self, name: str, pid: int, tid: int = 0,
                args: Optional[dict] = None):
        instant(name, self, pid=pid, tid=tid, **(args or {}))

    def counter(self, name: str, values: Dict[str, float], pid: int = PID_MEMORY):
        """Sample a counter track: ``values`` maps series name -> value."""
        self._push(TraceEvent(
            name, "C", self.clock(), pid, 0, args=dict(values)
        ))

    def counter_at(self, name: str, values: Dict[str, float], ts: float,
                   pid: int = PID_MEMORY):
        """Counter sample with an explicit (recorder-clock) timestamp.
        Trace-event JSON carries ts per event (viewers sort by it), so
        deferred emitters can batch hot-path samples and push them late —
        see :meth:`add_flush_hook`."""
        self._push(TraceEvent(name, "C", ts, pid, 0, args=dict(values)))

    def add_flush_hook(self, fn: Callable[[], None]):
        """Register ``fn()`` to run at export time, before serialization.
        Deferred emitters (e.g. the engine's per-step sparsity counters)
        queue raw samples on the hot path and materialize events here."""
        self._flush_hooks.append(fn)

    def name_thread(self, pid: int, tid: int, name: str):
        self._thread_names.setdefault((pid, tid), name)

    # -- export --------------------------------------------------------------

    def to_chrome(self) -> dict:
        """-> Chrome trace-event JSON object (Perfetto-loadable).

        Timestamps are microseconds relative to the earliest retained
        event; counter/instant semantics follow the trace-event spec.
        Flush hooks run first, so deferred emitters land in the export.
        """
        for fn in self._flush_hooks:
            fn()
        evs = list(self._events)
        t0 = min((e.ts for e in evs), default=0.0)
        out = []
        for pid, pname in PROCESS_NAMES.items():
            out.append({
                "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
                "args": {"name": pname},
            })
        for (pid, tid), name in self._thread_names.items():
            out.append({
                "name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
                "args": {"name": name},
            })
        for e in evs:
            rec: Dict[str, Any] = {
                "name": e.name, "ph": e.ph,
                "ts": (e.ts - t0) * 1e6,
                "pid": e.pid, "tid": e.tid,
            }
            if e.ph == "X":
                rec["dur"] = max(e.dur or 0.0, 0.0) * 1e6
            if e.ph == "i":
                rec["s"] = "t"                    # thread-scoped instant
            if e.args is not None:
                rec["args"] = e.args
            out.append(rec)
        return {
            "traceEvents": out,
            "displayTimeUnit": "ms",
            "otherData": {
                "dropped_events": self.dropped,
                "capacity": self.capacity,
            },
        }

    def dump(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)
            f.write("\n")
        return path


# -- the span API -------------------------------------------------------------


def profiling() -> bool:
    """Whether a profiler session is recording host annotations now."""
    return TraceAnnotation.is_enabled()


class Span:
    """Scoped span: a ``jax.profiler.TraceAnnotation`` always, and with a
    ``recorder`` one complete ("X") ring event at exit.

    ``step`` makes it a step annotation (``StepTraceAnnotation`` with
    ``step_num=step``).  ``meta`` is the span's metadata; :meth:`set` adds
    metadata known only inside the span, written at exit.  Metadata values
    are numbers or strings (a profiler stat each), so callers compute them
    only while :func:`profiling` or a recorder is attached."""

    __slots__ = ("name", "meta", "_rec", "_pid", "_tid", "_ann", "_late", "_t0")

    def __init__(self, name: str, recorder: Optional[TraceRecorder] = None, *,
                 pid: int = PID_ENGINE, tid: int = 0,
                 step: Optional[int] = None, **meta):
        if step is not None:
            meta["step_num"] = step
            self._ann = StepTraceAnnotation(name, **meta)
        else:
            self._ann = TraceAnnotation(name, **meta)
        self.name = name
        self.meta = meta
        self._rec = recorder
        self._pid = pid
        self._tid = tid
        self._late: Optional[Dict[str, Any]] = None

    def set(self, **meta):
        if self._late is None:
            self._late = meta
        else:
            self._late.update(meta)

    def __enter__(self) -> "Span":
        self._ann.__enter__()
        if self._rec is not None:
            self._t0 = self._rec.clock()
        return self

    def __exit__(self, *exc):
        late = self._late
        if late:
            self._ann.set_metadata(**late)
        self._ann.__exit__(*exc)
        rec = self._rec
        if rec is not None:
            args = {**self.meta, **late} if late else self.meta
            rec._push(TraceEvent(
                self.name, "X", self._t0, self._pid, self._tid,
                dur=rec.clock() - self._t0, args=args or None,
            ))
        return False


def instant(name: str, recorder: Optional[TraceRecorder] = None, *,
            pid: int = PID_ENGINE, tid: int = 0, **meta):
    """A point event: a zero-length profiler annotation, and with a
    ``recorder`` an instant ("i") ring event."""
    with TraceAnnotation(name, **meta):
        pass
    if recorder is not None:
        recorder._push(TraceEvent(
            name, "i", recorder.clock(), pid, tid, args=meta or None
        ))
