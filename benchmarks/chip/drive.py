"""Drive ``repro.serving.Engine`` through a cell's traffic.

The window calls only ``Engine.submit`` and ``Engine.step``, as a server
loop would.  The harness keeps its own timestamps on the host clock: each
output token is timed at the return of the tick that produced it.  In a
traced run it also wraps the engine's admit / prefill-chunk / decode /
sample calls in host annotations (the engine's own spans run on another
clock), and logs what each prefill chunk and decode step computed, for the
cost functions.
"""
from __future__ import annotations

import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

from stats import Served
from traffic import Planned, Traffic

clock = time.perf_counter


@dataclass
class WorkLog:
    """What a stretch of ticks computed, for the cost functions."""

    #: (chunk offset, tokens) of every prefill chunk
    chunks: List[tuple] = field(default_factory=list)
    #: per decode step, the live context lengths of the slots that decoded
    decode_steps: List[List[int]] = field(default_factory=list)
    #: KV pages the decode selection gathered (device-side counters)
    telemetry_pages: Optional[int] = None


@dataclass
class Window:
    """The measured window, on the host clock."""

    t0: float
    #: when the tick that crossed ``t0 + seconds`` returned
    t_end: float
    seconds: float
    #: prompt tokens prefilled by ``t0 + seconds``: those of every tick that
    #: ended by then, and the share of the straddling tick's that its time
    #: inside the window makes.  A prefill tick runs ~3 s, so counting it
    #: whole or not at all would make the rate jump with the host's jitter.
    prefill_tokens: float
    #: the longest tick inside the window (a host stall shows here)
    longest_tick_s: float
    #: ticks in the window, and the window's time outside ``Engine.step``
    ticks: int
    between_ticks_s: float


def work_by(ticks, t_stop: float) -> float:
    """Work done by ``t_stop``: ``ticks`` are (start, end, work) of ticks
    that started before it; the one that ends after it counts for the share
    of its time that lies before."""
    return sum(n * min(1.0, (t_stop - a) / (b - a)) for a, b, n in ticks)


class Driver:
    def __init__(self, engine, traffic: Traffic, vocab: int,
                 annotate: Optional[Callable[[str], object]] = None):
        self.eng = engine
        self.traffic = traffic
        self.vocab = vocab
        self.served: Dict[int, Served] = {}
        self.requests: Dict[int, object] = {}    # index -> Request
        self._live: Dict[int, object] = {}       # the ones in flight
        self._next = 0
        self._ann = annotate
        #: (start, end, prompt tokens prefilled) of every tick
        self._ticks: List[tuple] = []
        #: the work log of the stretch being logged, if any
        self.log: Optional[WorkLog] = None
        #: the traced window's work log, once it has closed
        self.trace_log: Optional[WorkLog] = None
        #: a tracer (start/stop) and when to start it, for traced runs
        self.tracer = None
        self.trace_at = float("inf")
        if annotate is not None:
            self._instrument()

    # -- traced runs ------------------------------------------------------

    def _span(self, name: str):
        return self._ann(name) if self._ann is not None else nullcontext()

    def _instrument(self):
        """Wrap the engine's per-tick calls in host annotations and log the
        work they do (instance attributes only; behaviour is unchanged)."""
        eng = self.eng

        def wrap(obj, attr, name, before=None):
            inner = getattr(obj, attr)

            def call(*a, **k):
                if before is not None and self.log is not None:
                    before(*a, **k)
                with self._ann(name):
                    return inner(*a, **k)

            setattr(obj, attr, call)

        def log_chunk(ch):
            self.log.chunks.append((int(ch.offset), len(ch.tokens)))

        def log_decode():
            from repro.serving.scheduler import DECODE

            self.log.decode_steps.append([
                int(eng._seq_len[s.slot]) + 1 for s in eng.slots
                if s is not None and s.state == DECODE
            ])

        wrap(eng.scheduler, "plan_tick", "admit")
        wrap(eng, "_run_chunk", "prefill_chunk", log_chunk)
        wrap(eng, "_decode_tick", "decode", log_decode)
        wrap(eng, "_sample", "sample")

    # -- requests ---------------------------------------------------------

    def _submit(self, p: Planned):
        from repro.serving import Request

        req = Request(p.index, p.prompt(self.vocab), max_new_tokens=p.output_len)
        self.served[p.index] = Served(p.index)
        self._live[p.index] = req
        self.requests[p.index] = req
        self.eng.submit(req)

    def _fill(self):
        """Give every free slot the next session of the replay set."""
        reqs = self.traffic.requests
        while len(self._live) < self.traffic.slots and self._next < len(reqs):
            self._submit(reqs[self._next])
            self._next += 1

    def _tick(self):
        if self.tracer is not None and self.log is None and (
                clock() >= self.trace_at):
            self.tracer.start()
        m = self.eng.metrics
        p0 = m.prefill_tokens_computed
        start = clock()
        with self._span("tick"):
            self.eng.step()
        now = clock()
        self._ticks.append((start, now, m.prefill_tokens_computed - p0))
        for i, req in list(self._live.items()):
            s = self.served[i]
            new = len(req.output) - len(s.token_times)
            s.token_times.extend([now] * max(new, 0))
            if req.done:
                s.finished = req.status == "ok"
                s.failed = req.status != "ok"
                del self._live[i]

    # -- the loop ---------------------------------------------------------

    def setup(self):
        """Fill every slot and run until each session decodes (set-up):
        every program the window runs is compiled, and the window opens
        with the slots full."""
        self._fill()
        while any(len(self.served[i].token_times) < 2 for i in self._live):
            self._tick()

    def run(self, seconds: float) -> Window:
        """Keep every slot busy for ``seconds``; the window closes with the
        tick that crosses its end."""
        self._ticks = []
        t0 = clock()
        t_stop = t0 + seconds
        while clock() < t_stop:
            self._fill()
            self._tick()
        t_end = clock()
        if self.log is not None:
            self.trace_log = self.tracer.stop()
        self.log = self.tracer = None
        spans = [b - a for a, b, _ in self._ticks]
        return Window(t0, t_end, seconds, work_by(self._ticks, t_stop),
                      max(spans), len(spans), t_end - t0 - sum(spans))

    def count(self, ticks: int) -> WorkLog:
        """``ticks`` more ticks with the engine's device-side counters on:
        a program of its own, run after the window so that neither the
        window nor its trace times it.  -> their work log with the KV pages
        the decode selection gathered."""
        from repro.obs.telemetry import PAGES

        eng = self.eng
        eng.set_tracing(None, telemetry=True)
        agg = eng.metrics.sparsity
        agg.snapshot()                                   # folds pending ticks
        p0 = int(agg.layer_sums[:, PAGES].sum())
        self.log = log = WorkLog()
        for _ in range(ticks):
            self._fill()
            self._tick()
        agg.snapshot()
        log.telemetry_pages = int(agg.layer_sums[:, PAGES].sum()) - p0
        self.log = None
        eng.set_tracing(None, telemetry=False)
        return log
