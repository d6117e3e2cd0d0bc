"""The one traffic generator: a traffic file's parameters -> requests.

A traffic file (``traffic/<mix>.json``) names its loop and the
distributions of its sizes.  Sizes are the mid-quantiles of those
distributions, in an order fixed by the file's ``schedule_seed``: a replay
of one fixed schedule.  The run's seed decides every prompt's token ids
(and, elsewhere, the weights).  So every seed offers the same work in the
same order, and the spread between runs measures the system rather than
the draw: at these prompt lengths a window holds a few requests, and which
sizes fall into it would otherwise decide the rate.

The one loop is ``closed``: ``slots`` sessions at a time; a finished
session's slot is taken by the next session of the replay set at once.
"""
from __future__ import annotations

from dataclasses import dataclass
from pathlib import Path
from typing import List

import numpy as np

HERE = Path(__file__).resolve().parent

#: size of the fixed multiset each size distribution is drawn from; every
#: run of ``cycle`` consecutive requests holds it exactly once.
CYCLE = 64


def quantile_sizes(dist: dict, n: int = CYCLE) -> np.ndarray:
    """The ``n`` mid-quantiles of a size distribution, as whole tokens."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = float(dist["low"]), float(dist["high"])
    kind = dist["dist"]
    if kind == "log_uniform":
        x = np.exp(np.log(lo) + u * (np.log(hi) - np.log(lo)))
    elif kind == "uniform":
        x = lo + u * (hi - lo)
    else:
        raise ValueError(f"unknown size distribution {kind!r}")
    return np.round(x).astype(np.int64)


@dataclass
class Planned:
    """One request as the traffic plans it."""

    index: int
    prompt_len: int
    output_len: int
    seed: int = 0

    def prompt(self, vocab: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, self.index])
        return rng.integers(0, vocab, self.prompt_len, dtype=np.int64).astype(
            np.int32)


@dataclass
class Traffic:
    requests: List[Planned]
    slots: int


def generate(spec: dict, seed: int) -> Traffic:
    """-> the requests of one run: the whole replay set."""
    if spec["loop"] != "closed":
        raise ValueError(f"unknown loop {spec['loop']!r}")
    rng = np.random.default_rng(int(spec["schedule_seed"]))
    prompts = quantile_sizes(spec["prompt_tokens"])
    outputs = quantile_sizes(spec["output_tokens"])
    n = int(spec["sessions"])
    order_p = np.concatenate([rng.permutation(CYCLE)
                              for _ in range(-(-n // CYCLE))])
    order_o = np.concatenate([rng.permutation(CYCLE)
                              for _ in range(-(-n // CYCLE))])
    reqs = [Planned(index=i, prompt_len=int(prompts[order_p[i]]),
                    output_len=int(outputs[order_o[i]]), seed=int(seed))
            for i in range(n)]
    return Traffic(reqs, int(spec["slots"]))
