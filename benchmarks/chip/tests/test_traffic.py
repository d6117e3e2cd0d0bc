"""Seeded traffic: exact repeats, and the same work for every seed."""
import _paths  # noqa: F401
import json
from collections import Counter

import numpy as np
import pytest

from traffic import CYCLE, HERE, generate, quantile_sizes

MIXES = ("decode-16k", "prompt-8k-24k")


def _spec(name):
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests_and_prompts(name):
    a = generate(_spec(name), 2**33 + 1)
    b = generate(_spec(name), 2**33 + 1)
    assert [(r.prompt_len, r.output_len) for r in a.requests] == [
        (r.prompt_len, r.output_len) for r in b.requests]
    assert np.array_equal(a.requests[3].prompt(1000), b.requests[3].prompt(1000))


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_replays_one_schedule_with_its_own_tokens(name):
    spec = _spec(name)
    a = generate(spec, 1)
    b = generate(spec, 2**33 + 2)
    assert [(r.prompt_len, r.output_len) for r in a.requests] == [
        (r.prompt_len, r.output_len) for r in b.requests]
    assert not np.array_equal(a.requests[0].prompt(151936),
                              b.requests[0].prompt(151936))
    assert a.slots == spec["slots"] and len(a.requests) == spec["sessions"]
    # each cycle holds every size of the distribution once
    cyc = a.requests[:CYCLE]
    assert Counter(r.prompt_len for r in cyc) == Counter(
        quantile_sizes(spec["prompt_tokens"]).tolist())
    assert Counter(r.output_len for r in cyc) == Counter(
        quantile_sizes(spec["output_tokens"]).tolist())


def test_sizes_follow_the_stated_distribution():
    s = quantile_sizes({"dist": "log_uniform", "low": 8192, "high": 24576})
    assert s.min() >= 8192 and s.max() <= 24576 and len(s) == CYCLE
    assert np.exp(np.log(s).mean()) == pytest.approx(np.sqrt(8192 * 24576), rel=0.01)
    u = quantile_sizes({"dist": "uniform", "low": 32, "high": 128})
    assert u.min() >= 32 and u.max() <= 128 and abs(u.mean() - 80) < 1


def test_only_the_closed_loop_is_generated():
    with pytest.raises(ValueError):
        generate(dict(_spec("decode-16k"), loop="open"), 1)
