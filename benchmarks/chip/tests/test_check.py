"""The comparison that decides ``correct``, driven through a whole run at
a size the CPU holds, with the chip check skipped.

- A sound run of the program's pure-jnp path in float32, each request in
  a slot no request held before, agrees with the plain reference (the same
  selection rules, independently written) to rounding.
- With the timed path broken underneath, ``correct`` comes out false: a
  served token altered where the sampler produces it, or a decode step
  that hands back the cache it was given (the state left unchanged).
- With the float8 control put in the program's place, the run's own
  decision comes out false, where the program's gap on the same served
  requests keeps the limit.
"""
import _paths  # noqa: F401
import pytest

from run import run_cell
from tiny import on_cpu, plant, tiny_cell

SEED = 2**33 + 11
CONTROL_SEED = 6


def _alter_tokens(eng):
    inner = eng._sample
    calls = [0]

    def sample(key, sids, poss, logits):
        toks, fin = inner(key, sids, poss, logits)
        calls[0] += 1
        if calls[0] % 7 == 3:
            toks = (toks + 1) % logits.shape[-1]
        return toks, fin

    eng._sample = sample


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_sound_run_agrees_with_reference(backend, monkeypatch):
    on_cpu(monkeypatch)
    line = run_cell(tiny_cell(backend=backend, sessions=2), SEED, 2.0, False)
    assert line["correct"] and line["failed"] == 0
    assert line["info"]["tokens_compared"] >= 48
    assert line["checks"]["max_logit_gap"]["value"] < 1e-3
    assert list(line)[-1] == "checks"


def _keep_state(eng):
    import jax
    import jax.numpy as jnp

    real, chunk = eng._rung_fns[0]

    def decode(params, cache, tokens):
        logits, _ = real(params, jax.tree.map(jnp.copy, cache), tokens)
        return logits, cache

    eng._rung_fns[0] = (decode, chunk)


@pytest.mark.parametrize("fault", [_alter_tokens, _keep_state])
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    on_cpu(monkeypatch)
    plant(monkeypatch, fault)
    line = run_cell(tiny_cell(), SEED, 2.0, False)
    assert not line["correct"]
    assert line["checks"]["max_logit_gap"]["value"] > line["checks"]["max_logit_gap"]["limit"]


def test_float8_control_in_the_programs_place_is_not_correct(monkeypatch):
    on_cpu(monkeypatch)
    line = run_cell(tiny_cell(dtype="bfloat16", limit=0.12), CONTROL_SEED, 2.0,
                    False, control=True)
    gap = line["checks"]["max_logit_gap"]
    assert not line["correct"]
    assert gap["value"] > gap["limit"]
    assert line["info"]["program_max_logit_gap"] <= gap["limit"]
