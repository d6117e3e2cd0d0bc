"""The comparison that decides ``correct``.

After the window closes, a sample drawn from the seed of the requests the
window served (the longest always among them) is replayed through the
configuration's plain reference: its prompt, then every token served to it
by then, fed back.  A session still decoding when the window closes is
compared on the tokens it was served: each was returned to its client.  At every served token the reference's float32 logits give a gap:
how far the served token's logit lies below the reference's best.  The
number compared is the widest gap; the limit is the cell's, set between
the program's readings and the control's (``cells/<cell>.json``).

The reference is the module ``references/<reference>.py`` that the
configuration file names.  It imports nothing of the program, and gives:

- ``weights(params, cfg)``: the seeded weights, drawn by the harness
  (``model.init_params``, in the program's parameter layout, nothing the
  program made), in the reference's own form; ``cfg`` is the cell's
  ``ModelConfig``, read for its shape.
- ``logits(weights, spec, block_sizes, tokens, n_prompt, positions,
  max_context, lower=False)``: float32 logits ``[len(positions), vocab]``
  at ``positions`` of ``tokens`` (the prompt's ``n_prompt`` tokens, then
  the served tokens fed back), over a ``max_context``-token frame.
  ``spec`` is the configuration file; ``block_sizes`` the committed
  assignment, one row for each global-attention layer in order
  (``model.sparse_layers``); ``lower`` computes the control, one precision
  below the configuration's.
"""
from __future__ import annotations

import importlib
from typing import Dict, List, Sequence

import numpy as np


def reference_module(spec: dict):
    return importlib.import_module(f"references.{spec['reference']}")


def sample(served: Sequence[tuple], seed: int, min_requests: int,
           min_tokens: int) -> List[int]:
    """``served``: (index, tokens served).  -> indices: the longest, then
    others in an order drawn from the seed, until ``min_requests`` requests
    and ``min_tokens`` served tokens are covered."""
    if not served:
        return []
    longest = max(served, key=lambda f: (f[1], -f[0]))
    rest = [f for f in served if f is not longest]
    rng = np.random.default_rng([int(seed), 0x636865636B])
    picked, total = [longest[0]], longest[1]
    for i in rng.permutation(len(rest)):
        if total >= min_tokens and len(picked) >= min_requests:
            break
        picked.append(rest[i][0])
        total += rest[i][1]
    return picked


def gaps(ref: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """Per position: reference best logit minus the reference logit of
    ``tokens`` (0 where the token is the reference's best)."""
    return ref.max(-1) - np.take_along_axis(ref, tokens[:, None], -1)[:, 0]


def replay(ref_mod, weights, spec, block_sizes, prompt: np.ndarray,
           served: np.ndarray, max_context: int, lower: bool = False):
    """Reference logits at the positions that produced ``served``."""
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    positions = np.arange(len(prompt) - 1, len(seq), dtype=np.int32)
    return ref_mod.logits(weights, spec, block_sizes, seq, len(prompt),
                          positions, max_context, lower=lower)


def compare(weights, spec: dict, block_sizes, items, max_context: int,
            control: bool = False) -> Dict[str, float]:
    """``items``: (prompt, served tokens).  -> widest gap over every served
    token (and, with ``control``, the widest gap of the tokens the float8
    reference puts first at the same positions)."""
    ref_mod = reference_module(spec)
    widest, ctrl, n = 0.0, 0.0, 0
    for prompt, served in items:
        served = np.asarray(served, np.int32)
        ref = replay(ref_mod, weights, spec, block_sizes, prompt, served,
                     max_context)
        widest = max(widest, float(gaps(ref, served).max()))
        n += len(served)
        if control:
            low = replay(ref_mod, weights, spec, block_sizes, prompt, served,
                         max_context, lower=True)
            ctrl = max(ctrl, float(gaps(ref, low.argmax(-1)).max()))
    out = {"max_logit_gap": widest, "tokens_compared": n}
    if control:
        out["control_max_logit_gap"] = ctrl
    return out
