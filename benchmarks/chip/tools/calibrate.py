#!/usr/bin/env python3
"""Write the per-(layer, KV-head) block-size assignment into a config file:
one row for each global-attention layer (``model.sparse_layers``), in order.

Runs the program's own offline calibration (``calibrate_for_config``: the
Eq.-2 rule over the synthetic head-role generator, under the file's tau,
candidate sizes, centroid method and quantization) once, on the CPU, and
commits the result as data so that no run repeats it and every PR measures
the same assignment.  The profile runs at a 1024-token context with a
256-token budget: the same quarter of the context that a 4096-token budget
is of the cells' 16k caches, and small enough for the CPU.

    PYTHONPATH=src JAX_PLATFORMS=cpu python benchmarks/chip/tools/calibrate.py qwen1.5-4b
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

SEQ_LEN, BUDGET, SEED = 1024, 256, 0


def dump(spec: dict) -> str:
    """JSON with one line per row of the assignment."""
    rows = spec.pop("block_sizes")
    text = json.dumps(spec, indent=2)[:-2]
    body = ",\n".join(f"    {json.dumps(r)}" for r in rows)
    spec["block_sizes"] = rows
    return f'{text},\n  "block_sizes": [\n{body}\n  ]\n}}\n'


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    args = ap.parse_args()

    import jax
    import numpy as np

    from model import architecture, load_config_file, model_config, sparse_layers
    from repro.core import calibrate_for_config

    path = HERE / "configs" / f"{args.config}.json"
    spec = load_config_file(args.config)
    n_kv, n_sparse = (spec["num_key_value_heads"],
                      len(sparse_layers(architecture(spec))))
    spec["block_sizes"] = [[64] * n_kv] * n_sparse        # placeholder
    cfg = model_config(spec)
    cfg = dataclasses.replace(cfg, sparse=dataclasses.replace(
        cfg.sparse, token_budget=BUDGET, block_sizes=None))
    _, res = calibrate_for_config(jax.random.PRNGKey(SEED), cfg,
                                  seq_len=SEQ_LEN, n_samples=1)
    rows = [res.as_tuple()[l] for l in sparse_layers(cfg)]
    sizes, counts = np.unique(rows, return_counts=True)
    spec = load_config_file(args.config)
    spec["block_sizes"] = [list(row) for row in rows]
    spec["block_sizes_made_by"] = (
        f"repro.core.calibrate_for_config(PRNGKey({SEED}), cfg, "
        f"seq_len={SEQ_LEN}, n_samples=1) with token_budget={BUDGET}, on the "
        f"CPU, by benchmarks/chip/tools/calibrate.py; counts "
        + ", ".join(f"{int(s)}: {int(c)}" for s, c in zip(sizes, counts)))
    path.write_text(dump(spec))
    print(spec["block_sizes_made_by"])


if __name__ == "__main__":
    main()
