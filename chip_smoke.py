#!/usr/bin/env python3
"""Bring-up smoke of the AB-Sparse serving path on a TPU.

Drives llama3.2-3b at its published widths (28 layers, d_model 3072, 24
query / 8 KV heads, head_dim 128, vocab 128256, bf16; random weights from
a fixed seed) through ``repro.serving.Engine`` on the Pallas path — fused
decode and sparse prefill kernels — with a calibrated, mixed 16/32/64
per-head block-size assignment at the full layer and head counts.

One chip (no arguments):
  serve    4 requests (5000-7500-token prompts sharing a 1024-token
           prefix, 32 greedy tokens each) at ``max_batch=2``,
           ``max_context=8192``; every request must finish on ladder rung 0
           with no degradation, and every compiled step must hold real
           Mosaic kernels (no interpreter).
  parity   on the cache of one 8000-token prompt, one decode step through
           the fused kernel and one through the pure-jnp ``reference``
           backend: page-set agreement (selected pages, intersection over
           union across all layers and heads) and the largest decode-logit
           error, each held to a stated tolerance.

``--four-chips``: only the mesh phase — the same traffic on a ``(1, 4)``
``(data, model)`` mesh against the single-device engine on device 0, which
must produce identical tokens.

Run from the checkout root; JAX must find a TPU, else it exits non-zero
before any work and prints no result.  The last stdout line is
``{"ok": true, "device": {"platform", "kind", "count"}}``.

    python chip_smoke.py
    python chip_smoke.py --four-chips
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
MAX_BATCH, MAX_CONTEXT = 2, 8192
PREFIX_LEN, PROMPT_LENS, NEW_TOKENS = 1024, (5000, 6000, 7000, 7500), 32
PARITY_PROMPT = 8000
SEED = 0
#: parity tolerances (justified in CHANGES.md): the fused kernel and the
#: jnp reference score the same int4 store in f32, so page sets may differ
#: only where two block scores tie to within rounding.
MIN_PAGE_AGREEMENT = 0.99
MAX_LOGIT_ERROR = 0.05          # relative to the reference logits' max |x|


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str):
    if not cond:
        raise SmokeFailure(what)


def report(phase: str, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


# ---------------------------------------------------------------------------
# configuration and traffic
# ---------------------------------------------------------------------------


def smoke_config():
    """llama3.2-3b at published widths on the fused / sparse-prefill Pallas
    path, with an Eq.-2 calibrated block size per (layer, kv head).

    The calibration profiles the model's own candidates and tau on the
    synthetic head-role generator at a 1024-token context with a
    256-token budget (the block size only matters when the budget is a
    small share of the context); at these settings it assigns a mix of
    16/32/64 within every layer."""
    import jax

    from repro.configs import get_config
    from repro.core import calibrate

    base = get_config("llama3.2-3b")
    sp = base.sparse
    cal = calibrate(
        jax.random.PRNGKey(SEED),
        n_layers=base.n_layers,
        n_kv_heads=base.n_kv_heads,
        head_dim=base.resolved_head_dim,
        seq_len=1024,
        candidates=sp.candidate_block_sizes,
        token_budget=256,
        tau=sp.tau,
        n_samples=1,
        method=sp.centroid_method,
    )
    sparse = dataclasses.replace(
        sp, backend="pallas", fused_decode=True, sparse_prefill=True,
        block_sizes=cal.as_tuple(),
    )
    return dataclasses.replace(base, sparse=sparse), cal


def serve_config():
    from repro.config import ServeConfig

    return ServeConfig(
        max_batch=MAX_BATCH, max_context=MAX_CONTEXT, temperature=0.0,
    )


def make_requests(vocab: int):
    import numpy as np

    from repro.serving import Request

    rng = np.random.default_rng(SEED)
    prefix = rng.integers(0, vocab, PREFIX_LEN).astype(np.int32)
    return [
        Request(
            rid,
            np.concatenate(
                [prefix, rng.integers(0, vocab, n - PREFIX_LEN).astype(np.int32)]
            ),
            max_new_tokens=NEW_TOKENS,
        )
        for rid, n in enumerate(PROMPT_LENS)
    ]


def peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def serve(cfg, params, mesh=None):
    """Serve the traffic; -> (tokens by request, engine).  Fails on any
    failed request, degradation, or step without a Mosaic kernel."""
    from repro.serving import Engine

    eng = Engine(cfg, params, serve_config(), mesh=mesh)
    reqs = make_requests(cfg.vocab_size)
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    done = eng.run_until_done(max_ticks=2000)
    wall = time.perf_counter() - t0
    snap = eng.metrics.snapshot()
    rung = eng.diagnostics()["rung"]
    kernels = {
        name: sum(t.count("tpu_custom_call") for t in step.compiled_texts())
        for name, step in eng.steps.items()
        if name in ("decode", "prefill_chunk")
    }
    fields = dict(
        mesh=None if mesh is None else dict(mesh.shape),
        compile_s=round(eng.compile_seconds, 3),
        wall_s=round(wall, 3),
        requests=len(done),
        tokens_served=sum(len(r.output) for r in done),
        prefill_tokens=snap["prefill_tokens_computed"],
        prefix_hit_tokens=snap["prefix_hit_tokens"],
        rung=rung,
        degradations=snap["degradations"],
        failed_requests=snap["requests_failed"],
        tpu_custom_calls=kernels,
    )
    check(len(done) == len(reqs), f"{len(done)}/{len(reqs)} requests retired")
    check(all(r.status == "ok" and len(r.output) == NEW_TOKENS for r in done),
          "a request failed or stopped short")
    check(snap["degradations"] == 0 and snap["requests_failed"] == 0,
          f"degradations={snap['degradations']} "
          f"failed={snap['requests_failed']}")
    check(rung == "fused", f"engine left ladder rung 0 (at {rung!r})")
    check(all(n > 0 for n in kernels.values()) and len(kernels) == 2,
          f"a compiled step holds no Mosaic kernel: {kernels}")
    return {r.req_id: list(r.output) for r in done}, eng, fields


def parity(cfg, params):
    """Fused kernel vs the jnp reference on one decode step from the same
    cache: page-set agreement and decode-logit error."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.backends import PallasBackend, get_backend, register_backend
    from repro.core.centroids import rank_query
    from repro.core.selection import select_page_table
    from repro.kernels import ops
    from repro.models import Transformer

    counts = []

    def page_mask(table, valid, n_pages):
        B, H, _ = table.shape
        bi = jnp.arange(B)[:, None, None]
        hi = jnp.arange(H)[None, :, None]
        return jnp.zeros((B, H, n_pages), bool).at[bi, hi, table].max(valid)

    class FusedVsReference(PallasBackend):
        """The fused decode, plus the reference selection on the same
        inputs; per-layer agreement counts leave the layer scan through a
        host callback."""

        name = "fused_vs_reference"

        def decode(self, q, k, v, store, layout, sparse, seq_len=None,
                   collect_tel=False):
            rq = rank_query(q, sparse.centroid_method, q.shape[-1])
            out, tbl_f, vld_f = ops.fused_decode(
                q, rq, k, v, store, layout,
                sink_pages=sparse.sink_pages, local_pages=sparse.local_pages,
                seq_len=seq_len,
                max_pages_per_block=sparse.max_block_size // sparse.page_size,
                interpret=self._interp(),
            )
            scores = get_backend("reference").scores(
                rq, store, layout, k.shape[1]
            )
            tbl_r, vld_r = select_page_table(
                scores, layout, seq_len=seq_len,
                sink_pages=sparse.sink_pages, local_pages=sparse.local_pages,
            )
            n_pages = k.shape[2]
            mf = page_mask(tbl_f, vld_f, n_pages)
            mr = page_mask(tbl_r, vld_r, n_pages)
            jax.debug.callback(
                lambda i, u: counts.append((int(i), int(u))),
                jnp.sum(mf & mr), jnp.sum(mf | mr),
            )
            return out, tbl_f

    register_backend(FusedVsReference(get_backend("pallas").interpret))
    probe_cfg = dataclasses.replace(
        cfg, sparse=dataclasses.replace(cfg.sparse, backend="fused_vs_reference")
    )
    ref_cfg = dataclasses.replace(
        cfg, sparse=dataclasses.replace(
            cfg.sparse, backend="reference", fused_decode=False,
            sparse_prefill=False,
        ),
    )
    model = Transformer(cfg)
    rng = np.random.default_rng(SEED + 1)
    tokens = rng.integers(0, cfg.vocab_size, (1, PARITY_PROMPT)).astype(np.int32)
    t0 = time.perf_counter()
    prefill = jax.jit(functools.partial(model.prefill, max_context=MAX_CONTEXT))
    logits0, cache = prefill(params, tokens)
    nxt = jnp.argmax(logits0, axis=-1).astype(jnp.int32)
    # f32-accurate matmuls on both sides: the comparison then measures the
    # kernel, not XLA's default single-pass bf16 rounding of f32 dots.
    with jax.default_matmul_precision("float32"):
        lf = jax.jit(lambda p, c, t: Transformer(probe_cfg).decode_step(p, c, t)[0])(
            params, cache, nxt
        )
        lr = jax.jit(lambda p, c, t: Transformer(ref_cfg).decode_step(p, c, t)[0])(
            params, cache, nxt
        )
    lf, lr = np.asarray(lf, np.float32), np.asarray(lr, np.float32)
    jax.effects_barrier()
    wall = time.perf_counter() - t0
    inter = sum(i for i, _ in counts)
    union = sum(u for _, u in counts)
    agreement = inter / max(union, 1)
    scale = float(np.max(np.abs(lr)))
    err = float(np.max(np.abs(lf - lr)))
    fields = dict(
        prompt_tokens=PARITY_PROMPT,
        wall_s=round(wall, 3),
        layers_compared=len(counts),
        page_agreement=agreement,
        min_page_agreement=MIN_PAGE_AGREEMENT,
        max_logit_error=err,
        logit_scale=scale,
        relative_logit_error=err / max(scale, 1e-30),
        max_relative_logit_error=MAX_LOGIT_ERROR,
        argmax_equal=bool(np.argmax(lf) == np.argmax(lr)),
        finite=bool(np.isfinite(lf).all() and np.isfinite(lr).all()),
    )
    check(len(counts) == cfg.n_layers, f"{len(counts)} layers compared")
    check(fields["finite"], "non-finite decode logits")
    check(agreement >= MIN_PAGE_AGREEMENT,
          f"page agreement {agreement} < {MIN_PAGE_AGREEMENT}")
    check(fields["relative_logit_error"] <= MAX_LOGIT_ERROR,
          f"relative logit error {fields['relative_logit_error']} "
          f"> {MAX_LOGIT_ERROR}")
    return fields


# ---------------------------------------------------------------------------


def run(args) -> dict:
    if not (ROOT / "src" / "repro").is_dir():
        raise SmokeFailure(f"no src/repro beside {Path(__file__).name}: run "
                           "it from a checkout of the repository")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax
    import numpy as np

    devices = jax.devices()
    dev = devices[0]
    check(dev.platform == "tpu",
          f"JAX found no TPU (platform {dev.platform!r}); nothing was run")
    from repro.backends import get_backend

    # never the interpreter: a kernel that cannot lower for the chip raises
    get_backend("pallas").interpret = False
    n_chips = 4 if args.four_chips else 1
    check(len(devices) >= n_chips, f"{len(devices)} devices < {n_chips}")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    report("device", **device, compile_cache=cache_dir)

    from repro.models import Transformer

    t0 = time.perf_counter()
    cfg, cal = smoke_config()
    cal_s = time.perf_counter() - t0
    sizes, counts = np.unique(cal.block_sizes, return_counts=True)
    t0 = time.perf_counter()
    params = jax.jit(Transformer(cfg).init)(jax.random.PRNGKey(SEED))
    jax.block_until_ready(params)
    report("setup", arch=cfg.name, layers=cfg.n_layers, d_model=cfg.d_model,
           calibrate_s=round(cal_s, 3),
           block_sizes={int(s): int(c) for s, c in zip(sizes, counts)},
           init_s=round(time.perf_counter() - t0, 3),
           peak_bytes_in_use=peak_bytes(dev))
    check(len(sizes) == 3, f"calibration did not mix block sizes: {sizes}")

    if args.four_chips:
        from jax.sharding import NamedSharding, PartitionSpec

        from repro.launch.mesh import make_serving_mesh

        single, eng, fields = serve(cfg, params)
        report("serve_one_device", **fields, peak_bytes_in_use=peak_bytes(dev))
        del eng
        mesh = make_serving_mesh((1, 4), n_kv_heads=cfg.n_kv_heads)
        params_m = jax.device_put(params, NamedSharding(mesh, PartitionSpec()))
        del params
        sharded, eng, fields = serve(cfg, params_m, mesh=mesh)
        same = single == sharded
        report("serve_mesh", **fields, token_identical=same,
               peak_bytes_in_use=[peak_bytes(d) for d in devices[:4]])
        check(same, "mesh serving diverged from the single-device engine")
        return device

    _, eng, fields = serve(cfg, params)
    report("serve", **fields, peak_bytes_in_use=peak_bytes(dev))
    del eng
    report("parity", **parity(cfg, params),
           peak_bytes_in_use=peak_bytes(dev))
    return device


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the (1, 4) mesh phase against one device")
    args = ap.parse_args()
    try:
        device = run(args)
    except Exception as exc:
        print(f"chip_smoke: FAILED: {type(exc).__name__}: {exc}",
              file=sys.stderr)
        raise SystemExit(1) from exc
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
