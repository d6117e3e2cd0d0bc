#!/usr/bin/env python3
"""On-chip benchmark of the AB-Sparse serving path.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on:
seeded random weights for the cell's configuration (``configs/``), the
engine on the Pallas path at rung 0, set-up (weights, engine, the cell's
slots filled, which compiles every program the window runs), then
``--seconds`` of the cell's traffic (``traffic/``) through
``Engine.submit`` / ``Engine.step``.  With ``--trace 0`` it reports the
cell's end-to-end metrics; with ``--trace 1`` its per-layer metrics, read
from a profiler trace of the window's last ``trace_seconds`` and, for
metrics that need the engine's device-side counters, from a short counting
pass after the window (``metrics/<name>.py`` each).  Every run then checks
what the window served against the configuration's plain reference
(``check.py``, ``references/``) and prints one JSON line last.

It exits non-zero, printing no result, where JAX finds no TPU, fewer chips
than the cell needs, or a device kind missing from ``peaks.json``.  JAX's
persistent compilation cache lives in ``JAX_COMPILATION_CACHE_DIR`` where
that is set, else in ``.jax_cache/`` at the checkout root.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, unknown device, bad spec)."""


# ---------------------------------------------------------------------------
# what BENCHMARK.json names
# ---------------------------------------------------------------------------


@dataclass
class Cell:
    name: str
    chips: int
    config: dict                 # configs/<config>.json
    traffic: dict                # traffic/<traffic>.json
    limits: dict                 # cells/<cell>.json
    end_to_end: List[str]
    per_layer: List[str]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: Path, name: str) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=json.loads((root / conf["file"]).read_text()),
        traffic=json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text()),
        limits=json.loads((HERE / "cells" / f"{name}.json").read_text()),
        end_to_end=[m["name"] for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m["name"] for m in bench["per_layer"] if _applies(m, name)],
    )


def load_reader(name: str):
    """``metrics/<name>.py`` (names may hold dots, so not an import path)."""
    path = HERE / "metrics" / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no reader {path.relative_to(HERE)} for metric {name!r}")
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------


def enable_compile_cache():
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def prepare_device(chips: int):
    """Find the TPU, keep compiled programs in the compile cache and take
    the Pallas kernels off the interpreter.  -> (devices used, device
    fields, peaks entry); raises BenchError."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise BenchError(f"JAX found no TPU (platform {dev.platform!r})")
    if len(devices) < chips:
        raise BenchError(f"{len(devices)} devices, the cell needs {chips}")
    peaks = json.loads((HERE / "peaks.json").read_text())["devices"]
    if dev.device_kind not in peaks:
        raise BenchError(f"device kind {dev.device_kind!r} is not in peaks.json")
    enable_compile_cache()
    from repro.backends import get_backend

    get_backend("pallas").interpret = False
    return devices[:chips], {"platform": dev.platform, "kind": dev.device_kind,
                             "count": chips}, peaks[dev.device_kind]


def memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

#: ticks of the counting pass after the window (``drive.Driver.count``)
COUNT_TICKS = 20


@dataclass
class Ctx:
    """What a metric reader may read."""

    cfg: object
    served: list
    window: tuple                    # (t0, t_end) on the host clock
    seconds: float                   # the window's set length
    prefill_tokens: float            # prompt tokens prefilled by t0 + seconds
    setup_s: float
    peak_flops: float
    peak_bytes: float
    trace: object = None             # tracing.DeviceTrace of the window's tail
    log: object = None               # drive.WorkLog of the same stretch
    counted: object = None           # drive.WorkLog of the counting pass
    notes: Dict[str, str] = field(default_factory=dict)


class Tracer:
    """Profiler capture of the window's tail, with the work log beside it."""

    def __init__(self, driver, log_dir: Path):
        self.driver, self.dir, self._cm, self._ann = driver, log_dir, None, None

    def start(self):
        import tracing
        from drive import WorkLog

        self._cm = tracing.capture(self.dir)
        self._cm.__enter__()
        self._ann = tracing.annotate("window")
        self._ann.__enter__()
        self.driver.log = WorkLog()

    def stop(self):
        """-> the traced stretch's work log."""
        self._ann.__exit__(None, None, None)
        self._cm.__exit__(None, None, None)
        return self.driver.log


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             control: bool = False) -> dict:
    """One run; -> the result line as a dict.  With ``control`` the float8
    control is put in the program's place: the number that decides
    ``correct`` is the gap of the tokens the control ranks first at the
    served requests' positions, and the program's own gap goes to ``info``
    (``tools/control.py``)."""
    import jax

    devices, device, peak = prepare_device(cell.chips)
    from repro.config import ServeConfig
    from repro.serving import Engine

    import check
    import tracing
    from drive import Driver, clock
    from model import init_params, model_config
    from traffic import generate

    tr = cell.traffic
    cfg = model_config(cell.config)
    params = init_params(cfg, seed)
    jax.block_until_ready(params)
    traffic = generate(tr, seed)
    serve = ServeConfig(max_batch=tr["max_batch"], max_context=tr["max_context"],
                        temperature=0.0, **tr.get("serve", {}))
    eng = Engine(cfg, params, serve)
    readers = {n: load_reader(n) for n in (cell.per_layer if trace else cell.end_to_end)}
    counts = any("telemetry" in getattr(r, "NEEDS", ()) for r in readers.values())
    driver = Driver(eng, traffic, cfg.vocab_size,
                    tracing.annotate if trace else None)
    driver.setup()
    # Freeze what set-up made (weights, compiled programs), as a server
    # does once it has loaded: no collection walks it inside the window.
    gc.collect()
    gc.freeze()
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(name)
        if name.endswith("backend_compile_duration") else None)
    setup_s = clock() - T_START

    trace_dir = Path(tempfile.mkdtemp(prefix="absparse-bench-")) if trace else None
    if trace:
        driver.trace_at = clock() + max(0.0, seconds - tr["trace_seconds"])
        driver.tracer = Tracer(driver, trace_dir)
    win = driver.run(seconds)
    compiled_in_window = len(compiles)
    served = list(driver.served.values())
    failed = sum(s.failed for s in served) + sum(eng.metrics.degradations.values())
    attempted = sum(1 for s in served
                    if not (s.finished and s.token_times and s.token_times[-1] <= win.t0))

    # -- the check's sample, as the window served it: the longest in it ----
    served_tokens = [(s.index, len(driver.requests[s.index].output))
                     for s in served if s.token_times and not s.failed]
    picked = check.sample(served_tokens, seed, tr["check_requests"],
                          tr["check_tokens"])
    items = [(driver.requests[i].prompt, list(driver.requests[i].output))
             for i in picked]

    memory = memory_peak(devices)
    counted = driver.count(COUNT_TICKS) if counts else None
    ctx = Ctx(cfg, served, (win.t0, win.t_end), win.seconds, win.prefill_tokens,
              setup_s,
              peak["bf16_flops_per_s"], peak["hbm_bytes_per_s"],
              log=driver.trace_log, counted=counted)
    if trace:
        ctx.trace = tracing.read_xplane(tracing.newest_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = {}
    units = {m["name"]: m["unit"] for m in _bench_metrics()}
    for name, reader in readers.items():
        value = reader.read(ctx)
        if value is not None:
            metrics[name] = {"value": float(value), "unit": units.get(name, "")}

    # -- free the program's state, then the reference ----------------------
    del driver, eng, params
    gc.unfreeze()
    gc.collect()
    jax.clear_caches()
    t_ref = clock()
    weights = check.reference_module(cell.config).weights(
        init_params(cfg, seed), cfg)
    result = check.compare(weights, cell.config, cfg.sparse.block_sizes, items,
                           tr["max_context"], control=control)
    ref_s = clock() - t_ref
    limit = float(cell.limits["max_logit_gap"])
    gap = result["control_max_logit_gap" if control else "max_logit_gap"]
    correct = bool(items) and gap <= limit

    device = dict(device, memory_peak_bytes=memory)
    if trace:
        device.update(busy_s=ctx.trace.busy_s(), window_s=ctx.trace.window_s)
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if trace:
        line["breakdown"] = ctx.trace.breakdown()
    line["info"] = {"compiles_in_window": compiled_in_window,
                    "longest_tick_s": win.longest_tick_s, "ticks": win.ticks,
                    "between_ticks_s": win.between_ticks_s,
                    "requests_compared": picked,
                    "tokens_compared": result["tokens_compared"],
                    "reference_s": ref_s, "notes": ctx.notes}
    if control:
        line["info"]["program_max_logit_gap"] = result["max_logit_gap"]
    line["checks"] = {"max_logit_gap": {"value": gap, "limit": limit}}
    return line


def _bench_metrics():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return bench["end_to_end"] + bench["per_layer"]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    try:
        if not (ROOT / "src" / "repro").is_dir():
            raise BenchError("no src/repro in this checkout: nothing to measure")
        cell = load_cell(ROOT, args.workload)
        line = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except BenchError as exc:
        print(f"benchmark: {exc}", file=sys.stderr)
        raise SystemExit(2) from None
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(line))


if __name__ == "__main__":
    main()
