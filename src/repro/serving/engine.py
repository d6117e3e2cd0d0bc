"""Continuous-batching serving engine.

The engine owns the device state (batched decode cache, jit'd model steps,
sampling) and executes what the :class:`~repro.serving.scheduler.Scheduler`
decides each tick:

1. **admit** waiting requests into free slots (page-pool gated); a prompt
   whose page-aligned prefix hits the radix prefix cache gets the cached KV
   pages installed directly into its slot — that span is never prefilled.
2. **prefill chunks** — ``prefill_tokens_per_tick`` worth of prompt tokens,
   written straight into the batch cache via ``Transformer.prefill_chunk``
   so long prompts interleave with decode instead of stalling the batch.
   When a prompt completes, its centroid store is rebuilt in one pass and
   its full prompt pages are inserted into the prefix cache.
3. **decode** — one jit'd ``decode_step`` over the whole batch; only slots
   in the decode state consume the sampled tokens.  The host-side sequence
   lengths are authoritative: prefilling slots ignore the batched step's
   garbage writes (their rows are overwritten by the next chunk).
4. **retire / preempt** — finished sequences free their pages (shared
   prefix pages survive in the cache); on pool exhaustion the newest
   running sequence is preempted and re-queued with its output preserved.

AB-Sparse is transparent here: the decode step internally runs
estimation -> adaptive top-k -> paged attention when the model's sparse
config is enabled for the engine's max_context.
"""
from __future__ import annotations

import dataclasses
import time
from contextlib import nullcontext
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig, ServeConfig
from repro.cache.paged_kv import PagePool
from repro.cache.prefix_cache import PrefixCache
from repro.memory import MemoryManager, TieredPagePool
from repro.distributed import params as pshard
from repro.distributed.kernel_partition import serving_rules
from repro.distributed.sharding import sharding_rules
from repro.models import Transformer
from repro.obs.telemetry import (
    BLOCKS,
    BUDGET,
    FORCED,
    N_COUNTERS,
    PAGES,
    SparsityAggregate,
    prefill_block_candidates,
)
from repro.obs.trace import (
    PID_ENGINE,
    PID_KERNEL,
    PID_MEMORY,
    PID_SCHED,
    TraceRecorder,
)
from repro.resilience import (
    DEVICE_FAULTS,
    FAIL_DEVICE,
    FAIL_SAMPLER,
    Checkpoint,
    FailureInfo,
    FaultInjector,
)
from repro.serving.metrics import ServingMetrics
from repro.serving.sampler import SamplerAnomaly, finite_mask, sample
from repro.serving.scheduler import (
    AdmitDecision,
    ChunkPlan,
    DECODE,
    PREFILL,
    Request,
    Scheduler,
    SeqState,
)


class EngineStalled(RuntimeError):
    """``run_until_done`` exhausted its tick budget with work still queued.

    Carries a post-mortem: ``diagnostics`` (queue depths, per-sequence
    phase / slot / tier residency / retry state, pool occupancy, the last
    metrics snapshot) so a stall can be analyzed without re-running under
    ``--trace``, and ``retired`` — the requests that DID complete during
    the call, which must not be discarded with the exception."""

    def __init__(self, message: str, diagnostics: Optional[Dict] = None,
                 retired: Optional[List[Request]] = None):
        super().__init__(message)
        self.diagnostics = diagnostics or {}
        self.retired = list(retired or [])


#: step faults the degradation ladder catches: injected or real device /
#: kernel errors plus non-finite sampler input.  Anything else is a bug
#: and propagates.
_STEP_FAULTS = DEVICE_FAULTS + (SamplerAnomaly,)


class StepCompileError(RuntimeError):
    """An engine step failed to trace, lower or compile (a kernel the chip's
    compiler refuses, a program that does not fit device memory).  That is
    a program error, not a step fault: it propagates instead of moving the
    engine down the degradation ladder."""


def _signature(args) -> tuple:
    leaves, tree = jax.tree.flatten(args)
    return (tree,) + tuple(
        (
            np.shape(x), np.result_type(x),
            getattr(x, "weak_type", False), getattr(x, "sharding", None),
        )
        for x in leaves
    )


class CompiledStep:
    """A jit'd engine step that compiles ahead of its first call for each
    argument signature, inside the engine's sharding context, so a failure
    to compile raises :class:`StepCompileError` and only a failure to RUN
    reaches the ladder as a step fault.  ``compile_seconds`` accumulates
    the time spent compiling."""

    def __init__(self, name: str, fn, ctx, donate_argnums=()):
        self._jit = jax.jit(fn, donate_argnums=donate_argnums)
        self._ctx = ctx
        self._name = name
        self._compiled: Dict[tuple, Callable] = {}
        self.compile_seconds = 0.0

    def __call__(self, *args):
        key = _signature(args)
        exe = self._compiled.get(key)
        if exe is None:
            t0 = time.perf_counter()
            try:
                with self._ctx():
                    exe = self._jit.lower(*args).compile()
            except Exception as exc:
                raise StepCompileError(
                    f"engine step {self._name!r} failed to compile: {exc}"
                ) from exc
            self.compile_seconds += time.perf_counter() - t0
            self._compiled[key] = exe
        return exe(*args)

    def compiled_texts(self) -> List[str]:
        """HLO text of every program compiled so far (one per signature)."""
        return [exe.as_text() for exe in self._compiled.values()]


def _fault_reason(exc: BaseException) -> str:
    return FAIL_SAMPLER if isinstance(exc, SamplerAnomaly) else FAIL_DEVICE


#: series names of the per-tick counter tracks (see Engine._trace_counters).
_COUNTER_KEYS = {
    "pool": ("used_pages", "free_pages"),
    "queue": ("waiting", "running"),
    "residency": ("hbm_pages", "host_pages"),
    "resilience": ("retries", "degradations", "requests_failed"),
}


class Engine:
    def __init__(
        self,
        model_cfg: ModelConfig,
        params,
        serve_cfg: ServeConfig,
        seed: int = 0,
        clock: Callable[[], float] = time.monotonic,
        mesh=None,
        shard_rules: Optional[Dict] = None,
        trace: Optional[TraceRecorder] = None,
        telemetry: Optional[bool] = None,
    ):
        """Batch capacity and context length come from ``serve_cfg``
        (``ServeConfig.max_batch`` / ``ServeConfig.max_context``) — the
        engine no longer carries shadow copies of those knobs.  The config
        is required: ``ServeConfig()``'s production-scale defaults
        (128 x 512k context) would allocate a colossal cache by accident.

        ``mesh`` (a ``(data, model)`` :class:`jax.sharding.Mesh`, e.g. from
        :func:`repro.launch.mesh.make_serving_mesh`) makes the engine
        mesh-native: the KV cache / centroid store / plan descriptors are
        allocated with ``NamedSharding`` (batch over ``data``, kv heads
        over ``model``), every jit'd step runs under the serving sharding
        context (so the Pallas backend shard_maps its kernel launches via
        :mod:`repro.distributed.kernel_partition`), and cache donation is
        preserved.  Sharded serving is token-identical to the single-device
        path.  ``shard_rules`` overrides individual logical-axis rules.

        ``trace`` (a :class:`~repro.obs.trace.TraceRecorder`) turns on
        timeline recording across every subsystem — scheduler, engine,
        memory manager, prefix cache all emit through this one recorder.
        ``telemetry`` turns on device-side sparsity counters (defaults to
        following ``trace``; requires the sparse decode path): the decode
        step emits a per-layer ``[blocks, pages, forced, budget]`` array
        that rides along on the host transfers the engine already makes.
        Both default OFF, and when off the cache carries no telemetry
        entries at all — the traced/untraced compiled steps are identical.
        """
        self.cfg = model_cfg
        self.serve = serve_cfg
        self.model = Transformer(model_cfg)
        self.params = params
        self.mesh = mesh
        assert shard_rules is None or mesh is not None, (
            "shard_rules given without a mesh — pass mesh= (the override "
            "would otherwise be silently ignored)"
        )
        self.shard_rules = (
            serving_rules(shard_rules) if mesh is not None else None
        )
        if (
            mesh is not None
            and int(np.prod(mesh.devices.shape)) > 1
            and model_cfg.sparse.backend == "pallas"
            and not model_cfg.sparse.fused_decode
        ):
            import warnings

            # still token-identical (GSPMD replicates the opaque kernel
            # launches), but the sharded KV pool is re-gathered every step.
            warnings.warn(
                "mesh serving with the STAGED pallas decode path: only "
                "SparseConfig.fused_decode=True runs shard_map'd kernels; "
                "the staged kernels replicate under GSPMD and re-gather "
                "the sharded KV pool each step",
                stacklevel=2,
            )
        default_pages = self.max_batch * (
            self.max_context // self.serve.page_size
        )
        if serve_cfg.hbm_pages is not None:
            # hierarchical KV memory: pages migrate between an HBM budget
            # and a host spill tier (see :mod:`repro.memory`).
            if serve_cfg.pool_pages is not None:
                raise ValueError(
                    "hbm_pages and pool_pages are mutually exclusive: the "
                    "tiered pool's capacity is hbm_pages + host_pages"
                )
            if not self.model.use_sparse(self.max_context):
                raise ValueError(
                    "tiered KV memory requires the sparse decode path to be "
                    f"active at max_context={self.max_context}: dense decode "
                    "reads every KV row, so host-resident pages would "
                    "corrupt it"
                )
            bad = {"rglru", "rwkv"} & set(self.model.plan.pattern)
            if bad or model_cfg.moe is not None:
                raise ValueError(
                    "tiered KV memory needs idempotent decode steps (a "
                    "host-tier miss re-runs the owning sequence's step): "
                    f"recurrent layers {sorted(bad)} / MoE routing carry "
                    "cross-step or cross-row state and are not supported"
                )
            self.pool: PagePool = TieredPagePool(
                hbm_pages=serve_cfg.hbm_pages,
                host_pages=serve_cfg.host_pages,
                page_size=self.serve.page_size,
            )
            # admission cap: each decoding sequence shields roughly its
            # selected pages + tail page + next-token reservation in HBM.
            # Past hbm_pages // ws concurrent sequences the combined
            # shields can cover the whole budget, leaving no demotion
            # victim for anyone — a livelock preemption only breaks after
            # the fact.  Refuse the admission up front instead.
            ws_est = (
                model_cfg.sparse.budget_for(self.max_context)
                // self.serve.page_size
                + 2
            )
            self.pool.max_live_seqs = max(1, serve_cfg.hbm_pages // ws_est)
        else:
            self.pool = PagePool(
                total_pages=serve_cfg.pool_pages or default_pages,
                page_size=self.serve.page_size,
            )
        self.key = jax.random.PRNGKey(seed)
        #: the engine's compiled steps by name (``decode``,
        #: ``prefill_chunk``, ... and ``<step>@<rung>`` for degraded rungs).
        self.steps: Dict[str, CompiledStep] = {}

        self.cache = self.model.init_cache(self.max_batch, self.max_context)
        if mesh is not None:
            # allocate device state mesh-wide: KV pool batch x kv-head
            # sharded, store codes batch-sharded (ragged rows whole), plan
            # descriptors replicated — all as explicit NamedShardings so
            # the jit'd steps start from (and donate back into) the
            # serving layout instead of resharding per tick.
            self.cache = jax.device_put(
                self.cache,
                pshard.tree_shardings(
                    self.cache, mesh, self.shard_rules, kind="cache"
                ),
            )
        self.slots: List[Optional[SeqState]] = [None] * self.max_batch
        self.finished: List[Request] = []
        self.metrics = ServingMetrics(clock=clock)
        self.trace = trace
        self.metrics.trace = trace
        # last emitted value per counter track (see _trace_counters dedup).
        self._last_counters: Dict[str, tuple] = {}
        self._chunkable = (
            serve_cfg.prefill_chunk > 0
            and self.model.supports_chunked_prefill()
        )
        self.prefix_cache = (
            PrefixCache(self.pool)
            if (serve_cfg.enable_prefix_cache and self._chunkable)
            else None
        )
        if self.prefix_cache is not None:
            self.prefix_cache.trace = trace
        #: sparse prefill active => chunk boundaries and reused prefix spans
        #: must align to the query-block size (chunked selection is then
        #: token-identical to single-shot sparse prefill).
        self._sparse_prefill = (
            model_cfg.sparse.sparse_prefill
            and self._chunkable
            and self.model.use_sparse(self.max_context)
        )
        self.scheduler = Scheduler(
            serve_cfg, self.pool, self.prefix_cache, self.metrics,
            chunkable=self._chunkable,
            chunk_align=(
                model_cfg.sparse.prefill_block_q if self._sparse_prefill else 1
            ),
        )
        # the cache argument is donated: every jit'd step updates the cache
        # functionally, and without donation XLA materializes a full copy of
        # the KV pool per tick.  The engine never reuses a pre-step cache
        # reference (it reassigns ``self.cache`` from each step's result),
        # and ``init_cache`` gives the cache private copies of the shared
        # plan descriptors, so donation is safe.
        # jit'd steps trace under the serving sharding context so
        # model-level ``constrain`` calls and the backend's shard_map'd
        # kernel launches see the mesh (see CompiledStep).
        self._decode = self._step("decode", self.model.decode_step, (1,))
        self._chunk = self._step("prefill_chunk", self.model.prefill_chunk, (1,))
        self._refresh = self._step(
            "refresh_store", self.model.refresh_slot_store, (0,)
        )
        self._refresh_scores = self._step(
            "refresh_score_rows", self.model.refresh_slot_score_rows, (0,)
        )
        self._chunk_len = min(serve_cfg.prefill_chunk, self.max_context)
        self._tokens_buf = np.zeros((self.max_batch,), np.int32)
        #: authoritative per-slot sequence lengths (tokens with KV in cache).
        self._seq_len = np.zeros((self.max_batch,), np.int32)
        # sampling keys are derived per (sequence, output position) — not
        # from a split-per-tick stream — so sampled tokens are invariant to
        # tick scheduling (stalls, preemption order, batch composition).
        # This is what makes an overcommitted tiered-memory run
        # token-identical to an all-HBM run.
        self._sample = self._step("sample", self._sample_batch)
        self.memory: Optional[MemoryManager] = None
        if isinstance(self.pool, TieredPagePool):
            nP = self.max_context // self.serve.page_size
            # plant the opt-in selection-emission keys: every decode step
            # reports the per-slot selected / margin-predicted page masks.
            self.cache["_sel_pages"] = jnp.zeros((self.max_batch, nP), bool)
            self.cache["_pre_pages"] = jnp.zeros((self.max_batch, nP), bool)
            self.memory = MemoryManager(self, self.pool)
        # opt-in device-side sparsity telemetry (repro.obs): plant the
        # per-layer counter outputs so the jit'd steps emit them; they ride
        # along on the per-tick host syncs (zero extra transfers when off).
        self._telemetry_on = False
        self._plan_layouts = None
        # raw (ts, tel, slots) samples awaiting export-time materialization
        # into "sparsity" counter events (see _flush_sparsity_counters).
        self._tel_pending: List[tuple] = []
        self._tel_flush_recorder: Optional[TraceRecorder] = None
        # -- failure domains (repro.resilience) ------------------------------
        self.resilience = serve_cfg.resilience
        #: optional FaultInjector; None keeps every injection point a
        #: single attribute check (the hot path is byte-for-byte unchanged).
        self._fault: Optional[FaultInjector] = None
        #: degradation ladder: rung 0 is the configured backend; later
        #: rungs are progressively more conservative decode/prefill paths
        #: (fused -> staged -> reference).  Rung step fns jit lazily.
        self._ladder = self._build_ladder()
        self._rung_fns: Dict[int, Tuple] = {0: (self._decode, self._chunk)}
        self._rung = 0              # current (sticky) operating rung
        self._clean_ticks = 0       # clean decode ticks since a degradation
        self._tick_had_fault = False
        self._idle_ticks = 0        # consecutive no-progress ticks (watchdog)
        self.set_tracing(trace, telemetry=telemetry)

    def set_tracing(
        self,
        trace: Optional[TraceRecorder],
        telemetry: Optional[bool] = None,
    ):
        """Attach/detach the trace recorder and device-side telemetry on a
        live engine.  Telemetry toggling adds/removes the counter entries
        from the decode cache, which swaps the jit'd step signature — the
        first tick after a toggle compiles that variant unless it already
        ran.  The overhead benchmark uses this to A/B traced vs untraced on
        ONE engine (same params / cache buffers), which removes per-engine
        allocation bias from the comparison."""
        self.trace = trace
        self.metrics.trace = trace
        self._last_counters = {}
        if trace is not None and self._tel_flush_recorder is not trace:
            trace.add_flush_hook(
                lambda t=trace: self._flush_sparsity_counters(t)
            )
            self._tel_flush_recorder = trace
        if telemetry is None:
            telemetry = trace is not None
        on = bool(telemetry and self.model.use_sparse(self.max_context))
        if on == self._telemetry_on:
            return
        self._telemetry_on = on
        L = self.cfg.n_layers
        self.cache = dict(self.cache)
        if on:
            self.cache["_telemetry"] = jnp.zeros(
                (L, self.max_batch, N_COUNTERS), jnp.int32
            )
            if self._sparse_prefill:
                self.cache["_ptel"] = jnp.zeros((L,), jnp.int32)
            if self.metrics.sparsity is None:
                self.metrics.sparsity = SparsityAggregate(L)
            if self._plan_layouts is None:
                self._plan_layouts = self.model.attention_plan(
                    self.max_context
                ).layouts
        else:
            self.cache.pop("_telemetry", None)
            self.cache.pop("_ptel", None)

    # -- fault injection / degradation ladder (repro.resilience) -------------

    def set_fault_injector(self, injector: Optional[FaultInjector]):
        """Attach/detach a :class:`~repro.resilience.FaultInjector` on a
        live engine — the same attach pattern as :meth:`set_tracing`.  The
        injector threads through the page pool's allocator, the memory
        manager's host-tier I/O, the decode/prefill dispatch and the tick
        clock; with ``None`` installed every one of those points is a
        single ``is not None`` check and no code path changes."""
        self._fault = injector
        self.pool.fault_hook = None if injector is None else self._pool_fault
        if self.memory is not None:
            self.memory.fault = injector

    def _pool_fault(self, reason: str, need: int):
        self._fault.check_raise(
            "pool_alloc", tick=self.metrics.ticks, detail=f"{reason} x{need}"
        )

    def _build_ladder(self) -> List[Tuple[str, Optional[Dict]]]:
        """Rungs of ``(name, sparse-config overrides)``; ``None`` = the
        configured backend as-is.  The reference rung disables the kernel
        paths entirely — it is the exact oracle every backend is parity-
        tested against, so it is the safe floor for anomalous steps."""
        sp = self.cfg.sparse
        ref = {"backend": "reference", "fused_decode": False,
               "sparse_prefill": False}
        if sp.backend == "pallas" and sp.fused_decode:
            return [("fused", None), ("staged", {"fused_decode": False}),
                    ("reference", ref)]
        if sp.backend == "pallas":
            return [("staged", None), ("reference", ref)]
        return [(sp.backend, None)]

    def _rung_step_fns(self, rung: int) -> Tuple:
        """(decode_step, prefill_chunk) jit'd for ``rung``, built lazily and
        compiled before their first call (:class:`CompiledStep`): a rung
        that cannot compile raises instead of degrading further.
        All rungs share the engine's params and cache: the paged KV / store
        layout is backend-independent (PR 1's byte-identical stores), so a
        degraded re-run picks up the exact device state the failed attempt
        would have used."""
        if rung not in self._rung_fns:
            _, over = self._ladder[rung]
            cfg = dataclasses.replace(
                self.cfg, sparse=dataclasses.replace(self.cfg.sparse, **over)
            )
            model = Transformer(cfg)
            name = self._ladder[rung][0]
            self._rung_fns[rung] = (
                self._step(f"decode@{name}", model.decode_step, (1,)),
                self._step(f"prefill_chunk@{name}", model.prefill_chunk, (1,)),
            )
        return self._rung_fns[rung]

    def _with_ladder(self, seqs_of, attempt) -> bool:
        """Run ``attempt(rung)`` under the degradation ladder: a step fault
        re-runs the attempt at the next rung down (fused -> staged ->
        reference) within the same tick; re-running is byte-safe because
        decode KV writes land at the host-authoritative ``seq_len`` and
        nothing advances until the attempt returns.  Success at a degraded
        rung makes that rung sticky (re-promotion after
        ``resilience.repromote_after`` clean ticks).  At the ladder floor
        the fault is charged to ``seqs_of(exc)`` — each implicated sequence
        restores from its last checkpoint or, past its failure budget,
        retires as FAILED.  -> True when the attempt ran to completion."""
        rung = self._rung
        while True:
            try:
                attempt(rung)
            except _STEP_FAULTS as exc:
                self._tick_had_fault = True
                if rung + 1 < len(self._ladder):
                    rung += 1
                    self.metrics.on_degrade(
                        self._ladder[rung][0], _fault_reason(exc)
                    )
                    continue
                self._on_step_failure(seqs_of(exc), exc)
                return False
            break
        if rung != self._rung:
            self._rung = rung
            self._clean_ticks = 0
        return True

    def _on_step_failure(self, seqs: List[SeqState], exc: BaseException):
        """Ladder floor: charge the fault to each implicated sequence's
        failure budget — restore from checkpoint with exponential backoff,
        or retire as FAILED once the budget is spent."""
        reason = _fault_reason(exc)
        for seq in list(seqs):
            if self.scheduler.running.get(seq.seq_id) is not seq:
                continue
            seq.retries += 1
            self.metrics.on_retry(seq.seq_id, reason)
            if seq.retries > self.resilience.failure_budget:
                self._fail_seq(seq, reason, exc)
            else:
                self._restore_seq(seq)

    def _restore_seq(self, seq: SeqState):
        """Re-admit ``seq`` from its last checkpoint: output truncated to
        the watermark, pages freed, re-queued behind an exponential
        backoff.  Token-identical by construction — sampling is keyed by
        (seq_id, position) and the resume prefill rebuilds KV exactly."""
        if self.memory is not None:
            self.memory.forget(seq.seq_id)
        slot = seq.slot
        backoff = self.resilience.retry_backoff_ticks * (
            2 ** max(0, seq.retries - 1)
        )
        self.scheduler.restore(seq, self.metrics.ticks + backoff)
        if slot >= 0:
            self.slots[slot] = None
            self._seq_len[slot] = 0
        seq.slot = -1

    def _fail_seq(self, seq: SeqState, reason: str, exc: BaseException):
        """Failure budget exhausted: retire as FAILED with a structured
        reason instead of poisoning the tick loop."""
        if self.memory is not None:
            self.memory.forget(seq.seq_id)
        slot = seq.slot
        self.scheduler.fail(seq, reason)
        if slot >= 0:
            self.slots[slot] = None
            self._seq_len[slot] = 0
        seq.slot = -1
        req = seq.req
        req.done = True
        req.status = "failed"
        req.failure = FailureInfo(
            reason=reason, detail=str(exc),
            tick=self.metrics.ticks, retries=seq.retries,
        ).as_dict()
        self.finished.append(req)

    def _take_checkpoint(self, seq: SeqState):
        """O(1) restore point: the committed-output watermark is all a
        restore needs (page bytes recompute exactly; see
        :mod:`repro.resilience.failure`)."""
        seq.checkpoint = Checkpoint(
            n_output=len(seq.req.output),
            n_pages=len(self.pool.table(seq.seq_id).physical),
            tick=self.metrics.ticks,
        )
        self.metrics.on_checkpoint(seq.seq_id)

    def diagnostics(self) -> Dict:
        """Post-mortem state dump (attached to :class:`EngineStalled` and
        usable any time): queue depths, per-sequence phase / slot / retry /
        tier residency, pool occupancy, ladder rung, metrics snapshot."""
        seqs = {}
        for sid, seq in self.scheduler.running.items():
            d = {
                "phase": seq.state,
                "slot": seq.slot,
                "prefilled": int(seq.prefilled),
                "output_tokens": len(seq.req.output),
                "retries": seq.retries,
            }
            if self.memory is not None:
                d["stalled"] = sid in self.memory.stalled
                d["host_resident_pages"] = len(
                    self.pool.host_resident_logical(sid)
                )
            seqs[sid] = d
        diag = {
            "tick": self.metrics.ticks,
            "waiting": len(self.scheduler.waiting),
            "running": len(self.scheduler.running),
            "in_backoff": [
                [s.seq_id, s.retry_after]
                for s in self.scheduler.waiting
                if s.retry_after > self.metrics.ticks
            ],
            "rung": self._ladder[self._rung][0],
            "idle_ticks": self._idle_ticks,
            "pool": {
                "used_pages": self.pool.used_pages,
                "free_pages": self.pool.free_pages,
            },
            "sequences": seqs,
            "last_snapshot": self.metrics.snapshot(),
        }
        if self._fault is not None:
            diag["faults_injected"] = self._fault.snapshot()
        return diag

    # -- sampling -------------------------------------------------------------

    def _sample_batch(self, base_key, seq_ids, positions, logits):
        t, k, p = self.serve.temperature, self.serve.top_k, self.serve.top_p

        def one(sid, pos, lg):
            kk = jax.random.fold_in(jax.random.fold_in(base_key, sid), pos)
            return sample(kk, lg[None], t, k, p)[0]

        # the finite mask rides the same host transfer as the tokens, so
        # non-finite detection is free on the fault-free path.
        return jax.vmap(one)(seq_ids, positions, logits), finite_mask(logits)

    def _shard_ctx(self):
        if self.mesh is None:
            return nullcontext()
        return sharding_rules(self.mesh, self.shard_rules)

    def _step(self, name: str, fn, donate_argnums=()) -> CompiledStep:
        step = CompiledStep(name, fn, self._shard_ctx, donate_argnums)
        self.steps[name] = step
        return step

    @property
    def compile_seconds(self) -> float:
        """Seconds this engine has spent compiling its steps."""
        return sum(s.compile_seconds for s in self.steps.values())

    @property
    def max_batch(self) -> int:
        return self.serve.max_batch

    @property
    def max_context(self) -> int:
        return self.serve.max_context

    @property
    def queue(self) -> List[Request]:
        """Waiting requests (scheduler view), oldest first."""
        return [s.req for s in self.scheduler.waiting]

    # -- admission -----------------------------------------------------------

    def submit(self, req: Request):
        if len(req.prompt) + req.max_new_tokens > self.max_context:
            raise ValueError(
                f"request {req.req_id}: prompt {len(req.prompt)} + "
                f"{req.max_new_tokens} new tokens exceeds max_context "
                f"{self.max_context}"
            )
        self.scheduler.submit(req)

    def _install(self, adm: AdmitDecision):
        """Occupy the slot; copy prefix-cache KV pages into its rows."""
        seq = adm.seq
        self.slots[adm.slot] = seq
        self._seq_len[adm.slot] = adm.prefix_tokens
        self._tokens_buf[adm.slot] = 0
        if adm.prefix_tokens:
            entry = dict(self.cache["pos0"])
            k = jnp.asarray(
                np.concatenate([kv["k"] for kv in adm.prefix_kv], axis=2)
            )
            v = jnp.asarray(
                np.concatenate([kv["v"] for kv in adm.prefix_kv], axis=2)
            )
            L = adm.prefix_tokens
            if entry["k"].ndim == 6:      # paged (sparse-active) cache
                ps = entry["k"].shape[4]
                nP = L // ps              # prefix spans are page-aligned
                kp = k.reshape(k.shape[0], k.shape[1], nP, ps, k.shape[-1])
                vp = v.reshape(kp.shape)
                entry["k"] = entry["k"].at[:, adm.slot, :, :nP].set(
                    kp.astype(entry["k"].dtype)
                )
                entry["v"] = entry["v"].at[:, adm.slot, :, :nP].set(
                    vp.astype(entry["v"].dtype)
                )
            else:
                entry["k"] = entry["k"].at[:, adm.slot, :, :L].set(
                    k.astype(entry["k"].dtype)
                )
                entry["v"] = entry["v"].at[:, adm.slot, :, :L].set(
                    v.astype(entry["v"].dtype)
                )
            self.cache = dict(self.cache)
            self.cache["pos0"] = entry
            if self._sparse_prefill:
                # the installed span's KV never ran prefill_chunk, so its
                # scoring rows must be derived before later chunks score it.
                # This rebuilds the whole slot (O(S_max), like the one-shot
                # refresh_slot_store at prompt completion) rather than just
                # the installed span: a span-sized window would need a
                # distinct compiled shape per prefix length.
                self.cache = self._refresh_scores(
                    self.cache, np.int32(adm.slot)
                )

    # -- prefill -------------------------------------------------------------

    def _run_chunk(self, ch: ChunkPlan):
        seq = ch.seq
        if seq.state != PREFILL:      # preempted after planning
            return
        if not self.scheduler._seq_chunkable(seq):
            # monolithic prefill has no kernel rungs to fall back to; a
            # step fault goes straight to the per-sequence failure budget.
            try:
                self._prefill_monolithic(seq)
            except _STEP_FAULTS as exc:
                self._tick_had_fault = True
                self._on_step_failure([seq], exc)
            return
        self._with_ladder(
            lambda exc: [seq],
            lambda rung: self._attempt_chunk(rung, ch),
        )

    def _attempt_chunk(self, rung: int, ch: ChunkPlan):
        """One ladder attempt at ``ch``: chunk prefill writes KV at explicit
        offsets, so a degraded re-run of the same chunk is byte-identical
        (``on_prefill`` may count the recomputed tokens twice — that is
        work genuinely performed)."""
        seq = ch.seq
        if self._fault is not None:
            # raised BEFORE dispatch so the donated cache stays valid.
            self._fault.check_raise(
                "prefill", tick=self.metrics.ticks, seq_id=seq.seq_id
            )
        n = len(ch.tokens)
        buf = np.zeros((self._chunk_len,), np.int32)
        buf[:n] = ch.tokens
        ctx = (
            self.trace.span(
                "prefill.chunk", PID_ENGINE,
                args={"seq": seq.seq_id, "offset": ch.offset, "tokens": n},
            )
            if self.trace is not None
            else nullcontext()
        )
        with ctx:
            logits, self.cache = self._rung_step_fns(rung)[1](
                self.params, self.cache, np.int32(seq.slot), buf,
                np.int32(ch.offset), np.int32(n),
            )
            if self._telemetry_on and self._sparse_prefill:
                attended = np.asarray(self.cache["_ptel"])
                cands = prefill_block_candidates(
                    self._plan_layouts, ch.offset, n,
                    self.cfg.sparse.prefill_block_q,
                )
                self.metrics.on_prefill_sparsity(attended, cands)
        self._seq_len[seq.slot] = ch.offset + n
        self.metrics.on_prefill(n)
        if ch.is_last:
            self._finish_prefill(seq, logits[None])

    def _prefill_monolithic(self, seq: SeqState):
        """Fallback for models without chunked-prefill support (recurrent /
        local-attention stacks) and prefix-embedding requests: single-shot
        prefill, scattered into the batch slot."""
        if self._fault is not None:
            self._fault.check_raise(
                "prefill", tick=self.metrics.ticks, seq_id=seq.seq_id
            )
        req = seq.req
        tokens = jnp.asarray(seq.prefill_tokens, jnp.int32)[None]
        prefix = (
            jnp.asarray(req.prefix_emb)[None]
            if req.prefix_emb is not None
            else None
        )
        with self._shard_ctx():
            logits, cache1 = self.model.prefill(
                self.params, tokens, prefix, max_context=self.max_context
            )
        slot = seq.slot

        # scatter the single-sequence cache into this batch slot
        def scatter(dst, src):
            if not isinstance(dst, jnp.ndarray) or dst.ndim == 0:
                return dst
            # find the batch axis: prefill cache has batch=1 at the same
            # axis position as the engine cache's max_batch axis.
            for ax in range(dst.ndim):
                if src.shape[ax] == 1 and dst.shape[ax] == self.max_batch:
                    idx = [slice(None)] * dst.ndim
                    idx[ax] = slot
                    return dst.at[tuple(idx)].set(
                        jnp.squeeze(src, axis=ax).astype(dst.dtype)
                    )
            return dst

        # engine-private cache keys (telemetry / selection-emission outputs)
        # don't exist in the single-sequence prefill cache: hold them aside
        # so the tree structures match, then restore.
        cache = dict(self.cache)
        private = {
            k: cache.pop(k) for k in list(cache)
            if k.startswith("_") and k not in cache1
        }
        cache = jax.tree.map(
            scatter, cache, {k: cache1[k] for k in cache},
            is_leaf=lambda x: isinstance(x, jnp.ndarray),
        )
        cache.update(private)
        self.cache = cache
        self._seq_len[slot] = seq.n_prefill
        self.metrics.on_prefill(seq.n_prefill)
        self._finish_prefill(seq, logits)

    def _finish_prefill(self, seq: SeqState, logits: jax.Array):
        """Prompt complete: rebuild the slot's centroid store, publish the
        prompt's pages to the prefix cache, emit the first token.

        The finite gate runs FIRST: poisoned prompt logits must raise
        :class:`SamplerAnomaly` before the refresh / prefix-cache insert
        side effects, so a ladder re-run of the chunk starts from the same
        state the failed attempt saw."""
        if seq.replay:
            # resumed: the first committed token is the next decode input;
            # its sample was already taken in the original run, so the
            # prompt logits are discarded (the remaining replay tokens are
            # drained by _decode_tick, one forced input per tick).
            tok = seq.replay.pop(0)
            self.metrics.on_replay_token(seq.seq_id)
            resumed = True
        else:
            first, fin = self._sample(
                self.key,
                np.asarray([seq.seq_id], np.int32),
                np.asarray([len(seq.req.output)], np.int32),
                logits,
            )
            if not bool(np.asarray(fin)[0]):
                self.metrics.on_sampler_anomaly(1)
                raise SamplerAnomaly([seq.seq_id], detail="prefill logits")
            tok = int(np.asarray(first)[0])
            resumed = False
        if self.scheduler._seq_chunkable(seq):
            if self.model.use_sparse(self.max_context):
                self.cache = self._refresh(
                    self.cache, np.int32(seq.slot)
                )
            if self.prefix_cache is not None:
                tokens = seq.prefill_tokens
                n_pages = len(tokens) // self.pool.page_size
                if n_pages:
                    pages = self.pool.table(seq.seq_id).physical[:n_pages]
                    self.prefix_cache.insert(
                        tokens, pages, self._page_snapshot_fn(seq.slot, n_pages)
                    )
        if not resumed:
            seq.req.output.append(tok)
            self.metrics.on_first_token(seq.seq_id)
            self.metrics.on_decode_token(seq.seq_id)
        self._tokens_buf[seq.slot] = tok
        seq.state = DECODE
        if self._is_finished(seq):
            self._retire(seq)
        else:
            # checkpoint on decode entry: every restorable sequence carries
            # a watermark from its first committed token on.
            self._take_checkpoint(seq)

    def _page_snapshot_fn(self, slot: int, n_pages: int):
        """Lazy host snapshot of one slot's prompt-span KV, sliced per page
        (pulled from device once, only if the insert adds new chunks)."""
        ps = self.pool.page_size
        memo = {}

        def fn(i: int):
            if not memo:
                entry = self.cache["pos0"]
                if entry["k"].ndim == 6:  # paged cache: slice whole pages
                    memo["k"] = np.asarray(entry["k"][:, slot, :, :n_pages])
                    memo["v"] = np.asarray(entry["v"][:, slot, :, :n_pages])
                    memo["paged"] = True
                else:
                    memo["k"] = np.asarray(entry["k"][:, slot, :, : n_pages * ps])
                    memo["v"] = np.asarray(entry["v"][:, slot, :, : n_pages * ps])
                    memo["paged"] = False
            if memo["paged"]:
                return {"k": memo["k"][:, :, i], "v": memo["v"][:, :, i]}
            return {
                "k": memo["k"][:, :, i * ps : (i + 1) * ps],
                "v": memo["v"][:, :, i * ps : (i + 1) * ps],
            }

        return fn

    # -- decode tick -----------------------------------------------------------

    def _is_finished(self, seq: SeqState) -> bool:
        out = seq.req.output
        hit_eos = (
            seq.req.eos_token is not None
            and out
            and out[-1] == seq.req.eos_token
        )
        return len(out) >= seq.req.max_new_tokens or bool(hit_eos)

    def _retire(self, seq: SeqState):
        if self.memory is not None:
            self.memory.forget(seq.seq_id)
        self.scheduler.retire(seq)
        self.slots[seq.slot] = None
        self._seq_len[seq.slot] = 0
        seq.req.done = True
        self.finished.append(seq.req)
        seq.slot = -1

    def _attempt_decode(self, rung: int, active: List[SeqState], res: Dict):
        """One ladder attempt at the batched decode step.  Tokens and the
        finite mask land in ``res`` BEFORE any anomaly raises: at the
        ladder floor the healthy rows still commit while only the poisoned
        sequences go to the failure budget."""
        if self._fault is not None:
            # raised BEFORE dispatch so the donated cache is never
            # invalidated by an injected device error.
            self._fault.check_raise("decode", tick=self.metrics.ticks)
        self.cache = dict(self.cache)
        self.cache["seq_len"] = jnp.asarray(self._seq_len)
        logits, self.cache = self._rung_step_fns(rung)[0](
            self.params, self.cache, jnp.asarray(self._tokens_buf)
        )
        if self._fault is not None:
            rows = self._fault.poison_rows(
                self.metrics.ticks, [(s.seq_id, s.slot) for s in active]
            )
            if rows:
                lg = np.array(logits)
                lg[rows, :] = np.nan
                logits = jnp.asarray(lg)
        sids = np.zeros((self.max_batch,), np.int32)
        poss = np.zeros((self.max_batch,), np.int32)
        for s in active:
            sids[s.slot] = s.seq_id
            poss[s.slot] = len(s.req.output)
        toks, fin = self._sample(self.key, sids, poss, logits)
        res["tokens"] = np.asarray(toks)
        res["finite"] = np.asarray(fin)
        bad = [s.seq_id for s in active if not res["finite"][s.slot]]
        if bad:
            self.metrics.on_sampler_anomaly(len(bad))
            raise SamplerAnomaly(bad)

    def _decode_tick(self) -> int:
        active = [
            s for s in self.slots if s is not None and s.state == DECODE
        ]
        if not active:
            return 0
        mem = self.memory
        if mem is not None:
            # {logical: physical} pages whose bytes sit in the host tier at
            # step launch; a selection overlapping them read poison and the
            # sequence must stall and re-run.
            host_before = {
                s.seq_id: mem.pool.host_resident_logical(s.seq_id)
                for s in active
            }
        res: Dict[str, np.ndarray] = {}
        self._with_ladder(
            lambda exc: (
                [s for s in active if s.seq_id in exc.seq_ids]
                if isinstance(exc, SamplerAnomaly)
                else list(active)
            ),
            lambda rung: self._attempt_decode(rung, active, res),
        )
        if "tokens" not in res:
            # a device fault reached the ladder floor before any attempt
            # produced tokens: every implicated sequence was restored or
            # failed above; there is nothing to commit this tick.
            return len(active)
        nt, fin = res["tokens"], res["finite"]
        if mem is not None:
            sel = np.asarray(self.cache["_sel_pages"])
            pre = np.asarray(self.cache["_pre_pages"])
        if self._telemetry_on:
            # ONE owned copy upfront: np.asarray alone returns a zero-copy
            # view of the device buffer, and every downstream read of that
            # view (fancy indexing, reductions) pays uncached-memory cost —
            # in situ that is several times the price of this 256-byte copy.
            # Everything downstream is deferred off the tick: the metrics
            # aggregate folds lazily at snapshot time, and the per-step
            # trace counters are queued raw and materialized by the
            # recorder's export-time flush hook (_flush_sparsity_counters).
            tel = np.array(self.cache["_telemetry"])     # [L, B, 4] owned
            live_slots = [s.slot for s in active]
            self.metrics.on_sparsity(tel, live_slots, owned=True)
            if self.trace is not None:
                self._tel_pending.append(
                    (self.trace.clock(), tel, live_slots)
                )
        for seq in active:
            slot = seq.slot
            if self.scheduler.running.get(seq.seq_id) is not seq or slot < 0:
                continue    # restored / failed at the ladder floor
            if not fin[slot]:
                continue    # anomalous row (already charged above)
            if mem is not None and not mem.on_step(
                seq,
                np.nonzero(sel[slot])[0],
                np.nonzero(pre[slot])[0],
                host_before[seq.seq_id],
            ):
                # host-tier miss: discard the sampled token, don't advance —
                # next tick re-runs this slot's step byte-identically once
                # the missing pages are promoted.  Only this sequence
                # stalls; the rest of the batch commits below.
                continue
            if seq.replay:
                # resume replay: this step rebuilt one committed token's KV
                # through the decode path (byte-identical by induction); the
                # sampled token is discarded and the next committed token is
                # forced as input.  Once the queue drains, the following
                # step's sample lands at position len(output) with the same
                # fold_in key the original run would have used.
                self._tokens_buf[slot] = seq.replay.pop(0)
                self._seq_len[slot] += 1
                self.metrics.on_replay_token(seq.seq_id)
                continue
            tok = int(nt[slot])
            seq.req.output.append(tok)
            self._tokens_buf[slot] = tok
            self._seq_len[slot] += 1
            self.metrics.on_decode_token(seq.seq_id)
            if self._is_finished(seq):
                self._retire(seq)
            else:
                ck = seq.checkpoint
                if ck is None or (
                    len(seq.req.output) - ck.n_output
                    >= self.resilience.checkpoint_interval
                ):
                    self._take_checkpoint(seq)
        # host lengths are authoritative (the batched step incremented
        # every slot, including ones still prefilling or stalled).
        self.cache = dict(self.cache)
        self.cache["seq_len"] = jnp.asarray(self._seq_len)
        return len(active)

    def step(self) -> int:
        """One engine tick: admit, prefill chunks, decode, retire.
        Returns the number of occupied slots."""
        if self.trace is not None:
            with self.trace.span("engine.tick", PID_ENGINE,
                                 args={"tick": self.metrics.ticks}):
                n = self._step_body()
            self._trace_counters()
            return n
        return self._step_body()

    def _flush_sparsity_counters(self, trace: TraceRecorder):
        """Materialize queued per-step sparsity samples into "sparsity"
        counter events (runs as the recorder's export-time flush hook —
        the reductions and event construction stay off the decode tick)."""
        pending, self._tel_pending = self._tel_pending, []
        for ts, tel, slots in pending:
            per_slot = tel.sum(axis=0, dtype=np.int64)   # [B, 4]
            live = (
                per_slot.sum(axis=0)
                if len(slots) == per_slot.shape[0]
                else per_slot[slots].sum(axis=0)
            )
            budget = max(int(live[BUDGET]), 1)
            trace.counter_at(
                "sparsity",
                {
                    "blocks_attended": int(live[BLOCKS]),
                    "pages_dma": int(live[PAGES]),
                    "forced_blocks": int(live[FORCED]),
                    "budget_util_pct": 100.0 * int(live[BLOCKS]) / budget,
                },
                ts,
                pid=PID_KERNEL,
            )

    def _trace_counters(self):
        """Per-tick counter tracks: pool occupancy, queue depth, HBM/host
        residency (tiered runs).  Counter tracks render as step functions,
        so a sample equal to the previous one is invisible — dedup keeps
        steady-state decode (constant pool/queue) nearly event-free."""
        t = self.trace
        last = self._last_counters
        for name, pid, values in (
            ("pool", PID_MEMORY, (self.pool.used_pages, self.pool.free_pages)),
            ("queue", PID_SCHED,
             (len(self.scheduler.waiting), len(self.scheduler.running))),
            ("resilience", PID_ENGINE,
             (self.metrics.retries,
              sum(self.metrics.degradations.values()),
              len(self.metrics.requests_failed))),
        ) + ((
            ("residency", PID_MEMORY,
             (self.metrics.hbm_resident_pages,
              self.metrics.host_resident_pages)),
        ) if self.memory is not None else ()):
            if last.get(name) != values:
                last[name] = values
                keys = _COUNTER_KEYS[name]
                t.counter(name, dict(zip(keys, values)), pid=pid)

    def _progress_sig(self) -> tuple:
        """Monotone counters that move whenever the engine does useful (or
        at least state-changing) work in a tick; the watchdog compares the
        signature across the tick to detect silent no-progress loops."""
        m = self.metrics
        return (
            m.decode_tokens,
            m.prefill_tokens_computed,
            m.prefix_hit_tokens,
            len(self.finished),
            m.preemptions,
            m.checkpoints_restored,
            m.replayed_tokens,
            len(m.requests_failed),
            self.memory.queue.applied if self.memory is not None else 0,
        )

    def _watchdog_break(self):
        """No-progress ticks hit ``resilience.watchdog_ticks``: force the
        scheduler's preemption victim (farthest effective deadline) out —
        the same ops as the memory starvation breaker — so whatever it is
        pinning frees up.  A no-op when nothing is running (e.g. every
        sequence sits in backoff)."""
        running = [s for s in self.slots if s is not None]
        if not running:
            return
        victim = self.scheduler.choose_victim(running)
        if self.trace is not None:
            self.trace.instant(
                "engine.watchdog", PID_ENGINE,
                args={"victim_seq": victim.seq_id,
                      "idle_ticks": self.resilience.watchdog_ticks},
            )
        self.scheduler.preempt(victim)
        if self.memory is not None:
            self.memory.forget(victim.seq_id)
        self.slots[victim.slot] = None
        self._seq_len[victim.slot] = 0
        victim.slot = -1

    def _step_body(self) -> int:
        self._tick_had_fault = False
        had_work = self.scheduler.has_work
        sig0 = self._progress_sig()
        stuck = self._fault is not None and self._fault.fires(
            "tick_stuck", self.metrics.ticks
        )
        if stuck:
            # injected stuck clock: the whole tick body is skipped — only
            # the idle accounting below runs, which is exactly what the
            # watchdog must catch.
            decoded = 0
        else:
            decoded = self._tick_work()
            if self._rung > 0 and decoded and not self._tick_had_fault:
                # clean decode tick on a degraded rung: count toward
                # re-promotion one rung up.
                self._clean_ticks += 1
                if self._clean_ticks >= self.resilience.repromote_after:
                    self._rung -= 1
                    self._clean_ticks = 0
                    self.metrics.on_repromote(self._ladder[self._rung][0])
        if had_work and self._progress_sig() == sig0:
            self._idle_ticks += 1
            if self._idle_ticks >= self.resilience.watchdog_ticks:
                self.metrics.on_watchdog(self._idle_ticks)
                self._idle_ticks = 0
                self._watchdog_break()
        else:
            self._idle_ticks = 0
        self.metrics.ticks += 1
        return len([s for s in self.slots if s is not None])

    def _tick_work(self) -> int:
        """admit -> prefill chunks -> decode -> retire (one tick's work);
        -> the number of decoding slots stepped."""
        if self.memory is not None:
            # apply staged host->HBM promotions (stall targets first, then
            # predictions into free headroom) and rebuild the demotion
            # shield before anything allocates or reads the cache.
            self.memory.begin_tick()
            # liveness breaker: a stalled sequence whose miss-promotes
            # have failed for consecutive ticks is starved — the other
            # sequences' working-set shields cover the whole HBM budget.
            # prepare_decode can't help (stalled seqs hold their
            # reservation and are excluded from it), so preempt the
            # scheduler's victim (farthest effective deadline) among the
            # starved directly; its freed pages restore room for the rest.
            starved = [
                self.scheduler.running[sid]
                for sid in self.memory.starved_seqs()
                if sid in self.scheduler.running
            ]
            if starved:
                victim = self.scheduler.choose_victim(starved)
                if self.trace is not None:
                    self.trace.instant(
                        "mem.starvation_breaker", PID_MEMORY,
                        args={"victim_seq": victim.seq_id},
                    )
                self.scheduler.preempt(victim)
                self.memory.forget(victim.seq_id)
                self.slots[victim.slot] = None
                self._seq_len[victim.slot] = 0
                victim.slot = -1
        free = [i for i, s in enumerate(self.slots) if s is None]
        plan = self.scheduler.plan_tick(free)
        for adm in plan.admitted:
            if self.trace is not None:
                self.trace.instant(
                    "sched.admit", PID_SCHED,
                    args={"seq": adm.seq.seq_id, "slot": adm.slot,
                          "prefix_tokens": adm.prefix_tokens},
                )
            self._install(adm)
        for ch in plan.chunks:
            self._run_chunk(ch)
        decoding = [
            s for s in self.slots if s is not None and s.state == DECODE
        ]
        if self.memory is not None:
            # a stalled sequence already holds its next-token reservation
            # from the tick it missed on; reserving again would leak span.
            decoding = [
                s for s in decoding if s.seq_id not in self.memory.stalled
            ]
        for seq in self.scheduler.prepare_decode(decoding):
            if self.memory is not None:
                self.memory.forget(seq.seq_id)
            self.slots[seq.slot] = None
            self._seq_len[seq.slot] = 0
            seq.slot = -1
        if self.trace is not None:
            with self.trace.span("engine.decode", PID_ENGINE):
                decoded = self._decode_tick()
        else:
            decoded = self._decode_tick()
        if self.memory is not None:
            self.memory.end_tick()
        return decoded

    def run_until_done(
        self,
        max_ticks: int = 10_000,
        tick_callback: Optional[Callable[["Engine", int], None]] = None,
    ) -> List[Request]:
        """Tick until queue and slots drain; -> the requests retired DURING
        this call, in retirement order (a copy — the engine's cumulative
        record stays in ``self.finished``).  Raises :class:`EngineStalled`
        if ``max_ticks`` elapse with work still pending — a partial result
        must not masquerade as success.  ``tick_callback(engine, tick)``
        fires after every tick (periodic metrics snapshots)."""
        start = len(self.finished)
        for tick in range(max_ticks):
            self.step()
            if tick_callback is not None:
                tick_callback(self, tick)
            if not self.scheduler.has_work:
                break
        else:
            if self.scheduler.has_work:
                raise EngineStalled(
                    f"max_ticks={max_ticks} exhausted with "
                    f"{len(self.scheduler.waiting)} queued and "
                    f"{len(self.scheduler.running)} running requests",
                    diagnostics=self.diagnostics(),
                    retired=list(self.finished[start:]),
                )
        return list(self.finished[start:])
