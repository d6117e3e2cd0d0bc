"""Attention backend registry: one plan/execute API over all paths.

    from repro.backends import get_backend, build_plan

    plan = build_plan(model_cfg, context_len)      # static layouts, cached
    backend = get_backend(model_cfg.sparse.backend)
    store = backend.build_store(keys, plan.layout(l), method, quant)
    out, page_table = backend.decode(q, k, v, store, plan.layout(l), sparse)

Registered backends: ``"dense"`` (full-attention oracle), ``"reference"``
(pure jnp), ``"pallas"`` (interpret off-TPU, Mosaic on TPU).
"""
from repro.backends.base import (
    AttentionBackend,
    AttentionPlan,
    CentroidStore,
    available_backends,
    build_plan,
    get_backend,
    register_backend,
)
from repro.backends.dense import DenseBackend
from repro.backends.pallas import PallasBackend
from repro.backends.reference import ReferenceBackend

register_backend(DenseBackend())
register_backend(ReferenceBackend())
register_backend(PallasBackend())

__all__ = [
    "AttentionBackend",
    "AttentionPlan",
    "CentroidStore",
    "DenseBackend",
    "PallasBackend",
    "ReferenceBackend",
    "available_backends",
    "build_plan",
    "get_backend",
    "register_backend",
]
