"""Distribution-layer tests: sharding rules, param mapping, mesh factory,
kernel partitioning, dry-run machinery and the mesh-sharded serving smoke
(forced-device meshes run via subprocess so the main test session keeps
its single-device view)."""
import json
import os
import subprocess
import sys
import textwrap
from types import SimpleNamespace

import numpy as np
import pytest

from repro.config import MeshPlan, SHAPES_BY_NAME
from repro.configs import get_config
from repro.distributed import params as pshard
from repro.distributed import kernel_partition as kpart
from repro.launch.mesh import derive_mesh_shape, parse_mesh_arg

PLAN = MeshPlan()
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def fake_mesh(**axes):
    """Mesh stand-in for spec-derivation unit tests (axis_names +
    devices.shape are all :mod:`kernel_partition` reads)."""
    return SimpleNamespace(
        axis_names=tuple(axes),
        devices=SimpleNamespace(shape=tuple(axes.values())),
    )


def test_rules_head_alignment():
    sh = SHAPES_BY_NAME["train_4k"]
    r_gemma = pshard.rules_for(get_config("gemma-7b"), sh, PLAN)
    r_nemo = pshard.rules_for(get_config("nemotron-4-340b"), sh, PLAN)
    # gemma (7B) trains pure-FSDP: no TP at all
    assert r_gemma["heads"] is None and r_gemma["mlp"] is None
    assert "model" in r_gemma["batch"]
    # nemotron (340B) keeps head-aligned TP (96 % 16 == 0)
    assert r_nemo["heads"] == "model"
    assert r_nemo["batch"] == ("data",)


def test_rules_rwkv_excluded_from_pure_fsdp():
    sh = SHAPES_BY_NAME["train_4k"]
    r = pshard.rules_for(get_config("rwkv6-3b"), sh, PLAN)
    assert "model" not in (r["batch"] or ()), (
        "token-recurrent stacks must not use pure FSDP (per-timestep "
        "weight re-gather, EXPERIMENTS.md §Perf 2.7)"
    )


def test_rules_decode_gqa_fallback():
    sh = SHAPES_BY_NAME["decode_32k"]
    r = pshard.rules_for(get_config("llama3.2-3b"), sh, PLAN)  # kv=8 < 16
    assert r["kv_heads"] is None and r["head_dim"] == "model"
    r2 = pshard.rules_for(get_config("gemma-7b"), sh, PLAN)    # kv=16
    assert r2["kv_heads"] == "model"


def test_param_logical_mapping():
    cases = [
        ("cycles/pos0/attn/wq/w", 3, (None, "fsdp", "heads")),
        ("cycles/pos0/ffn/down/w", 3, (None, "mlp", "fsdp")),
        ("embed", 2, ("vocab", "fsdp")),
        ("cycles/pos0/ffn/up", 4, (None, "experts", "fsdp", "mlp")),
        ("rest/0/norm1/scale", 1, (None,)),
    ]
    for path, ndim, want in cases:
        got = pshard.logical_axes_for_param(path, ndim)
        assert got == want, (path, got, want)


def test_spec_divisibility_guard():
    import jax
    from jax.sharding import PartitionSpec

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    # 8 kv heads on a 16-way model axis must drop to replication — emulate
    # via explicit sizes using the pure function
    spec = pshard.spec_from_logical(
        mesh, {"kv_heads": "model"}, ("kv_heads",), (8,)
    )
    assert spec == PartitionSpec(None) or spec == PartitionSpec("model")
    # (axis size 1 here always divides; the real guard is exercised in the
    # dry-run subprocess test below)


def test_derive_mesh_shape_adapts_to_device_count():
    # largest model axis dividing the count, capped by model_cap
    assert derive_mesh_shape(8, model_cap=2) == (4, 2)
    assert derive_mesh_shape(8) == (1, 8)
    assert derive_mesh_shape(8, model_cap=3) == (4, 2)   # 3 doesn't divide 8
    assert derive_mesh_shape(1, model_cap=16) == (1, 1)
    assert derive_mesh_shape(6, model_cap=4) == (2, 3)
    assert derive_mesh_shape(512, model_cap=16) == (32, 16)
    # multi-pod splits a leading pod axis of 2 when possible
    assert derive_mesh_shape(512, model_cap=16, multi_pod=True) == (2, 16, 16)
    assert derive_mesh_shape(7, model_cap=16, multi_pod=True) == (1, 1, 7)


def test_parse_mesh_arg():
    assert parse_mesh_arg("4,2") == (4, 2)
    assert parse_mesh_arg(" 1 , 8 ") == (1, 8)
    with pytest.raises(ValueError):
        parse_mesh_arg("4")
    with pytest.raises(ValueError):
        parse_mesh_arg("2,2,2")


def test_shard_axes_divisibility_and_gqa_degradation():
    mesh = fake_mesh(data=4, model=2)
    rules = kpart.serving_rules()
    # batch 8 over data=4, 2 kv heads over model=2
    assert kpart.shard_axes(mesh, rules, 8, 2) == ("data", "model")
    # batch 1 can't shard; kv heads still do
    assert kpart.shard_axes(mesh, rules, 1, 2) == (None, "model")
    # GQA degradation: n_kv < model axis -> head replication
    mesh24 = fake_mesh(data=2, model=4)
    assert kpart.shard_axes(mesh24, rules, 8, 2) == ("data", None)
    # degenerate (1, 1) mesh -> fully replicated (single-device semantics)
    assert kpart.shard_axes(fake_mesh(data=1, model=1), rules, 8, 2) == (
        None, None,
    )


def test_layout_and_store_spec_trees():
    from jax.sharding import PartitionSpec as P

    from repro.backends import CentroidStore, build_plan

    cfg = get_config("llama3.2-3b")
    la = build_plan(cfg, 32768).stacked.layer(0)
    specs = kpart._layout_specs(la, "model")
    assert specs.row_offsets == P("model")
    assert specs.scatter_rows == P("model", None)
    assert specs.tile_head == P(None), "flat-row axis must stay whole"
    # decode store: per-head affine params shard with the heads
    n_kv = cfg.n_kv_heads
    store = CentroidStore(
        np.zeros((2, la.total_rows, 8), np.uint8),
        np.ones((2, n_kv, 16), np.float32),
        np.zeros((2, n_kv, 16), np.float32),
        4, False,
    )
    sspec = kpart._store_spec_tree(
        store, "data", "model", head_aligned_params=True
    )
    assert sspec.codes == P("data", None, None)
    assert sspec.scale == P("data", "model", None)
    # prefill score segment: per-ROW params ride the (whole) row axis
    score = CentroidStore(
        np.zeros((2, la.total_rows, 8), np.uint8),
        np.ones((2, la.total_rows, 1), np.float32),
        np.zeros((2, la.total_rows, 1), np.float32),
        4, False,
    )
    pspec = kpart._store_spec_tree(
        score, "data", "model", head_aligned_params=False
    )
    assert pspec.scale == P("data", None, None)


DRYRUN_SNIPPET = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, json
    import jax.numpy as jnp
    from repro.config import MeshPlan, ShapeConfig
    from repro.configs import get_config, smoke_variant
    from repro.distributed import params as pshard
    from repro.distributed.sharding import sharding_rules
    from repro.launch.specs import build_cell
    import dataclasses

    cfg = smoke_variant(get_config("llama3.2-3b"))
    shape = ShapeConfig("t", 256, 8, "%KIND%")
    mesh = jax.make_mesh(
        (4, 2), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
    )
    plan = MeshPlan()
    rules = pshard.rules_for(cfg, shape, plan)
    cell = build_cell(cfg, shape, plan)
    ins = [
        pshard.tree_shardings(
            t, mesh, rules,
            kind=("param" if k in ("param", "opt") else "cache"),
        )
        for t, k in zip(cell["args"], cell["kinds"])
    ]
    with mesh, sharding_rules(mesh, rules):
        compiled = (
            jax.jit(cell["fn"], in_shardings=tuple(ins))
            .lower(*cell["args"]).compile()
        )
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    print(json.dumps({"ok": True, "flops": cost.get("flops", 0)}))
    """
)


@pytest.mark.distributed
@pytest.mark.parametrize("kind", ["train", "decode", "prefill"])
def test_dryrun_lowers_on_forced_mesh(kind):
    """The dry-run machinery (specs -> shardings -> lower -> compile) works
    end-to-end on a small forced-device mesh for every step kind."""
    code = DRYRUN_SNIPPET.replace("%KIND%", kind)
    env = dict(os.environ)
    env["PYTHONPATH"] = "src:" + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=560, cwd=REPO_ROOT,
        env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["ok"] and res["flops"] > 0


MESH_SERVE_SNIPPET = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, json
    import jax, numpy as np
    from repro.config import ServeConfig
    from repro.configs import get_config, smoke_variant
    from repro.launch.mesh import make_serving_mesh
    from repro.models import Transformer
    from repro.serving import Engine, Request

    cfg = smoke_variant(get_config("llama3.2-3b"))
    cfg = dataclasses.replace(cfg, sparse=dataclasses.replace(
        cfg.sparse, backend="pallas", sparse_prefill=True, fused_decode=True))
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))
    assert model.use_sparse(256), "smoke config must hit the sparse path"

    def run(mesh):
        eng = Engine(cfg, params, ServeConfig(
            max_batch=4, max_context=256, prefill_chunk=64,
            prefill_tokens_per_tick=128, pool_pages=%POOL%), mesh=mesh)
        rng = np.random.default_rng(0)
        prefix = rng.integers(0, cfg.vocab_size, 64).astype(np.int32)
        for rid in range(4):
            body = np.concatenate(
                [prefix,
                 rng.integers(0, cfg.vocab_size, 64).astype(np.int32)]
            )
            eng.submit(Request(rid, body, max_new_tokens=12))
        done = eng.run_until_done(max_ticks=600)
        eng.pool.assert_consistent()
        return eng, {r.req_id: list(r.output) for r in done}

    eng_s, single = run(None)
    mesh = make_serving_mesh((4, 2), n_kv_heads=cfg.n_kv_heads)
    eng_m, sharded = run(mesh)
    k = eng_m.cache["pos0"]["k"]
    shard = k.addressable_shards[0].data.shape
    print(json.dumps({
        "ok": True,
        "identical": single == sharded,
        "n_requests": len(sharded),
        "n_tokens": sum(len(v) for v in sharded.values()),
        "prefix_hits": eng_m.metrics.prefix_hit_tokens,
        "preemptions": eng_m.metrics.preemptions,
        "kv_shard_batch": shard[1],
        "kv_shard_heads": shard[2],
        "spec": str(k.sharding.spec),
    }))
    """
)


@pytest.mark.distributed
def test_mesh_sharded_serving_token_identical():
    """Acceptance oracle for the mesh-native serving path: on a forced
    8-device host under a ``(4, 2)`` ``(data, model)`` mesh, the engine
    (shard_map'd fused decode + sparse prefill, prefix sharing, preemption
    pressure) produces token-identical output to the single-device path,
    with the KV pool genuinely sharded over both axes."""
    code = MESH_SERVE_SNIPPET.replace("%POOL%", "17")
    env = dict(os.environ)
    env["PYTHONPATH"] = "src:" + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=560, cwd=REPO_ROOT,
        env=env,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["ok"]
    assert res["identical"], "sharded serving diverged from single-device"
    assert res["n_requests"] == 4 and res["n_tokens"] == 4 * 12
    assert res["prefix_hits"] > 0, "prefix sharing must engage"
    assert res["preemptions"] >= 1, "pool pressure must force a preemption"
    # the KV pool must genuinely split: batch 4 -> 1 per device over the
    # data axis, kv heads 2 -> 1 over the model axis.
    assert res["kv_shard_batch"] == 1, res
    assert res["kv_shard_heads"] == 1, res
    assert "data" in res["spec"] and "model" in res["spec"]
