"""A configuration with sliding-window and global layers and routed experts,
at a size the CPU holds: how the harness builds, checks and counts it.
Its registry entry is made here; the program has no such model."""
import _paths  # noqa: F401
import copy
import dataclasses
import types

import pytest

from costs import fused_decode, sparse_prefill, window_attention
from costs import model as costs
from model import model_config, sparse_layers
from run import load_reader

D, HD, NQ, NKV, E, K, DFF, W = 256, 128, 4, 2, 8, 2, 128, 64
SPEC = {
    "name": "tiny-mixed", "reference": "dense_decoder", "registry": "tiny-mixed",
    "hidden_size": D, "intermediate_size": 1024, "moe_intermediate_size": DFF,
    "num_attention_heads": NQ, "num_key_value_heads": NKV, "head_dim": HD,
    "num_hidden_layers": 8, "vocab_size": 512, "hidden_act": "silu",
    "qkv_bias": False, "rms_norm_eps": 1e-6, "rope_theta": 5e5,
    "tie_word_embeddings": False, "torch_dtype": "float32",
    "sliding_window": W, "num_experts": E, "num_experts_per_tok": K,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"]
                   + ["sliding_attention"] * 3 + ["full_attention"]
                   + ["sliding_attention"] * 3 + ["full_attention"],
    "mlp_layer_types": ["sparse"] * 12,
    "sparse": {"backend": "reference", "fused_decode": True,
               "sparse_prefill": True, "quant": "int4_asym",
               "token_budget": 256, "page_size": 16, "centroid_method": "quest",
               "candidate_block_sizes": [16, 32, 64], "tau": 0.98,
               "sink_pages": 1, "local_pages": 4, "prefill_block_q": 64,
               "prefill_topk_scale": 1.0},
    "block_sizes": [[16, 64], [32, 16]],
}


def registry():
    from repro.config import ModelConfig, MoEConfig

    return ModelConfig(
        name="tiny-mixed", family="moe", n_layers=12, d_model=D, n_heads=NQ,
        n_kv_heads=NKV, d_ff=DFF, vocab_size=512, head_dim=HD,
        moe=MoEConfig(n_experts=E, experts_per_token=K, capacity_factor=4.0),
        layer_pattern=("local_attn",) * 3 + ("attn",), local_window=W,
        rwkv_head_dim=32)


def build(**changes):
    spec = copy.deepcopy(SPEC)
    spec.update(changes)
    return model_config(spec, registry())


def test_builds_the_registry_entry_with_the_files_fields():
    from repro.config import SparseConfig

    sp = dict(SPEC["sparse"], candidate_block_sizes=(16, 32, 64),
              block_sizes=((16, 64), (32, 16)))
    assert build() == dataclasses.replace(
        registry(), n_layers=8, norm_eps=1e-6, rope_theta=5e5, dtype="float32",
        sparse=SparseConfig(**sp))


def test_without_a_registry_entry_the_file_alone():
    from repro.config import MoEConfig

    spec = copy.deepcopy(SPEC)
    del spec["registry"]
    assert model_config(spec) == dataclasses.replace(
        build(), moe=MoEConfig(n_experts=E, experts_per_token=K),
        rwkv_head_dim=64)


@pytest.mark.parametrize("changes, message", [
    ({"hidden_size": 512}, "hidden_size=512"),
    ({"moe_intermediate_size": 256}, "moe_intermediate_size=256"),
    ({"sliding_window": 128}, "sliding_window=128"),
    ({"num_experts": 16}, "num_experts"),
    ({"layer_types": ["sliding_attention"] * 2 + ["full_attention"] * 2},
     "layer_types"),
    ({"num_hidden_layers": 6}, "split the period"),
    ({"block_sizes": [[16, 64]] * 8}, "8 block-size rows for 2 global"),
    ({"hidden_act": "gelu"}, "hidden_act"),
])
def test_a_file_that_disagrees_stops_with_a_message(changes, message):
    with pytest.raises(ValueError, match=message):
        build(**changes)


def test_a_window_switched_off_is_not_read():
    from model import load_config_file

    spec = load_config_file("qwen3-8b")
    off = dict(spec, sliding_window=32768, use_sliding_window=False)
    assert model_config(off) == model_config(spec)
    with pytest.raises(ValueError, match="sliding_window=32768"):
        model_config(dict(off, use_sliding_window=True))


def test_global_layers_are_the_sparse_ones():
    assert sparse_layers(build()) == (3, 7)


def test_costs_count_active_experts_and_window_attention():
    cfg = build()
    proj = D * NQ * HD * 2 + 2 * D * NKV * HD
    per_token = 2 * 8 * (proj + D * E + K * 3 * D * DFF)
    assert costs.dense_per_token(cfg) == per_token
    # decode: live 50 and 1000 -> the window reads 50 and 64 keys
    lens = [50, 1000]
    win = 4 * NQ * HD * (50 + 64)
    assert window_attention.decode(cfg, lens) == (
        win, 2 * NKV * HD * 2 * 114 + 2 * 2 * NQ * HD * 2)
    assert costs.decode_step(cfg, lens) == (
        2 * (per_token + costs.head(cfg)) + 6 * win
        + fused_decode.call(cfg, 0, lens)[0] + fused_decode.call(cfg, 1, lens)[0])
    # prefill of [0, 128): sum of min(p + 1, 64) = 2080 + 64 * 64 keys;
    # [100, 164) reads keys from 37 on
    assert window_attention.prefill(cfg, 0, 128)[0] == 4 * NQ * HD * 6176
    assert window_attention.prefill(cfg, 100, 64)[1] == (
        2 * NKV * HD * 2 * 127 + 2 * 64 * NQ * HD * 2)


def fake_ctx(cfg, steps):
    trace = types.SimpleNamespace(time_matching=lambda kernel: (0.5, 10),
                                  window_s=10.0)
    return types.SimpleNamespace(
        cfg=cfg, trace=trace, peak_flops=1e14, peak_bytes=1e12, notes={},
        log=types.SimpleNamespace(decode_steps=steps, chunks=[(0, 128)]),
        counted=types.SimpleNamespace(telemetry_pages=48, decode_steps=steps))


def test_sparse_kernels_are_counted_on_the_global_layers_only(monkeypatch):
    cfg = build()
    steps = [[300, 400], [301, 401]]
    calls = []
    monkeypatch.setattr(fused_decode, "call", lambda cfg, i, lens: (
        calls.append(i) or (1e6, 1e6)))
    assert load_reader("fused_decode_roofline").read(fake_ctx(cfg, steps)) == (
        pytest.approx(100.0 * 1e-6 * 10 / 0.5))
    assert calls == [0, 1, 0, 1]
    calls.clear()
    monkeypatch.setattr(sparse_prefill, "call", lambda cfg, i, o, n: (
        calls.append(i) or (1e6, 1e6)))
    assert load_reader("sparse_prefill_roofline").read(fake_ctx(cfg, steps)) == (
        pytest.approx(100.0 * 1e-6 * 10 / 0.5))
    assert calls == [0, 1]
    # 48 pages of 16 tokens over 1402 live tokens x 2 heads x 2 layers
    assert load_reader("attended_fraction").read(fake_ctx(cfg, steps)) == (
        pytest.approx(100.0 * 48 * 16 / (1402 * 2 * 2)))


def test_a_layer_kind_with_no_count_gives_none():
    cfg = dataclasses.replace(build(), layer_pattern=("rwkv", "attn"))
    assert costs.decode_step(cfg, [100]) is None
    assert costs.prefill_chunk(cfg, 0, 64) is None
    for name in ("decode_mfu", "prefill_mfu"):
        ctx = fake_ctx(cfg, [[100]])
        assert load_reader(name).read(ctx) is None
        assert ctx.notes[name] == "no FLOP count for layer kinds rwkv"
