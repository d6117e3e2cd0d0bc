"""Cost functions against hand arithmetic at both configurations' widths."""
import _paths  # noqa: F401
import dataclasses

import pytest

from costs import fused_decode, model, sparse_prefill
from model import load_config_file, model_config


def _cfg(name, block):
    cfg = model_config(load_config_file(name))
    rows = ((block,) * cfg.n_kv_heads,) * cfg.n_layers
    return dataclasses.replace(cfg, sparse=dataclasses.replace(
        cfg.sparse, block_sizes=rows))


def test_fused_decode_qwen15_4b_one_slot():
    # MHA: g = 1; rank key 256 wide, int4 -> 128 bytes a row
    cfg = _cfg("qwen1.5-4b", 64)
    rows = 157                                    # ceil(10000 / 64)
    head_flops = 2 * rows * 256 * 1 + 4 * 1 * 4096 * 128
    head_bytes = rows * 128 + 2 * 256 * 4 + 2 * 4096 * 128 * 2
    assert head_flops == 2177536 and head_bytes == 2119296
    f, b = fused_decode.call(cfg, 0, [10000])
    assert f == 20 * head_flops
    assert b == 20 * head_bytes + 2 * 20 * 128 * 2


def test_fused_decode_qwen3_8b_budget_caps_selection():
    cfg = _cfg("qwen3-8b", 16)
    # L = 20000: 1250 rows; 4096 selected tokens; g = 4
    head_flops = 2 * 1250 * 256 * 4 + 4 * 4 * 4096 * 128
    head_bytes = 1250 * 128 + 2048 + 2 * 4096 * 128 * 2
    f, b = fused_decode.call(cfg, 3, [20000, 20000])
    assert f == 2 * 8 * head_flops
    assert b == 2 * (8 * head_bytes + 2 * 32 * 128 * 2)
    # a context shorter than the budget attends all of it
    f_short, _ = fused_decode.call(cfg, 0, [1000])
    assert f_short == 8 * (2 * 63 * 256 * 4 + 4 * 4 * 1000 * 128)


def test_sparse_prefill_one_query_block_qwen3_8b():
    cfg = _cfg("qwen3-8b", 64)
    # query block [8192, 8256): causal blocks 0..128 (129); forced: sink 0
    # and blocks 127, 128 reaching into [8128, 8255]; 126 candidates; 64 kept
    attended = (3 + 64) * 64
    head_flops = 2 * 126 * 256 * 4 * 64 + 4 * 4 * 64 * attended * 128
    head_bytes = 126 * (128 + 8) + 2 * attended * 128 * 2
    f, b = sparse_prefill.call(cfg, 0, 8192, 64)
    assert f == 8 * head_flops
    assert b == 8 * head_bytes + 2 * 32 * 64 * 128 * 2


def test_sparse_prefill_short_context_attends_everything():
    cfg = _cfg("qwen1.5-4b", 16)
    # query block [0, 64): 4 causal blocks, all forced, nothing to score
    f, b = sparse_prefill.call(cfg, 0, 0, 64)
    assert f == 20 * (4 * 1 * 64 * 64 * 128)
    assert b == 20 * (2 * 64 * 128 * 2) + 2 * 20 * 64 * 128 * 2


def test_sparse_prefill_chunk_is_sum_of_query_blocks():
    cfg = _cfg("qwen3-8b", 32)
    whole = sparse_prefill.call(cfg, 2, 4096, 256)
    parts = [sparse_prefill.call(cfg, 2, 4096 + 64 * i, 64) for i in range(4)]
    assert whole == tuple(map(sum, zip(*parts)))


def test_model_flops_per_token():
    cfg = _cfg("qwen1.5-4b", 64)
    per_layer = 2560 * 2560 * 2 + 2 * 2560 * 2560 + 3 * 2560 * 6912
    assert model.dense_per_token(cfg) == 2 * per_layer * 10 == 1585971200
    assert model.head(cfg) == 2 * 2560 * 151936
    step = model.decode_step(cfg, [10000])
    attn = sum(fused_decode.call(cfg, l, [10000])[0] for l in range(10))
    assert step == 1585971200 + 2 * 2560 * 151936 + attn
    cfg8 = _cfg("qwen3-8b", 64)
    per_layer8 = 4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 12288
    assert model.dense_per_token(cfg8) == 2 * per_layer8 * 9
    assert model.prefill_chunk(cfg8, 0, 256) == pytest.approx(
        256 * 2 * per_layer8 * 9
        + sum(sparse_prefill.call(cfg8, l, 0, 256)[0] for l in range(9)))
