"""The two committed configurations read as before the harness learned
window layers and routed experts: the same ``ModelConfig``, reference
weights, FLOP counts and per-layer readings.  Every constant below was
recorded from the harness as it stood before that change, on these inputs."""
import _paths  # noqa: F401
import dataclasses
import hashlib
import types

import numpy as np
import pytest

from costs import model as costs
from model import init_params, load_config_file, model_config
from references import dense_decoder
from run import load_reader
from tiny import tiny_cell

DENSE = dict(family="dense", activation="swiglu", tie_embeddings=False,
             dtype="bfloat16", moe=None, layer_pattern=("attn",),
             local_window=2048, rwkv_head_dim=64, frontend=None,
             n_prefix_embeddings=0, head_dim=128, vocab_size=151936,
             norm_eps=1e-06)
FIELDS = {
    "qwen1.5-4b": dict(DENSE, name="qwen1.5-4b", n_layers=10, d_model=2560,
                       n_heads=20, n_kv_heads=20, d_ff=6912, qkv_bias=True,
                       rope_theta=5000000.0),
    "qwen3-8b": dict(DENSE, name="qwen3-8b", n_layers=9, d_model=4096,
                     n_heads=32, n_kv_heads=8, d_ff=12288, qkv_bias=False,
                     rope_theta=1000000.0),
}
#: sha256 of ``repr(cfg.sparse)``: the file's settings and block sizes
SPARSE_SHA = {
    "qwen1.5-4b": "4b664f43ab4dbbfa59d6a04cac42cc05ba8df2676bc09a7cf70909701bb5e978",
    "qwen3-8b": "1b0f451221db5101b182c893e61d8526cd25ef571d561316de096e7a4e43c6fc",
}

LIVE = ([10000, 12000, 9000], [16384, 1, 4096, 4097], [17, 300, 24000, 8191])
CHUNKS = ([(0, 256), (8192, 256)], [(256, 256), (24448, 256), (4000, 100)],
          [(12000, 256)])
DECODE_STEP = {
    "qwen1.5-4b": [8492636160, 10827182080, 10476513280],
    "qwen3-8b": [16164102144, 20840628224, 20334141440],
}
PREFILL_CHUNK = {
    "qwen1.5-4b": [[410246348800, 527275327488],
                   [417226752000, 546418393088, 202154291200], [532350566400]],
    "qwen3-8b": [[895158190080, 1063321337856],
                 [905198174208, 1090088337408, 409934536704], [1070490451968]],
}
READERS = ("fused_decode_roofline", "sparse_prefill_roofline",
           "attended_fraction", "decode_mfu", "prefill_mfu")
READINGS = {
    "qwen1.5-4b": [
        (45.745497602668316, 12.587967956163077, 11.757714285714286,
         0.0011650516170109908, 0.048216750563827224, "memory", "memory"),
        (39.93149686808224, 12.98609221592419, 15.06928487511634,
         0.0013822912481549485, 0.05995707837872032, "memory", "memory"),
        (24.558290730648455, 24.328419442045462, 15.117383251955367,
         0.0013225139401046087, 0.027378795735423452, "memory", "memory"),
    ],
    "qwen3-8b": [
        (18.29639891775393, 5.778734164115376, 32.66031746031746,
         0.0022172931808947796, 0.10072462458333976, "memory", "compute"),
        (15.977297274641447, 6.1490892313256875, 41.85912465310094,
         0.0026654117678037844, 0.12370054610031939, "memory", "compute"),
        (9.828048703279055, 11.092752118635909, 41.99273125543157,
         0.002579334340127238, 0.05505533622205422, "memory", "compute"),
    ],
}
#: sha256 over the tiny cell's reference weights, seed 2**33 + 5
TINY_WEIGHTS_SHA = "129b65bce7ac21d1ffcf64e4975bda9f9747518d73e9ef83879aceef99f716ef"

NAMES = list(FIELDS)


@pytest.mark.parametrize("name", NAMES)
def test_model_config_is_the_parents(name):
    from repro.config import ModelConfig, SparseConfig

    cfg = model_config(load_config_file(name))
    assert dataclasses.replace(cfg, sparse=SparseConfig()) == ModelConfig(
        **FIELDS[name])
    assert hashlib.sha256(repr(cfg.sparse).encode()).hexdigest() == SPARSE_SHA[name]


def test_reference_weights_are_the_parents_bit_for_bit():
    cfg = model_config(tiny_cell().config)
    w = dense_decoder.weights(init_params(cfg, 2**33 + 5), cfg)
    h = hashlib.sha256()

    def feed(a):
        a = np.asarray(a)
        h.update(str((a.dtype, a.shape)).encode())
        h.update(a.tobytes())

    for key in ("embed", "final_norm", "lm_head"):
        feed(w[key])
    for p in w["layers"]:
        for k in sorted(p):
            h.update(k.encode())
            feed(p[k])
    assert h.hexdigest() == TINY_WEIGHTS_SHA


@pytest.mark.parametrize("name", NAMES)
def test_model_flops_are_the_parents(name):
    cfg = model_config(load_config_file(name))
    assert [costs.decode_step(cfg, l) for l in LIVE] == DECODE_STEP[name]
    assert [[costs.prefill_chunk(cfg, o, n) for o, n in c]
            for c in CHUNKS] == PREFILL_CHUNK[name]


def fake_ctx(cfg, lens, chunks, pages):
    """A traced stretch of four decode steps (one empty) and ``chunks``."""
    steps = [lens, lens[:2], [], lens[::-1]]
    trace = types.SimpleNamespace(
        time_matching=lambda kernel: (0.0123 * (1 + len(kernel) % 3), 40),
        window_s=9.87)
    return types.SimpleNamespace(
        cfg=cfg, trace=trace, peak_flops=1.97e14, peak_bytes=8.19e11, notes={},
        log=types.SimpleNamespace(decode_steps=steps, chunks=chunks),
        counted=types.SimpleNamespace(telemetry_pages=pages, decode_steps=steps))


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("case", range(3))
def test_readers_give_the_parents_numbers(name, case):
    cfg = model_config(load_config_file(name))
    ctx = fake_ctx(cfg, LIVE[case], CHUNKS[case], 123456 + case)
    got = tuple(load_reader(m).read(ctx) for m in READERS)
    want = READINGS[name][case]
    assert got == want[:5]
    assert (ctx.notes["fused_decode_bound"],
            ctx.notes["sparse_prefill_bound"]) == want[5:]
