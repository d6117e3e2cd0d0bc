"""A cell small enough for the CPU: the harness end to end, on the
program's pure-jnp backend (or the Pallas kernels in interpret mode)."""
import _paths  # noqa: F401

from run import Cell

#: the metric readers a tiny run reads (every end-to-end metric there is)
END_TO_END = ["decode_tok_s", "prefill_tok_s", "itl_p95_ms", "itl_p50_ms",
              "setup_s"]


def tiny_cell(backend="reference", dtype="float32", limit=0.05, sessions=8):
    conf = {
        "name": "tiny", "reference": "dense_decoder", "hidden_size": 256,
        "intermediate_size": 512, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 128, "num_hidden_layers": 2,
        "vocab_size": 512, "hidden_act": "silu", "qkv_bias": True,
        "rms_norm_eps": 1e-6, "rope_theta": 1e6, "tie_word_embeddings": False,
        "torch_dtype": dtype,
        "sparse": {"backend": backend, "fused_decode": backend == "pallas",
                   "sparse_prefill": True, "quant": "int4_asym",
                   "token_budget": 256, "page_size": 16, "centroid_method": "quest",
                   "candidate_block_sizes": [16, 32, 64], "tau": 0.98,
                   "sink_pages": 1, "local_pages": 4, "prefill_block_q": 64,
                   "prefill_topk_scale": 1.0},
        "block_sizes": [[16, 64], [32, 16]],
    }
    tr = {"loop": "closed", "slots": 2, "max_batch": 2, "max_context": 1024,
          "sessions": sessions, "trace_seconds": 2, "schedule_seed": 0,
          "check_requests": 2, "check_tokens": 32,
          "prompt_tokens": {"dist": "log_uniform", "low": 600, "high": 800},
          "output_tokens": {"dist": "uniform", "low": 24, "high": 48},
          "serve": {"enable_prefix_cache": False}}
    return Cell("tiny", 1, conf, tr, {"max_logit_gap": limit}, END_TO_END, [])


def on_cpu(monkeypatch):
    """Skip the harness's look for a TPU (and its peaks): the rest of a run
    goes as on the chip, with the Pallas kernels left in interpret mode."""
    import jax

    import run

    monkeypatch.setattr(run, "prepare_device", lambda chips: (
        jax.devices()[:chips], {"platform": "cpu", "kind": "cpu", "count": chips},
        {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}))


def plant(monkeypatch, fault):
    """Every engine a run builds gets ``fault(engine)`` planted in it."""
    import repro.serving

    class Broken(repro.serving.Engine):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            fault(self)

    monkeypatch.setattr(repro.serving, "Engine", Broken)
