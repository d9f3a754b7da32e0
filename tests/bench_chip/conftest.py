"""Shared pieces of the benchmark's tests: the repo root on the import
path (for ``benchmarks.chip``) and a tiny cell that runs on the CPU."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def tiny_config(tied: bool = True) -> dict:
    """A configuration file's keys at a size the CPU serves in seconds
    (head_dim 32 keeps the engine on its XLA path)."""
    return {"name": "tiny", "family": "dense", "hidden_size": 128, "intermediate_size": 256,
            "max_position_embeddings": 4096, "num_attention_heads": 4,
            "num_hidden_layers": 4, "num_key_value_heads": 2,
            "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
            "tie_word_embeddings": tied, "vocab_size": 8192,
            "qkv_bias": tied}


# 4 requests on 2 shared documents, 100 tokens out each: 400 served
# tokens, enough near-ties for the check's readings to mean something
TINY_MIX = {"wave": 4,
            "docs": {"count": 2, "dist": "loguniform", "min": 40, "max": 90},
            "prompt": {"dist": "uniform", "min": 3, "max": 20},
            "output": 100}


def tiny_cell(limit: float, *, tied: bool = True, per_layer=()):
    from benchmarks.chip import harness
    e2e = [{"name": n, "unit": "u"}
           for n in ("tok_s", "ttft_p90_s", "tpot_p90_ms", "setup_s")]
    return harness.Cell(
        name="tiny", chips=1, config=tiny_config(tied), mix=dict(TINY_MIX),
        sizes={"max_batch": 4, "max_len": 256, "n_pages": 80,
               "prefill_budget": 64,
               "check": {"sample_tokens": 400, "max_logit_gap": limit}},
        end_to_end=e2e, per_layer=[{"name": n, "unit": "u"}
                                   for n in per_layer])

