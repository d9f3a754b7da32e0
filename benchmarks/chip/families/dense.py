"""Dense decoder-only transformers as Qwen2 and Llama publish them:
grouped-query attention with optional q/k/v biases, one SwiGLU MLP a
layer, tied or untied embeddings. The counts are ``counts.py``'s, the
weights ``weights.py``'s and the reference ``reference.py``'s."""
from __future__ import annotations

from benchmarks.chip.counts import (Dims, decode_flops,  # noqa: F401
                                    decode_step_bytes, kv_bytes_per_token,
                                    prefill_flops)
from benchmarks.chip.reference import logits, padded_len  # noqa: F401
from benchmarks.chip.weights import served_params  # noqa: F401


def dims(cfg: dict) -> Dims:
    return Dims.of(cfg)


def arch_config(cfg: dict):
    """The engine's ``ArchConfig`` for a configuration file."""
    from repro.configs.base import ArchConfig
    m = dims(cfg)
    return ArchConfig(name=cfg["name"], family="dense", n_layers=m.layers,
                      d_model=m.d, n_heads=m.heads, n_kv_heads=m.kv_heads,
                      head_dim=m.head_dim, d_ff=m.d_ff, vocab=m.vocab,
                      qkv_bias=m.qkv_bias, gated_mlp=True,
                      tie_embeddings=m.tied,
                      max_context=cfg["max_position_embeddings"])
