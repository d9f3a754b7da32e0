"""Operations and bytes the served model needs, from its shapes alone.

These are the yardstick of the roofline and utilization metrics: what the
algorithm needs, not what one implementation happens to do. A prefill
chunk needs its tokens' matmuls and causal attention at each token's
position; a decode step needs every weight read once, each active
sequence's live KV read once and the new token's KV written. Logits are
needed only where a token is chosen: the last prompt token and every
decode step.

``Dims`` is built from a configuration file of ``configs/`` (the keys of
the model's published ``config.json``).
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    qkv_bias: bool
    tied: bool
    dtype_bytes: int = 2

    @classmethod
    def of(cls, cfg: dict) -> "Dims":
        heads = cfg["num_attention_heads"]
        return cls(layers=cfg["num_hidden_layers"], d=cfg["hidden_size"],
                   heads=heads, kv_heads=cfg["num_key_value_heads"],
                   head_dim=cfg.get("head_dim", cfg["hidden_size"] // heads),
                   d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
                   qkv_bias=cfg["qkv_bias"],
                   tied=cfg["tie_word_embeddings"])


def layer_matmul_params(m: Dims) -> int:
    """Weights of one layer's matmuls: q, k, v, o, gate, up, down."""
    q_o = 2 * m.d * m.heads * m.head_dim
    k_v = 2 * m.d * m.kv_heads * m.head_dim
    return q_o + k_v + 3 * m.d * m.d_ff


def layer_params(m: Dims) -> int:
    bias = (m.heads + 2 * m.kv_heads) * m.head_dim if m.qkv_bias else 0
    return layer_matmul_params(m) + bias + 2 * m.d


def n_params(m: Dims) -> int:
    """Every parameter: embeddings, an untied head, layers, final norm."""
    emb = m.vocab * m.d * (1 if m.tied else 2)
    return emb + m.layers * layer_params(m) + m.d


def kv_bytes_per_token(m: Dims) -> int:
    return 2 * m.layers * m.kv_heads * m.head_dim * m.dtype_bytes


def weight_bytes_per_step(m: Dims) -> int:
    """Weights a decode step reads: every layer, the final norm and the
    output head (the tied embedding table, or the untied head; an untied
    input table is read a row per token, which is left out)."""
    return m.dtype_bytes * (m.layers * layer_params(m) + m.d + m.vocab * m.d)


def _attn_flops(m: Dims, n_ctx: int) -> int:
    """Scores and weighted values of one query over ``n_ctx`` keys."""
    return 4 * m.layers * m.heads * m.head_dim * n_ctx


def _head_flops(m: Dims) -> int:
    return 2 * m.d * m.vocab


def prefill_flops(m: Dims, start: int, end: int, last: bool) -> int:
    """Prompt positions ``[start, end)`` of one sequence, each attending
    causally over itself and every earlier position; ``last`` adds the
    logits of the final prompt token."""
    n = end - start
    ctx = (end * (end + 1) - start * (start + 1)) // 2   # sum of p + 1
    flops = 2 * m.layers * layer_matmul_params(m) * n
    flops += 4 * m.layers * m.heads * m.head_dim * ctx
    return flops + (_head_flops(m) if last else 0)


def decode_flops(m: Dims, n_ctx: int) -> int:
    """One decode token whose query attends over ``n_ctx`` cached
    positions (its own included)."""
    return (2 * m.layers * layer_matmul_params(m) + _attn_flops(m, n_ctx)
            + _head_flops(m))


def decode_step_bytes(m: Dims, contexts) -> int:
    """One fused decode step over active sequences whose queries attend
    over ``contexts`` positions: weights once, each live KV read once, one
    new KV row written per sequence."""
    kv = kv_bytes_per_token(m)
    return weight_bytes_per_step(m) + sum(contexts) * kv + len(contexts) * kv
