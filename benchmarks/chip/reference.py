"""Plain reference of the served models, in float32.

A decoder-only transformer as the published configurations describe it
(Qwen2 and Llama architectures): RMSNorm, rotary embeddings on the
rotate-half convention, grouped-query attention with optional q/k/v
biases, a SwiGLU MLP, tied or untied output embeddings. Plain
``jax.numpy`` under ``jax.default_matmul_precision("highest")``, with no
cache, paging or batching. It imports nothing of the program under test:
the weights are drawn again from the seed (``weights.py``) one layer at
a time and mapped to the published form here.

``logits`` runs one sequence layer by layer, attention in query blocks,
so that a long context fits beside nothing else on one chip. With
``quant="fp8"`` every matmul's weight (per output channel) and input
(per row) is rounded to float8 e4m3 first: the control, one precision
below the bfloat16 the configurations state.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import weights
from benchmarks.chip.counts import Dims

Q_BLOCK = 512          # query rows per attention block
ROW_BLOCK = 2048       # rows per MLP block
_FP8_MAX = 448.0


def _fp8(x, axis):
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30)
    s = s / _FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant):
    if quant == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return x @ w


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x: (T, heads, hd); rotate-half convention."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def published_layer(m: Dims, key, layer):
    """One layer in the published form, float32."""
    lv = {n: v.astype(jnp.float32)
          for n, v in weights.layer_leaves(m, key, layer).items()}
    lv["ln1"] = 1.0 + lv["ln1"]
    lv["ln2"] = 1.0 + lv["ln2"]
    lv["gate"], lv["up"] = lv["up"][:, :m.d_ff], lv["up"][:, m.d_ff:]
    return lv


def published_globals(m: Dims, key):
    g = {n: v.astype(jnp.float32)
         for n, v in weights.global_leaves(m, key).items()}
    root = math.sqrt(m.d)
    g["emb"] = g["emb"] * root
    g["final_norm"] = 1.0 + g["final_norm"]
    if m.tied:
        g["final_norm"] = g["final_norm"] / root
        g["head"] = g["emb"].T
    return g


@partial(jax.jit, static_argnums=0)
def _embed(m: Dims, key, tokens):
    emb = weights.global_leaves(m, key)["emb"].astype(jnp.float32)
    return emb[tokens] * math.sqrt(m.d)


@partial(jax.jit, static_argnums=(0, 1, 2))
def _layer(m: Dims, cfg: tuple, quant, key, layer, x, n_valid):
    theta, eps = cfg
    w = published_layer(m, key, layer)
    T = x.shape[0]
    g = m.heads // m.kv_heads
    pos = jnp.arange(T)
    h = _rms(x, w["ln1"], eps)
    q = _mm(h, w["wq"], quant) + w.get("bq", 0.0)
    k = _mm(h, w["wk"], quant) + w.get("bk", 0.0)
    v = _mm(h, w["wv"], quant) + w.get("bv", 0.0)
    q = _rope(q.reshape(T, m.heads, m.head_dim), pos, theta)
    k = _rope(k.reshape(T, m.kv_heads, m.head_dim), pos, theta)
    v = v.reshape(T, m.kv_heads, m.head_dim)
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)

    def attend(qb_i):
        qb, i = qb_i
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(m.head_dim)
        ok = (pos[None, :] <= qpos[:, None]) & (pos[None, :] < n_valid)
        s = jnp.where(ok[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    nb = T // Q_BLOCK
    o = jax.lax.map(attend, (q.reshape(nb, Q_BLOCK, m.heads, m.head_dim),
                             jnp.arange(nb)))
    x = x + _mm(o.reshape(T, m.heads * m.head_dim), w["wo"], quant)

    def mlp(xb):
        hb = _rms(xb, w["ln2"], eps)
        a = jax.nn.silu(_mm(hb, w["gate"], quant)) * _mm(hb, w["up"], quant)
        return xb + _mm(a, w["down"], quant)

    rb = ROW_BLOCK if T % ROW_BLOCK == 0 else Q_BLOCK
    return jax.lax.map(mlp, x.reshape(T // rb, rb, m.d)).reshape(T, m.d)


@partial(jax.jit, static_argnums=(0, 1, 2))
def _head(m: Dims, eps: float, quant, key, x_rows):
    g = published_globals(m, key)
    return _mm(_rms(x_rows, g["final_norm"], eps), g["head"], quant)


def padded_len(n: int) -> int:
    """Sequence lengths are padded to whole attention and MLP blocks, so
    one compiled layer serves every sequence of a cell."""
    blk = ROW_BLOCK if n > ROW_BLOCK else Q_BLOCK
    return -(-n // blk) * blk


def logits(cfg: dict, seed: int, tokens, rows, *, pad_to: int,
           quant: str = ""):
    """Float32 logits of one sequence at positions ``rows``.

    tokens: the sequence (prompt followed by served tokens); rows: the
    positions whose next-token logits are wanted; pad_to: the padded
    length (``padded_len`` of the cell's longest sequence)."""
    m = Dims.of(cfg)
    theta, eps = float(cfg["rope_theta"]), float(cfg["rms_norm_eps"])
    key = weights.seed_key(seed)
    n = len(tokens)
    toks = np.zeros((pad_to,), np.int32)
    toks[:n] = tokens
    with jax.default_matmul_precision("highest"):
        x = _embed(m, key, jnp.asarray(toks))
        for layer in range(m.layers):
            x = _layer(m, (theta, eps), quant, key, jnp.int32(layer), x,
                       jnp.int32(n))
        out = _head(m, eps, quant, key, x[jnp.asarray(rows)])
    return np.asarray(out)
