"""FlashAttention-2-style Pallas TPU kernel (prefill/training attention).

Blocked (q_block x kv_block) online-softmax attention with explicit
BlockSpec VMEM tiling, GQA-aware, with TRUE causal block skipping (the
strictly-upper kv blocks are not computed — unlike the XLA fallback path,
which only masks them; see DESIGN.md SS7 and EXPERIMENTS.md SSPerf).

Layout: inputs are transposed to (B, heads, seq, head_dim) so the MXU
contraction dims (head_dim, kv block) are trailing and 128-aligned.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, block_q: int, block_kv: int,
            n_kv: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: skip blocks strictly above the diagonal band
    run = (ki * block_kv <= qi * block_q + block_q - 1) if causal else True

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, dh)
        k = k_ref[0, 0].astype(jnp.float32)            # (bkv, dh)
        v = v_ref[0, 0].astype(jnp.float32)            # (bkv, dh)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            kpos = ki * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new[:, None]))
        corr = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
        m_scr[...] = m_new
        l_scr[...] = l_prev * corr + p.sum(axis=-1)
        acc_scr[...] = (acc_scr[...] * corr[:, None]
                        + jax.lax.dot(p.astype(v.dtype), v,
                                      preferred_element_type=jnp.float32))

    @pl.when(ki == n_kv - 1)
    def _out():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, scale: float = None,
                    block_q: int = 256, block_kv: int = 512,
                    interpret: bool = False):
    """q: (B,S,H,dh); k/v: (B,L,Hkv,dh) -> (B,S,H,dh). GQA via H//Hkv."""
    B, S, H, dh = q.shape
    L, Hkv = k.shape[1], k.shape[2]
    group = H // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    block_q = min(block_q, S)
    block_kv = min(block_kv, L)
    n_q = -(-S // block_q)
    n_kv = -(-L // block_kv)
    assert S % block_q == 0 and L % block_kv == 0, (
        "pad seq lens to block multiples before calling the kernel")

    qt = q.transpose(0, 2, 1, 3)                       # (B, H, S, dh)
    kt = k.transpose(0, 2, 1, 3)                       # (B, Hkv, L, dh)
    vt = v.transpose(0, 2, 1, 3)

    grid = (B, H, n_q, n_kv)
    kern = functools.partial(_kernel, scale=scale, causal=causal,
                             block_q=block_q, block_kv=block_kv, n_kv=n_kv)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_kv, dh),
                         lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, dh),
                         lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dh),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)                   # (B, S, H, dh)
