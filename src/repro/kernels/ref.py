"""Pure-jnp oracles for the Pallas kernels (ground truth for allclose)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_attention_ref(q, k, v, *, causal: bool = True, scale: float = 1.0):
    """q: (B,S,H,dh); k/v: (B,L,Hkv,dh) -> (B,S,H,dh)."""
    B, S, H, dh = q.shape
    L, Hkv = k.shape[1], k.shape[2]
    g = H // Hkv
    qg = q.reshape(B, S, Hkv, g, dh).astype(jnp.float32)
    s = jnp.einsum("bshgd,blhd->bhgsl", qg, k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.arange(L)[None, :] <= jnp.arange(S)[:, None]
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgsl,blhd->bshgd", p, v.astype(jnp.float32))
    return o.reshape(B, S, H, dh).astype(q.dtype)


def decode_attention_ref(q, k_cache, v_cache, kv_valid, *, scale: float,
                         k_scale=None, v_scale=None):
    """q: (B,H,dh); k/v_cache: (B,L,Hkv,dh) [int8 when scales given];
    kv_valid: (B,) valid lengths -> (B,H,dh)."""
    B, H, dh = q.shape
    L, Hkv = k_cache.shape[1], k_cache.shape[2]
    g = H // Hkv
    kf = k_cache.astype(jnp.float32)
    vf = v_cache.astype(jnp.float32)
    if k_scale is not None:
        kf = kf * k_scale[None, None, :, None]
    if v_scale is not None:
        vf = vf * v_scale[None, None, :, None]
    qg = q.reshape(B, Hkv, g, dh).astype(jnp.float32)
    s = jnp.einsum("bhgd,blhd->bhgl", qg, kf) * scale
    valid = jnp.arange(L)[None, None, None, :] < kv_valid[:, None, None, None]
    s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgl,blhd->bhgd", p, vf)
    return o.reshape(B, H, dh).astype(q.dtype)


def gather_pages(pages, page_table):
    """Head-major (n_pages, Hkv, ps, dh) pool + (B, n_pp) table -> dense
    (B, L, Hkv, dh)."""
    B, n_pp = page_table.shape
    Hkv, ps, dh = pages.shape[1:]
    return (pages[page_table].transpose(0, 1, 3, 2, 4)
            .reshape(B, n_pp * ps, Hkv, dh))


def paged_decode_attention_ref(q, k_pages, v_pages, page_table, seq_lens, *,
                               scale: float, k_scale=None, v_scale=None):
    """Oracle for the paged kernel: gather pages densely, then dense ref."""
    kd = gather_pages(k_pages, page_table)
    vd = gather_pages(v_pages, page_table)
    return decode_attention_ref(q, kd, vd, seq_lens, scale=scale,
                                k_scale=k_scale, v_scale=v_scale)


def chunk_prefill_attention_ref(q, k_pages, v_pages, page_table, start,
                                n_valid, *, scale: float, k_scale=None,
                                v_scale=None):
    """Oracle for the chunk-prefill kernel: gather pages densely, causal
    mask by absolute position. q: (B, C, H, dh); start: scalar or (B,);
    n_valid: (B,) total valid tokens including this chunk."""
    B, C, H, dh = q.shape
    kd = gather_pages(k_pages, page_table).astype(jnp.float32)
    vd = gather_pages(v_pages, page_table).astype(jnp.float32)
    if k_scale is not None:
        kd = kd * k_scale[None, None, :, None]
    if v_scale is not None:
        vd = vd * v_scale[None, None, :, None]
    L, Hkv = kd.shape[1], kd.shape[2]
    g = H // Hkv
    start = jnp.broadcast_to(jnp.asarray(start, jnp.int32).reshape(-1), (B,))
    qpos = start[:, None] + jnp.arange(C)[None, :]          # (B, C)
    qpos = jnp.minimum(qpos, n_valid[:, None] - 1)          # clip pad rows
    kpos = jnp.arange(L)
    mask = kpos[None, None, :] <= qpos[:, :, None]          # (B, C, L)
    qg = q.reshape(B, C, Hkv, g, dh).astype(jnp.float32)
    s = jnp.einsum("bchgd,blhd->bhgcl", qg, kd) * scale
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgcl,blhd->bchgd", p, vd)
    return o.reshape(B, C, H, dh).astype(q.dtype)


def spec_verify_attention_ref(q, k_pages, v_pages, page_table, seq_lens,
                              n_fed, *, scale: float, k_scale=None,
                              v_scale=None):
    """Oracle for the speculative-verify kernel (DESIGN.md SS14): row j of
    sequence b attends KV positions <= seq_lens[b] + min(j, n_fed[b]-1)
    — per-sequence window start, per-row causal frontier, padding rows
    clipped to the last real row."""
    seq_lens = jnp.asarray(seq_lens, jnp.int32)
    n_fed = jnp.asarray(n_fed, jnp.int32)
    return chunk_prefill_attention_ref(q, k_pages, v_pages, page_table,
                                       seq_lens, seq_lens + n_fed,
                                       scale=scale, k_scale=k_scale,
                                       v_scale=v_scale)
