"""Decode-attention Pallas TPU kernel — the paper's hot memory-bound kernel.

One new token per sequence attends to a long KV cache: a GEMV chain with
O(1) arithmetic intensity (the memory-wall regime of paper Sec. I). The
kernel streams KV blocks HBM->VMEM (BlockSpec tiling = the paper's
"hierarchical tiling towards on-chip registers") and supports an
**int8-quantized KV** variant with per-kv-head scales: the TPU-native
analogue of the paper's "restrict Q/K/V traffic to the fast tier" — it
halves the dominant traffic term instead of adding a physical tier.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _online_softmax_step(q, k, v, valid, base_pos, scale,
                         m_scr, l_scr, acc_scr):
    """One flash-attention block update against KV rows [base_pos, +len(k)).

    q: (rows, dh) f32; k/v: (bkv, dh) f32 (already dequantized); ``valid``
    masks KV at absolute position >= valid — a scalar for a shared limit or
    a (rows, 1) array for per-row (causal) limits. The running max and sum
    live in (rows, 1) scratch: Mosaic lays out 2-D tiles, not 1-D vectors.
    Shared by the dense-cache decode, paged decode, and chunk-prefill
    kernels."""
    bkv = k.shape[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kpos = base_pos + jax.lax.broadcasted_iota(
        jnp.int32, (q.shape[0], bkv), 1)
    s = jnp.where(kpos < valid, s, NEG_INF)
    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new))
    corr = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
    m_scr[...] = m_new
    l_scr[...] = l_prev * corr + p.sum(axis=-1, keepdims=True)
    acc_scr[...] = (acc_scr[...] * corr
                    + jax.lax.dot(p.astype(jnp.float32), v,
                                  preferred_element_type=jnp.float32))


def _init_scratch(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _finalize(o_ref, l_scr, acc_scr):
    l = jnp.maximum(l_scr[...], 1e-30)
    o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _scratch(rows: int, dh: int):
    """Running max, running sum and accumulator of one (rows, dh) q block."""
    return [pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, dh), jnp.float32)]


def _kernel(valid_ref, ksc_ref, vsc_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale: float, block_kv: int,
            n_kv: int, quantized: bool):
    b = pl.program_id(0)
    h = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        _init_scratch(m_scr, l_scr, acc_scr)

    valid = valid_ref[b]
    run = ki * block_kv < valid

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)            # (group, dh)
        k = k_ref[0, 0].astype(jnp.float32)            # (bkv, dh)
        v = v_ref[0, 0].astype(jnp.float32)
        if quantized:
            k = k * ksc_ref[h]
            v = v * vsc_ref[h]
        _online_softmax_step(q, k, v, valid, ki * block_kv, scale,
                             m_scr, l_scr, acc_scr)

    @pl.when(ki == n_kv - 1)
    def _out():
        _finalize(o_ref, l_scr, acc_scr)


# whole-array SMEM operand (lengths, per-head scales): Mosaic accepts a
# rank-1 SMEM block only at full size, so kernels index it by program id
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def decode_attention(q, k_cache, v_cache, kv_valid, *, scale: float = None,
                     k_scale=None, v_scale=None, block_kv: int = 512,
                     interpret: bool = False):
    """q: (B,H,dh); k/v_cache: (B,L,Hkv,dh) (int8 when scales given);
    kv_valid: (B,) int32 -> (B,H,dh)."""
    B, H, dh = q.shape
    L, Hkv = k_cache.shape[1], k_cache.shape[2]
    group = H // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    # non-multiple cache lengths: keep the lane-aligned block size and pad
    # the KV tail instead (padded rows sit at kpos >= L >= kv_valid, so the
    # kernel's validity mask already discards them) — shrinking block_kv to
    # a divisor of L would degenerate to 1-row blocks for prime L
    block_kv = min(block_kv, L)
    n_kv = -(-L // block_kv)
    quantized = k_scale is not None

    qt = q.reshape(B, Hkv, group, dh)                  # (B,Hkv,g,dh)
    kt = k_cache.transpose(0, 2, 1, 3)                 # (B,Hkv,L,dh)
    vt = v_cache.transpose(0, 2, 1, 3)
    if n_kv * block_kv != L:
        pad = ((0, 0), (0, 0), (0, n_kv * block_kv - L), (0, 0))
        kt = jnp.pad(kt, pad)
        vt = jnp.pad(vt, pad)
    if k_scale is None:
        k_scale = jnp.ones((Hkv,), jnp.float32)
        v_scale = jnp.ones((Hkv,), jnp.float32)

    grid = (B, Hkv, n_kv)
    kern = functools.partial(_kernel, scale=scale, block_kv=block_kv,
                             n_kv=n_kv, quantized=quantized)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            _SMEM, _SMEM, _SMEM,
            pl.BlockSpec((1, 1, group, dh), lambda b, h, ki: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_kv, dh), lambda b, h, ki: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, dh), lambda b, h, ki: (b, h, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, dh), lambda b, h, ki: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, dh), q.dtype),
        scratch_shapes=_scratch(group, dh),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="decode_attention",
    )(kv_valid.astype(jnp.int32), k_scale.astype(jnp.float32),
      v_scale.astype(jnp.float32), qt, kt, vt)
    return out.reshape(B, H, dh)


def _paged_kernel(pt_ref, len_ref, ksc_ref, vsc_ref, q_ref, k_ref, v_ref,
                  o_ref, m_scr, l_scr, acc_scr, *, scale: float,
                  page_size: int, n_pages_per_seq: int, quantized: bool):
    b = pl.program_id(0)
    h = pl.program_id(1)
    pi = pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        _init_scratch(m_scr, l_scr, acc_scr)

    valid = len_ref[b]
    run = pi * page_size < valid

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)            # (group, dh)
        k = k_ref[0, 0].astype(jnp.float32)            # (page_size, dh)
        v = v_ref[0, 0].astype(jnp.float32)
        if quantized:
            k = k * ksc_ref[h]
            v = v * vsc_ref[h]
        _online_softmax_step(q, k, v, valid, pi * page_size, scale,
                             m_scr, l_scr, acc_scr)

    @pl.when(pi == n_pages_per_seq - 1)
    def _out():
        _finalize(o_ref, l_scr, acc_scr)


def paged_decode_attention(q, k_pages, v_pages, page_table, seq_lens, *,
                           scale: float = None, k_scale=None, v_scale=None,
                           interpret: bool = False):
    """Decode attention over a page-table-indirected KV cache.

    q: (B, H, dh); k/v_pages: (n_pages, Hkv, page_size, dh) pooled pages,
    head-major so that one (page, head) block is a (page_size, dh) tile
    (int8 when scales given); page_table: (B, n_pages_per_seq) int32
    physical page ids (entries past a sequence's last used page may point
    anywhere — typically the reserved null page 0 — and are masked by
    ``seq_lens``); seq_lens: (B,) int32 valid tokens per sequence -> (B,
    H, dh).

    The page table is a scalar-prefetch operand: the BlockSpec ``index_map``
    reads it to gather each sequence's physical KV pages, so the kernel
    streams exactly the pages the sequence owns (the paper's hierarchical
    tiling, with one extra level of indirection for continuous batching).
    """
    B, H, dh = q.shape
    n_pages, Hkv, page_size = k_pages.shape[:3]
    n_pp = page_table.shape[1]
    group = H // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    quantized = k_scale is not None

    qt = q.reshape(B, Hkv, group, dh)                  # (B,Hkv,g,dh)
    if k_scale is None:
        k_scale = jnp.ones((Hkv,), jnp.float32)
        v_scale = jnp.ones((Hkv,), jnp.float32)

    kern = functools.partial(_paged_kernel, scale=scale, page_size=page_size,
                             n_pages_per_seq=n_pp, quantized=quantized)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,        # page_table, seq_lens
        grid=(B, Hkv, n_pp),
        in_specs=[
            _SMEM, _SMEM,
            pl.BlockSpec((1, 1, group, dh),
                         lambda b, h, pi, pt, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, dh),
                         lambda b, h, pi, pt, ln: (pt[b, pi], h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, dh),
                         lambda b, h, pi, pt, ln: (pt[b, pi], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, dh),
                               lambda b, h, pi, pt, ln: (b, h, 0, 0)),
        scratch_shapes=_scratch(group, dh),
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="paged_decode_attention",
    )(page_table.astype(jnp.int32), seq_lens.astype(jnp.int32),
      k_scale.astype(jnp.float32), v_scale.astype(jnp.float32),
      qt, k_pages, v_pages)
    return out.reshape(B, H, dh)


def _chunk_kernel(pt_ref, start_ref, len_ref, ksc_ref, vsc_ref, q_ref,
                  k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, page_size: int, n_pages_per_seq: int,
                  chunk: int, group: int, quantized: bool):
    b = pl.program_id(0)
    h = pl.program_id(1)
    pi = pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        _init_scratch(m_scr, l_scr, acc_scr)

    start = start_ref[b]
    nv = len_ref[b]
    # pages strictly past the chunk's last query position hold no
    # attendable KV (causal) — skip them
    run = pi * page_size < start + chunk

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)            # (chunk*group, dh)
        k = k_ref[0, 0].astype(jnp.float32)            # (page_size, dh)
        v = v_ref[0, 0].astype(jnp.float32)
        if quantized:
            k = k * ksc_ref[h]
            v = v * vsc_ref[h]
        # per-row causal limit: query row r sits at absolute position
        # start + r // group and may attend KV positions <= its own,
        # clipped to the chunk's true (unpadded) extent
        rows = jax.lax.broadcasted_iota(jnp.int32, (chunk * group, 1), 0)
        valid = jnp.minimum(start + rows // group + 1, nv)
        _online_softmax_step(q, k, v, valid, pi * page_size, scale,
                             m_scr, l_scr, acc_scr)

    @pl.when(pi == n_pages_per_seq - 1)
    def _out():
        _finalize(o_ref, l_scr, acc_scr)


def chunk_prefill_attention(q, k_pages, v_pages, page_table, start, n_valid,
                            *, scale: float = None, k_scale=None,
                            v_scale=None, interpret: bool = False):
    """Chunked-prefill attention: a q-block against a page-table KV cache.

    q: (B, C, H, dh) — one fixed-size prefill chunk whose queries sit at
    absolute positions [start, start + C); k/v_pages: (n_pages, Hkv,
    page_size, dh) head-major pooled pages (int8 when scales given)
    ALREADY containing the chunk's own KV at those positions; page_table:
    (B, n_pages_per_seq) int32 physical page ids; start: scalar or (B,)
    int32 first absolute position of the chunk; n_valid: (B,) int32 total
    valid tokens once this chunk lands (masks the chunk's right-padding).
    Returns (B, C, H, dh).

    Each query attends causally — KV positions <= its own — across every
    page the sequence owns, so a chunk sees the whole cached prefix (shared
    prefix pages included) plus the in-chunk causal triangle. The page
    table is a scalar-prefetch operand dereferenced by the K/V BlockSpec
    ``index_map`` (same indirection as ``paged_decode_attention``); pages
    past the chunk's last query are skipped, giving the flash-style
    diagonal-band block skipping of the dense prefill kernel.
    """
    B, C, H, dh = q.shape
    n_pages, Hkv, page_size = k_pages.shape[:3]
    n_pp = page_table.shape[1]
    group = H // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    quantized = k_scale is not None

    # rows ordered (position, head-in-group): row r -> position r // group
    qt = (q.reshape(B, C, Hkv, group, dh).transpose(0, 2, 1, 3, 4)
          .reshape(B, Hkv, C * group, dh))
    start = jnp.broadcast_to(jnp.asarray(start, jnp.int32).reshape(-1), (B,))
    if k_scale is None:
        k_scale = jnp.ones((Hkv,), jnp.float32)
        v_scale = jnp.ones((Hkv,), jnp.float32)

    kern = functools.partial(_chunk_kernel, scale=scale, page_size=page_size,
                             n_pages_per_seq=n_pp, chunk=C, group=group,
                             quantized=quantized)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,        # page_table, start, n_valid
        grid=(B, Hkv, n_pp),
        in_specs=[
            _SMEM, _SMEM,
            pl.BlockSpec((1, 1, C * group, dh),
                         lambda b, h, pi, pt, st, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, dh),
                         lambda b, h, pi, pt, st, ln: (pt[b, pi], h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, dh),
                         lambda b, h, pi, pt, st, ln: (pt[b, pi], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, C * group, dh),
                               lambda b, h, pi, pt, st, ln: (b, h, 0, 0)),
        scratch_shapes=_scratch(C * group, dh),
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, C * group, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="chunk_prefill_attention",
    )(page_table.astype(jnp.int32), start, n_valid.astype(jnp.int32),
      k_scale.astype(jnp.float32), v_scale.astype(jnp.float32),
      qt, k_pages, v_pages)
    return (out.reshape(B, Hkv, C, group, dh).transpose(0, 2, 1, 3, 4)
            .reshape(B, C, H, dh))


def spec_verify_attention(q, k_pages, v_pages, page_table, seq_lens, n_fed,
                          *, scale: float = None, k_scale=None, v_scale=None,
                          interpret: bool = False):
    """Speculative-verify attention: a K-query block per sequence against
    the paged KV cache with per-row causal validity (DESIGN.md SS14).

    q: (B, C, H, dh) — the verify window ``[t_last, d_1 .. d_{C-1}]``
    whose queries sit at per-sequence absolute positions
    ``seq_lens[b] + j`` (the window's KV — including each draft token's
    own — is ALREADY scattered into the pages); page_table: (B,
    n_pages_per_seq); seq_lens: (B,) int32 landed tokens per sequence
    (the window starts there); n_fed: (B,) real fed window tokens per
    sequence (<= C — shorter per-slot draft lengths right-pad).

    Row j of sequence b may attend absolute KV positions
    ``<= seq_lens[b] + min(j, n_fed[b] - 1)`` — the same per-row causal
    frontier as chunked prefill, with a per-sequence (not scalar) window
    start. The implementation IS the chunk-prefill kernel: its
    ``_chunk_kernel`` body already takes a (B,) scalar-prefetch ``start``
    and computes ``valid = min(start + row + 1, n_valid)`` per row, which
    is exactly the verify semantics with ``n_valid = seq_lens + n_fed``.
    This wrapper pins those semantics down as a public entry so the
    verify path (model layer, ops routing, oracle, tests) does not lean
    on a prefill implementation detail."""
    n_valid = (jnp.asarray(seq_lens, jnp.int32)
               + jnp.asarray(n_fed, jnp.int32))
    return chunk_prefill_attention(q, k_pages, v_pages, page_table,
                                   jnp.asarray(seq_lens, jnp.int32), n_valid,
                                   scale=scale, k_scale=k_scale,
                                   v_scale=v_scale, interpret=interpret)


def quantize_kv(k, v, *, head_axis: int = 2):
    """Per-kv-head symmetric int8 quantization of a KV cache.

    k/v: (B, L, Hkv, dh), or a head-major page pool (n_pages, Hkv,
    page_size, dh) with ``head_axis=1`` -> (k_i8, v_i8, k_scale, v_scale)
    with (Hkv,) scales."""
    def one(x):
        axes = tuple(a for a in range(x.ndim) if a != head_axis)
        amax = jnp.maximum(jnp.abs(x.astype(jnp.float32)).max(axis=axes),
                           1e-6)                       # (Hkv,)
        scale = amax / 127.0
        bshape = [1] * x.ndim
        bshape[head_axis] = -1
        xi = jnp.clip(jnp.round(x.astype(jnp.float32)
                                / scale.reshape(bshape)),
                      -127, 127).astype(jnp.int8)
        return xi, scale
    ki, ks = one(k)
    vi, vs = one(v)
    return ki, vi, ks, vs
