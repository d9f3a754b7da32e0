"""Jit'd public entries for the Pallas kernels, with their eligibility rules.

A caller that asks for a kernel (``RuntimeOptions.attn_impl="pallas"``)
gets the kernel or a ``ValueError`` that names the kernel, the shapes and
the rule they break — never a silent switch to the XLA path. Eligibility
is decided from static shapes/dtypes only, never from traced values, so
the entries are safe inside ``jax.lax.scan`` bodies: the fused multi-step
decode (DESIGN.md SS12) traces them once per scan, and every micro-step
runs the same kernel.

Kernels run compiled on TPU and through the Pallas interpreter on the CPU
backend (the test rig).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _refuse(kernel: str, why: str, **shapes) -> None:
    desc = ", ".join(f"{k}={v}" for k, v in shapes.items())
    raise ValueError(f"{kernel}: {why} ({desc}); attn_impl='pallas' needs "
                     f"an eligible shape, use attn_impl='xla' otherwise")


def _check_paged(kernel: str, q, k_pages, softcap: float) -> None:
    """Shared rule of the paged kernels: one (page_size, dh) K/V tile per
    (page, kv-head) block must meet the dtype's minimum sublane count, the
    head width must be 64 or a multiple of 128 lanes, and the query heads
    must group evenly over the KV heads."""
    H, dh = q.shape[-2], q.shape[-1]
    Hkv, page_size = k_pages.shape[1], k_pages.shape[2]
    shapes = dict(q=tuple(q.shape), k_pages=tuple(k_pages.shape),
                  kv_dtype=jnp.dtype(k_pages.dtype).name)
    if softcap:
        _refuse(kernel, f"logit softcap {softcap} is not implemented",
                **shapes)
    if dh % 128 != 0 and dh != 64:
        _refuse(kernel, f"head_dim {dh} is neither 64 nor a multiple of "
                "128", **shapes)
    min_sublane = {1: 32, 2: 16}.get(jnp.dtype(k_pages.dtype).itemsize, 8)
    if page_size % min_sublane:
        _refuse(kernel, f"page_size {page_size} is not a multiple of "
                f"{min_sublane} rows", **shapes)
    if H % Hkv:
        _refuse(kernel, f"{H} query heads do not group over {Hkv} KV "
                "heads", **shapes)


def flash_attention(q, k, v, *, mask_kind: str, window: int = 0,
                    prefix_len: int = 0, q_offset=0, kv_valid=None,
                    scale: float = 1.0, softcap: float = 0.0) -> jax.Array:
    """Dense causal/full attention through the Pallas flash kernel."""
    B, S, H, dh = q.shape
    L, Hkv = k.shape[1], k.shape[2]
    shapes = dict(q=tuple(q.shape), k=tuple(k.shape), mask=mask_kind)
    if mask_kind not in ("causal", "full") or softcap or kv_valid is not None:
        _refuse("flash_attention", "only unpadded causal/full masks without "
                "softcap are implemented", **shapes)
    if S < 128 or L < 128 or dh % 128 != 0 or H % Hkv != 0:
        _refuse("flash_attention", "needs seq >= 128, head_dim a multiple "
                "of 128 and grouped heads", **shapes)
    if isinstance(q_offset, jax.Array) or q_offset != 0 or S != L:
        _refuse("flash_attention", "needs queries aligned with the keys "
                "(no offset, S == L)", **shapes)
    from repro.kernels.flash_attention import flash_attention as kernel
    return kernel(q, k, v, causal=(mask_kind == "causal"), scale=scale,
                  interpret=_interpret())


def paged_decode_attention(q, k_pages, v_pages, page_table, seq_lens, *,
                           scale: float, k_scale=None, v_scale=None,
                           softcap: float = 0.0) -> jax.Array:
    """The paged Pallas decode kernel (page-table KV gather)."""
    _check_paged("paged_decode_attention", q, k_pages, softcap)
    from repro.kernels import decode_attention as da
    return da.paged_decode_attention(q, k_pages, v_pages, page_table,
                                     seq_lens, scale=scale, k_scale=k_scale,
                                     v_scale=v_scale, interpret=_interpret())


def chunk_prefill_attention(q, k_pages, v_pages, page_table, start, n_valid,
                            *, scale: float, k_scale=None, v_scale=None,
                            softcap: float = 0.0) -> jax.Array:
    """The chunked-prefill Pallas kernel (q-block x paged KV)."""
    _check_paged("chunk_prefill_attention", q, k_pages, softcap)
    from repro.kernels import decode_attention as da
    return da.chunk_prefill_attention(q, k_pages, v_pages, page_table, start,
                                      n_valid, scale=scale, k_scale=k_scale,
                                      v_scale=v_scale, interpret=_interpret())


def spec_verify_attention(q, k_pages, v_pages, page_table, seq_lens, n_fed,
                          *, scale: float, k_scale=None, v_scale=None,
                          softcap: float = 0.0) -> jax.Array:
    """The speculative-verify kernel: a (B, C) query window at per-sequence
    positions ``seq_lens + j`` with per-row causal validity (DESIGN.md
    SS14). Same rule as the chunk kernel it shares its body with."""
    _check_paged("spec_verify_attention", q, k_pages, softcap)
    from repro.kernels import decode_attention as da
    return da.spec_verify_attention(q, k_pages, v_pages, page_table,
                                    seq_lens, n_fed, scale=scale,
                                    k_scale=k_scale, v_scale=v_scale,
                                    interpret=_interpret())
