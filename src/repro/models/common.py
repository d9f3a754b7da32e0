"""Shared pure-JAX building blocks: init, norms, RoPE, masks, attention core.

No flax in this container — parameters are plain pytrees (nested dicts of
jnp arrays), modules are (init_fn, apply_fn) pairs.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

DType = jnp.dtype


# ------------------------------ init ---------------------------------- #

def dense_init(key, d_in: int, d_out: int, dtype, *, bias: bool = False,
               scale: Optional[float] = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    w = (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)
    if bias:
        return {"w": w, "b": jnp.zeros((d_out,), dtype)}
    return {"w": w}


def dense(p, x):
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


def embed_init(key, vocab: int, d: int, dtype):
    return {"emb": (jax.random.normal(key, (vocab, d), jnp.float32)
                    * 0.02).astype(dtype)}


# ------------------------------ norms --------------------------------- #

def rms_norm(x, w, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + w.astype(jnp.float32))).astype(dt)


def layer_norm(x, w, b, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(dt)


# ------------------------------ RoPE ----------------------------------- #

def rope_freqs(head_dim: int, theta: float = 10000.0):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x, positions, theta: float = 10000.0):
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                        # (hd/2,)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(ang)[..., :, None, :]
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ------------------------------ masks ---------------------------------- #

NEG_INF = -1e30


def causal_mask(S: int, L: int, q_offset: int = 0):
    """(S, L) True where query i may attend key j."""
    qpos = jnp.arange(S)[:, None] + q_offset
    kpos = jnp.arange(L)[None, :]
    return kpos <= qpos


def sliding_mask(S: int, L: int, window: int, q_offset: int = 0):
    qpos = jnp.arange(S)[:, None] + q_offset
    kpos = jnp.arange(L)[None, :]
    return (kpos <= qpos) & (kpos > qpos - window)


def prefix_lm_mask(S: int, L: int, prefix_len: int, q_offset: int = 0):
    """Bidirectional over the first ``prefix_len`` positions, causal after."""
    qpos = jnp.arange(S)[:, None] + q_offset
    kpos = jnp.arange(L)[None, :]
    return (kpos <= qpos) | (kpos < prefix_len)


def length_mask(L: int, valid_len):
    return jnp.arange(L)[None, :] < valid_len


# --------------------------- attention core ---------------------------- #
# Masks are LAZY (kind + params), materialized per block — a full (S, L)
# mask/score tensor at 32k context would dwarf HBM. This is the XLA-level
# analogue of the paper's hierarchical tiling; the Pallas kernel does the
# same blocking explicitly in VMEM.

def _block_mask(kind: str, qpos, kpos, *, window: int = 0,
                prefix_len: int = 0, kv_valid: Optional[jnp.ndarray] = None):
    q = qpos[:, None]
    kk = kpos[None, :]
    if kind == "causal":
        m = kk <= q
    elif kind == "sliding":
        m = (kk <= q) & (kk > q - window)
    elif kind == "prefix":
        m = (kk <= q) | (kk < prefix_len)
    elif kind == "full":
        m = jnp.ones((q.shape[0], kk.shape[1]), bool)
    else:
        raise ValueError(kind)
    if kv_valid is not None:   # (B,) valid KV length (decode caches)
        m = m[None] & (kk[None] < kv_valid[:, None, None])
    return m


def _scores_block(qg, kb, scale, softcap):
    # bf16 operands + f32 accumulation: upcasting the KV operand would
    # materialize an f32 copy of the whole cache (2x HBM) — observed as a
    # carried f32[L,B,Lshard,H,dh] twin of the cache in decode graphs
    s = jnp.einsum("bshgd,blhd->bshgl", qg, kb,
                   preferred_element_type=jnp.float32) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    return s


def attention(q, k, v, *, mask_kind: str = "causal", window: int = 0,
              prefix_len: int = 0, q_offset=0, kv_valid=None,
              scale: Optional[float] = None, softcap: float = 0.0,
              impl: str = "xla", block_q: int = 512, block_kv: int = 1024,
              acc_dtype: str = "float32"):
    """GQA attention with lazy masks and flash-style KV blocking.

    q: (B,S,H,dh); k/v: (B,L,Hkv,dh); kv_valid: optional (B,) valid length.
    ``impl='pallas'`` runs the Pallas flash kernel (ineligible shapes raise).
    """
    B, S, H, dh = q.shape
    L, Hkv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    if impl == "pallas":
        from repro.kernels import ops as kops
        return kops.flash_attention(
            q, k, v, mask_kind=mask_kind, window=window,
            prefix_len=prefix_len, q_offset=q_offset, kv_valid=kv_valid,
            scale=scale, softcap=softcap)
    group = H // Hkv
    qg = q.reshape(B, S, Hkv, group, dh)
    qpos = jnp.arange(S) + q_offset

    if S * L <= 1 << 21:  # small: single block, no scan
        kpos = jnp.arange(L)
        s = _scores_block(qg, k, scale, softcap)          # (B,S,Hkv,g,L)
        m = _block_mask(mask_kind, qpos, kpos, window=window,
                        prefix_len=prefix_len, kv_valid=kv_valid)
        m = m[:, :, None, None, :] if m.ndim == 3 else m[None, :, None, None, :]
        s = jnp.where(m, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bshgl,blhd->bshgd", p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        return out.reshape(B, S, H, dv).astype(q.dtype)

    # flash-style: outer scan over Q blocks, inner scan over KV blocks with
    # online softmax. Peak live block: (B, bq, Hkv, g, bkv) — independent of
    # S and L. NOTE: causal masking zeroes but does not SKIP upper blocks on
    # this XLA path (~2x attention FLOPs at long S); the Pallas kernel skips
    # them properly on TPU (see kernels/flash_attention.py + SSPerf).
    nq, nkv = -(-S // block_q), -(-L // block_kv)
    Sp, Lp = nq * block_q, nkv * block_kv
    qp = jnp.pad(qg, ((0, 0), (0, Sp - S), (0, 0), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, Lp - L), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Lp - L), (0, 0), (0, 0)))
    qb = qp.reshape(B, nq, block_q, Hkv, group, dh).transpose(1, 0, 2, 3, 4, 5)
    kb = kp.reshape(B, nkv, block_kv, Hkv, dh).transpose(1, 0, 2, 3, 4)
    vb = vp.reshape(B, nkv, block_kv, Hkv, dv).transpose(1, 0, 2, 3, 4)
    base_valid = (kv_valid if kv_valid is not None
                  else jnp.full((B,), L, jnp.int32))

    def q_step(_, qxs):
        qblk, qi = qxs                                   # (B,bq,Hkv,g,dh)
        qpos_blk = qi * block_q + jnp.arange(block_q) + q_offset

        @jax.checkpoint
        def kv_step(carry, xs):
            m_i, l_i, acc = carry
            kblk, vblk, j = xs
            kpos = j * block_kv + jnp.arange(block_kv)
            s = _scores_block(qblk, kblk, scale, softcap)  # (B,bq,Hkv,g,bkv)
            msk = _block_mask(mask_kind, qpos_blk, kpos, window=window,
                              prefix_len=prefix_len, kv_valid=base_valid)
            s = jnp.where(msk[:, :, None, None, :], s, NEG_INF)
            m_new = jnp.maximum(m_i, s.max(axis=-1))
            # fully-masked block: s == m_new == NEG_INF would give exp(0)=1
            p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new[..., None]))
            corr = jnp.exp(jnp.minimum(m_i - m_new, 0.0))
            l_new = l_i * corr + p.sum(axis=-1)
            upd = jnp.einsum("bshgl,blhd->bshgd", p.astype(vblk.dtype),
                             vblk, preferred_element_type=jnp.float32)
            acc = (acc * corr[..., None].astype(acc.dtype)
                   + upd.astype(acc.dtype))
            return (m_new, l_new, acc), None

        adt = jnp.dtype(acc_dtype)
        init = (jnp.full((B, block_q, Hkv, group), NEG_INF, jnp.float32),
                jnp.zeros((B, block_q, Hkv, group), jnp.float32),
                jnp.zeros((B, block_q, Hkv, group, dv), adt))
        (m_f, l_f, acc), _ = jax.lax.scan(kv_step, init,
                                          (kb, vb, jnp.arange(nkv)))
        blk_out = (acc.astype(jnp.float32)
                   / jnp.maximum(l_f, 1e-30)[..., None])
        return None, blk_out.astype(q.dtype)

    # checkpointed scans: the backward recomputes scores blockwise instead
    # of saving the full (S, L) residuals — flash-attention memory behaviour
    _, outb = jax.lax.scan(jax.checkpoint(q_step), None,
                           (qb, jnp.arange(nq)))
    out = outb.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sp, Hkv, group, dv)
    return out[:, :S].reshape(B, S, H, dv)


# ------------------------------ misc ----------------------------------- #

def constrain(x, sharding):
    """with_sharding_constraint when a sharding is provided (else no-op)."""
    if sharding is None:
        return x
    return jax.lax.with_sharding_constraint(x, sharding)


def constrain_tree(tree, path_shardings):
    """ZeRO-3 weight gathering: constrain each leaf whose path SUFFIX
    matches an entry of ``path_shardings`` (tuple of (path, sharding)).

    Applied to one layer's param slice inside the scan body, this forces
    GSPMD to all-gather the data-axis weight shards per layer (~weight
    bytes) instead of all-reducing activation-sized partial matmul outputs
    (~token bytes — 40x larger at 32k-token prefill)."""
    if not path_shardings:
        return tree
    table = dict(path_shardings)

    def rule(path, leaf):
        ps = "/".join(str(p.key) if hasattr(p, "key") else str(p.idx)
                      for p in path)
        for suffix, sh in table.items():
            if suffix.endswith(ps) or ps.endswith(suffix):
                return jax.lax.with_sharding_constraint(leaf, sh)
        return leaf
    return jax.tree_util.tree_map_with_path(rule, tree)


def swiglu(gate, up):
    return jax.nn.silu(gate) * up


def gelu_mlp(p, x):
    return dense(p["fc2"], jax.nn.gelu(dense(p["fc1"], x)))


def update_cache(cache_k, cache_v, k_new, v_new, pos):
    """Write k/v (B,S,Hkv,dh) at position ``pos`` into (B,Lmax,Hkv,dh)."""
    ck = jax.lax.dynamic_update_slice(cache_k, k_new.astype(cache_k.dtype),
                                      (0, pos, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache_v, v_new.astype(cache_v.dtype),
                                      (0, pos, 0, 0))
    return ck, cv


def cross_entropy(logits, labels, ignore: int = -1):
    """Mean token NLL; positions with label==ignore are masked out."""
    valid = labels != ignore
    labels_safe = jnp.where(valid, labels, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels_safe[..., None], axis=-1)[..., 0]
    nll = jnp.where(valid, nll, 0.0)
    return nll.sum() / jnp.maximum(valid.sum(), 1)


def chunked_xent(h, w, labels, *, tied: bool = False, chunk: int = 512,
                 ignore: int = -1):
    """Cross-entropy WITHOUT materializing (B, S, V) logits.

    Scans over sequence chunks: peak live logits = (B, chunk, V_shard).
    h: (B,S,d) final hidden states; w: (d,V) head or (V,d) tied embedding.
    At 256-way batches x 4k seq x 256k vocab the full logits tensor is
    tens of GB per device — this is what makes train_4k cells fit HBM."""
    B, S, d = h.shape
    nc = -(-S // chunk)
    Sp = nc * chunk
    hp = jnp.pad(h, ((0, 0), (0, Sp - S), (0, 0)))
    lp = jnp.pad(labels, ((0, 0), (0, Sp - S)), constant_values=ignore)
    hc = hp.reshape(B, nc, chunk, d).transpose(1, 0, 2, 3)
    lc = lp.reshape(B, nc, chunk).transpose(1, 0, 2)

    def body(carry, xs):
        tot, cnt = carry
        h_c, lab_c = xs
        if tied:
            logits = jnp.einsum("bsd,vd->bsv", h_c.astype(jnp.float32),
                                w.astype(jnp.float32))
        else:
            logits = jnp.einsum("bsd,dv->bsv", h_c.astype(jnp.float32),
                                w.astype(jnp.float32))
        valid = lab_c != ignore
        safe = jnp.where(valid, lab_c, 0)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        nll = jnp.where(valid, lse - gold, 0.0)
        return (tot + nll.sum(), cnt + valid.sum()), None

    (tot, cnt), _ = jax.lax.scan(jax.checkpoint(body),
                                 (jnp.zeros((), jnp.float32),
                                  jnp.zeros((), jnp.int32)), (hc, lc))
    return tot / jnp.maximum(cnt, 1)
