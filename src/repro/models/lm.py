"""Unified decoder-only LM covering dense/GQA, MLA, MoE, local:global and
prefix-LM architectures — pure JAX, layer stacks executed with ``lax.scan``
(identical-shape layers are stacked; shape-divergent prefix layers, e.g.
DeepSeek-V2's first dense layer, run unscanned).

Public surface (used by launch/serving/tests):
    init_params(cfg, key, opts)          -> params pytree
    forward(cfg, params, tokens, opts[, prefix_emb])   -> logits
    train_loss(cfg, params, batch, opts) -> (loss, metrics)
    init_cache(cfg, batch, max_len, opts)-> cache pytree
    prefill(cfg, params, tokens, cache, opts[, prefix_emb]) -> (logits, cache)
    decode_step(cfg, params, token, pos, cache, opts)  -> (logits, cache)
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import common as cm
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import sampling as sampling_mod


@dataclass(frozen=True)
class RuntimeOptions:
    dtype: str = "bfloat16"
    attn_impl: str = "xla"          # xla | pallas
    moe_impl: str = "capacity"      # capacity | ragged
    remat: str = "none"             # none | block  (activation checkpointing)
    cache_dtype: str = ""           # "" -> same as dtype; "int8" -> quantized
    capacity_factor: float = 1.25
    # flash-scan attention tiling knobs (hillclimb levers; SSPerf)
    block_q: int = 512
    block_kv: int = 1024
    flash_acc: str = "float32"      # "bfloat16" halves carry HBM traffic
    # NamedSharding for the (B, S, d) residual stream. Without an explicit
    # constraint GSPMD propagation can drop the batch sharding entirely
    # (observed: batch replicated, d_model model-sharded => 16x activation
    # memory and redundant compute). Set by the launcher; None in tests.
    residual_sharding: object = None
    # MoE dispatch shardings (SSPerf): expert-major (E, C, d) tensors on
    # "model" (EP all-to-all) and the combine buffer back on the batch axes
    # (kills the replicated (T, d) f32 all-reduce, ~2.3 TB/step on arctic)
    moe_expert_sharding: object = None
    moe_out_sharding: object = None
    # ZeRO-3 per-layer weight gathering (SSPerf iteration 3): tuple of
    # (param path suffix, NamedSharding-without-data-axes); applied to the
    # layer slice inside the scan body
    zero3_gather: tuple = ()
    # sequence-parallel decode attention (SSPerf iteration 2): manual
    # shard_map update+attend for LENGTH-sharded caches — avoids GSPMD's
    # full-cache all-gather on every decode step
    seq_shard_attn: bool = False
    seq_shard_mesh: object = None
    # shard-local EP MoE dispatch (SSPerf iteration 4)
    moe_shard_map_mesh: object = None
    # head-sharded paged serving (DESIGN.md SS16): a jax Mesh with a
    # "model" axis partitions the paged KV pool's KV-head dim; the paged
    # attend runs per shard under shard_map and all-gathers head outputs
    # (bitwise identical to single-device). None: replicated paged path.
    kv_shard_mesh: object = None

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)


# ------------------------------ layers -------------------------------- #

def _init_attn(key, cfg: ArchConfig, dtype):
    H, Hkv, hd, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    ks = jax.random.split(key, 4)
    b = cfg.qkv_bias
    return {
        "wq": cm.dense_init(ks[0], d, H * hd, dtype, bias=b),
        "wk": cm.dense_init(ks[1], d, Hkv * hd, dtype, bias=b),
        "wv": cm.dense_init(ks[2], d, Hkv * hd, dtype, bias=b),
        "wo": cm.dense_init(ks[3], H * hd, d, dtype),
    }


def _init_layer(key, cfg: ArchConfig, dtype, *, is_moe: bool, d_ff: int):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"ln1": jnp.zeros((cfg.d_model,), dtype),
         "ln2": jnp.zeros((cfg.d_model,), dtype)}
    if cfg.mla is not None:
        p["attn"] = mla_mod.init_mla(k1, cfg, dtype)
    else:
        p["attn"] = _init_attn(k1, cfg, dtype)
    if is_moe:
        p["moe"] = moe_mod.init_moe(k2, cfg, dtype)
    else:
        p["mlp"] = moe_mod.init_dense_ffn(k3, cfg, d_ff, dtype)
    return p


def _layer_split(cfg: ArchConfig) -> Tuple[int, bool]:
    """(n_unscanned_prefix_layers, stack_is_moe)."""
    if cfg.moe is not None and cfg.moe.first_dense:
        return cfg.moe.first_dense, True
    return 0, cfg.moe is not None


def _kind_array(cfg: ArchConfig, start: int, n: int):
    """Per-layer attention kind: 0=global/causal, 1=local/sliding."""
    kinds = [1 if cfg.attention_kind(start + i) == "local" else 0
             for i in range(n)]
    return jnp.asarray(kinds, jnp.int32)


def init_params(cfg: ArchConfig, key, opts: RuntimeOptions = RuntimeOptions()):
    dtype = opts.jdtype
    n_pre, stack_moe = _layer_split(cfg)
    n_stack = cfg.n_layers - n_pre
    k_emb, k_pre, k_stack, k_out = jax.random.split(key, 4)
    params = {"embed": cm.embed_init(k_emb, cfg.vocab, cfg.d_model, dtype),
              "final_norm": jnp.zeros((cfg.d_model,), dtype)}
    if n_pre:
        dff = cfg.moe.d_ff_dense or cfg.d_ff
        params["head_layers"] = [
            _init_layer(k, cfg, dtype, is_moe=False, d_ff=dff)
            for k in jax.random.split(k_pre, n_pre)]
    params["stack"] = jax.vmap(
        lambda k: _init_layer(k, cfg, dtype, is_moe=stack_moe, d_ff=cfg.d_ff)
    )(jax.random.split(k_stack, n_stack))
    if not cfg.tie_embeddings:
        params["lm_head"] = cm.dense_init(k_out, cfg.d_model, cfg.vocab,
                                          dtype, scale=cfg.d_model ** -0.5)
    return params


# ----------------------------- forward -------------------------------- #

def _attn_apply(p, x, cfg: ArchConfig, opts: RuntimeOptions, *, kind,
                positions, mask_kind: str, prefix_len: int):
    """Full-sequence attention (train/prefill). Returns out and (k, v)."""
    B, S, d = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = cm.dense(p["wq"], x).reshape(B, S, H, hd)
    k = cm.dense(p["wk"], x).reshape(B, S, Hkv, hd)
    v = cm.dense(p["wv"], x).reshape(B, S, Hkv, hd)
    q = cm.apply_rope(q, positions)
    k = cm.apply_rope(k, positions)

    def run(mk, window):
        return cm.attention(q, k, v, mask_kind=mk, window=window,
                            prefix_len=prefix_len, softcap=cfg.logit_softcap,
                            impl=opts.attn_impl, block_q=opts.block_q,
                            block_kv=opts.block_kv, acc_dtype=opts.flash_acc)
    if cfg.sliding_window and cfg.local_global_ratio:
        # kind is traced (scanned layer): both branches built once in HLO
        out = jax.lax.cond(
            kind == 1,
            lambda: run("sliding", cfg.sliding_window),
            lambda: run(mask_kind, 0))
    elif cfg.sliding_window:
        out = run("sliding", cfg.sliding_window)
    else:
        out = run(mask_kind, 0)
    out = cm.dense(p["wo"], out.reshape(B, S, H * hd))
    return out, (k, v)


def _ffn_apply(p, x, cfg: ArchConfig, opts: RuntimeOptions):
    if "moe" in p:
        y, aux = moe_mod.moe_ffn(p["moe"], x, cfg, impl=opts.moe_impl,
                                 capacity_factor=opts.capacity_factor,
                                 expert_sharding=opts.moe_expert_sharding,
                                 out_sharding=opts.moe_out_sharding,
                                 shard_map_mesh=opts.moe_shard_map_mesh)
        return y, aux
    return moe_mod.dense_ffn(p["mlp"], x, cfg.gated_mlp), {}


def _block(p, x, cfg, opts, *, kind, positions, mask_kind, prefix_len):
    x = cm.constrain(x, opts.residual_sharding)
    p = cm.constrain_tree(p, opts.zero3_gather)
    if cfg.mla is not None:
        h, kv = mla_mod.mla_prefill_attn(p["attn"], cm.rms_norm(x, p["ln1"]),
                                         cfg, positions, impl=opts.attn_impl)
    else:
        h, kv = _attn_apply(p["attn"], cm.rms_norm(x, p["ln1"]), cfg, opts,
                            kind=kind, positions=positions,
                            mask_kind=mask_kind, prefix_len=prefix_len)
    x = x + h
    h, aux = _ffn_apply(p, cm.rms_norm(x, p["ln2"]), cfg, opts)
    return x + h, kv, aux


def _logits(cfg, params, x):
    x = cm.rms_norm(x, params["final_norm"])
    if cfg.tie_embeddings:
        return x @ params["embed"]["emb"].T
    return cm.dense(params["lm_head"], x)


def _embed_tokens(cfg, params, tokens, prefix_emb):
    x = params["embed"]["emb"][tokens]
    x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)  # gemma-style scale
    if prefix_emb is not None:
        x = jnp.concatenate([prefix_emb.astype(x.dtype), x], axis=1)
    return x


def forward(cfg: ArchConfig, params, tokens, opts: RuntimeOptions = RuntimeOptions(),
            prefix_emb=None, *, collect_kv: bool = False,
            return_hidden: bool = False):
    """Full-sequence forward. tokens: (B, S) int32.

    prefix_emb: (B, P, d) stub frontend output (VLM patches), prepended.
    Returns (logits, aux) or (logits, aux, kvs) when collect_kv."""
    B = tokens.shape[0]
    x = _embed_tokens(cfg, params, tokens, prefix_emb)
    S = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    mask_kind = ("prefix" if (cfg.prefix_bidirectional and cfg.prefix_len)
                 else "causal")
    prefix_len = cfg.prefix_len if cfg.prefix_bidirectional else 0
    n_pre, _ = _layer_split(cfg)
    aux_sum = {"load_balance": 0.0, "router_z": 0.0}
    kvs = []

    for lp in params.get("head_layers", []):
        x, kv, aux = _block(lp, x, cfg, opts, kind=jnp.int32(0),
                            positions=positions, mask_kind=mask_kind,
                            prefix_len=prefix_len)
        kvs.append(kv)
        for k2 in aux:
            aux_sum[k2] = aux_sum.get(k2, 0.0) + aux[k2]

    kinds = _kind_array(cfg, n_pre, cfg.n_layers - n_pre)

    def scan_body(carry, xs):
        lp, kind = xs
        h, kv, aux = _block(lp, carry, cfg, opts, kind=kind,
                            positions=positions, mask_kind=mask_kind,
                            prefix_len=prefix_len)
        outs = (kv, aux) if collect_kv else (None, aux)
        return h, outs

    body = scan_body
    if opts.remat == "block":
        body = jax.checkpoint(scan_body)
    x, (kv_stack, aux_stack) = jax.lax.scan(body, x, (params["stack"], kinds))
    for k2 in aux_sum:
        if aux_stack and k2 in aux_stack:
            aux_sum[k2] = aux_sum[k2] + jnp.sum(aux_stack[k2])
    if return_hidden:
        return cm.rms_norm(x, params["final_norm"]), aux_sum
    logits = _logits(cfg, params, x)
    if collect_kv:
        return logits, aux_sum, (kvs, kv_stack)
    return logits, aux_sum


def train_loss(cfg: ArchConfig, params, batch: Dict, opts=RuntimeOptions()):
    """batch: {"tokens": (B,S), "labels": (B,S)} (+"prefix_emb" for VLM).

    Uses chunked cross-entropy: (B,S,vocab) logits never materialize."""
    h, aux = forward(cfg, params, batch["tokens"], opts,
                     prefix_emb=batch.get("prefix_emb"), return_hidden=True)
    labels = batch["labels"]
    Pfx = (batch["prefix_emb"].shape[1]
           if batch.get("prefix_emb") is not None else 0)
    S = labels.shape[1]
    h_pred = h[:, Pfx:Pfx + S - 1]
    if cfg.tie_embeddings:
        loss = cm.chunked_xent(h_pred, params["embed"]["emb"],
                               labels[:, 1:], tied=True)
    else:
        loss = cm.chunked_xent(h_pred, params["lm_head"]["w"],
                               labels[:, 1:], tied=False)
    total = loss
    if cfg.moe is not None:
        total = total + 0.01 * aux["load_balance"] + 1e-4 * aux["router_z"]
    return total, {"nll": loss, **{k: jnp.asarray(v) for k, v in aux.items()}}


# ------------------------------ serving ------------------------------- #

def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               opts: RuntimeOptions = RuntimeOptions()):
    """KV cache pytree. ``opts.cache_dtype='int8'`` enables the tiered-KV
    policy: int8 cache + per-(layer, kv-head) scales — the paper's
    "shrink the Q/K/V traffic class" realized as a bandwidth/capacity
    reduction (DESIGN.md SS3). MLA archs already compress the cache."""
    quant = opts.cache_dtype == "int8" and cfg.mla is None
    dtype = (jnp.int8 if quant else
             (jnp.dtype(opts.cache_dtype) if opts.cache_dtype else opts.jdtype))
    n_pre, _ = _layer_split(cfg)
    n_stack = cfg.n_layers - n_pre

    def one(_):
        if cfg.mla is not None:
            return mla_mod.init_mla_cache(cfg, batch, max_len, opts.jdtype)
        c = {"k": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.head_dim),
                            dtype),
             "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.head_dim),
                            dtype)}
        if quant:
            c["k_scale"] = jnp.ones((cfg.n_kv_heads,), jnp.float32)
            c["v_scale"] = jnp.ones((cfg.n_kv_heads,), jnp.float32)
        return c
    cache = {"stack": jax.vmap(one)(jnp.arange(n_stack))}
    if n_pre:
        cache["head"] = [one(None) for _ in range(n_pre)]
    return cache


def _decode_attn(p, x, cfg, opts, cache_layer, pos, *, kind):
    """Single-token attention against the cache. x: (B,1,d)."""
    B = x.shape[0]
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    positions = jnp.broadcast_to(jnp.asarray(pos)[None, None], (B, 1))
    q = cm.dense(p["wq"], x).reshape(B, 1, H, hd)
    k = cm.dense(p["wk"], x).reshape(B, 1, Hkv, hd)
    v = cm.dense(p["wv"], x).reshape(B, 1, Hkv, hd)
    q = cm.apply_rope(q, positions)
    k = cm.apply_rope(k, positions)
    quant = "k_scale" in cache_layer
    if opts.seq_shard_attn and not quant:
        from repro.models.seq_shard_attn import decode_attn_seq_sharded

        def seq_att(window):
            return decode_attn_seq_sharded(
                q, k, v, cache_layer["k"], cache_layer["v"], pos,
                opts.seq_shard_mesh, scale=hd ** -0.5,
                softcap=cfg.logit_softcap, window=window)
        if cfg.sliding_window and cfg.local_global_ratio:
            out, ck, cv = jax.lax.cond(
                kind == 1, lambda: seq_att(cfg.sliding_window),
                lambda: seq_att(0))
        elif cfg.sliding_window:
            out, ck, cv = seq_att(cfg.sliding_window)
        else:
            out, ck, cv = seq_att(0)
        out = cm.dense(p["wo"], out.reshape(B, 1, H * hd))
        return out, {"k": ck, "v": cv}
    if quant:
        # quantize the new entries with the prefill scales (tiered policy)
        ksc, vsc = cache_layer["k_scale"], cache_layer["v_scale"]
        kq = _quantize_with(k, ksc)
        vq = _quantize_with(v, vsc)
        ck, cv = cm.update_cache(cache_layer["k"], cache_layer["v"],
                                 kq, vq, pos)
        ck_f = ck.astype(q.dtype) * ksc[None, None, :, None].astype(q.dtype)
        cv_f = cv.astype(q.dtype) * vsc[None, None, :, None].astype(q.dtype)
    else:
        ck, cv = cm.update_cache(cache_layer["k"], cache_layer["v"], k, v,
                                 pos)
        ck_f, cv_f = ck.astype(q.dtype), cv.astype(q.dtype)

    def att(mk, w):
        return cm.attention(q, ck_f, cv_f,
                            mask_kind=mk, window=w, q_offset=pos,
                            softcap=cfg.logit_softcap, impl=opts.attn_impl,
                            block_q=opts.block_q, block_kv=opts.block_kv,
                            acc_dtype=opts.flash_acc)
    if cfg.sliding_window and cfg.local_global_ratio:
        out = jax.lax.cond(kind == 1,
                           lambda: att("sliding", cfg.sliding_window),
                           lambda: att("causal", 0))
    elif cfg.sliding_window:
        out = att("sliding", cfg.sliding_window)
    else:
        out = att("causal", 0)
    out = cm.dense(p["wo"], out.reshape(B, 1, H * hd))
    new_cache = {"k": ck, "v": cv}
    if quant:
        new_cache["k_scale"] = cache_layer["k_scale"]
        new_cache["v_scale"] = cache_layer["v_scale"]
    return out, new_cache


def _decode_block(lp, x, cfg, opts, cache_layer, pos, *, kind):
    x = cm.constrain(x, opts.residual_sharding)
    if cfg.mla is not None:
        h, new_cache = mla_mod.mla_decode_attn(
            lp["attn"], cm.rms_norm(x, lp["ln1"]), cfg, cache_layer, pos)
    else:
        h, new_cache = _decode_attn(lp["attn"], cm.rms_norm(x, lp["ln1"]),
                                    cfg, opts, cache_layer, pos, kind=kind)
    x = x + h
    h, _ = _ffn_apply(lp, cm.rms_norm(x, lp["ln2"]), cfg, opts)
    return x + h, new_cache


def decode_step(cfg: ArchConfig, params, token, pos, cache,
                opts: RuntimeOptions = RuntimeOptions()):
    """One new token for every sequence. token: (B,) int32; pos: scalar."""
    x = _embed_tokens(cfg, params, token[:, None], None)
    n_pre, _ = _layer_split(cfg)
    new_head = []
    for lp, cl in zip(params.get("head_layers", []), cache.get("head", [])):
        x, nc = _decode_block(lp, x, cfg, opts, cl, pos, kind=jnp.int32(0))
        new_head.append(nc)
    kinds = _kind_array(cfg, n_pre, cfg.n_layers - n_pre)

    def scan_body(carry, xs):
        lp, cl, kind = xs
        h, nc = _decode_block(lp, carry, cfg, opts, cl, pos, kind=kind)
        return h, nc
    x, new_stack = jax.lax.scan(scan_body, x,
                                (params["stack"], cache["stack"], kinds))
    logits = _logits(cfg, params, x)[:, 0]
    new_cache = {"stack": new_stack}
    if new_head:
        new_cache["head"] = new_head
    return logits, new_cache


def prefill(cfg: ArchConfig, params, tokens, cache,
            opts: RuntimeOptions = RuntimeOptions(), prefix_emb=None):
    """Run the prompt, fill the cache, return last-position logits."""
    logits, _, (kv_head, kv_stack) = forward(cfg, params, tokens, opts,
                                             prefix_emb=prefix_emb,
                                             collect_kv=True)

    def fill(buf, val):
        return jax.lax.dynamic_update_slice(
            buf, val.astype(buf.dtype), (0,) * buf.ndim)

    if cfg.mla is not None:
        new_stack = {"c": jax.vmap(fill)(cache["stack"]["c"], kv_stack[0]),
                     "k_rope": jax.vmap(fill)(cache["stack"]["k_rope"],
                                              kv_stack[1])}
    elif "k_scale" in cache["stack"]:
        def qfill(buf, val):   # per-layer quantize with fresh scales
            sc = _amax_scale(val, (0, 1, 3))               # (Hkv,)
            return fill(buf, _quantize_with(val, sc)), sc
        ks_new, ksc = jax.vmap(qfill)(cache["stack"]["k"], kv_stack[0])
        vs_new, vsc = jax.vmap(qfill)(cache["stack"]["v"], kv_stack[1])
        new_stack = {"k": ks_new, "v": vs_new, "k_scale": ksc,
                     "v_scale": vsc}
    else:
        new_stack = {"k": jax.vmap(fill)(cache["stack"]["k"], kv_stack[0]),
                     "v": jax.vmap(fill)(cache["stack"]["v"], kv_stack[1])}
    new_cache = {"stack": new_stack}
    if cache.get("head"):
        new_head = []
        for cl, kv in zip(cache["head"], kv_head):
            if cfg.mla is not None:
                new_head.append({"c": fill(cl["c"], kv[0]),
                                 "k_rope": fill(cl["k_rope"], kv[1])})
            else:
                new_head.append({"k": fill(cl["k"], kv[0]),
                                 "v": fill(cl["v"], kv[1])})
        new_cache["head"] = new_head
    return logits[:, -1], new_cache


# --------------------------- paged serving ---------------------------- #
# Page-pool KV cache for continuous batching (DESIGN.md SS10): fixed-size
# pages shared by all sequences, indirected through per-sequence page
# tables. Page 0 is reserved as the null page — padded page-table entries
# and inactive batch slots write/read it harmlessly (reads are masked by
# seq_lens, writes land on garbage nobody consumes).


def paged_supported(cfg: ArchConfig) -> Optional[str]:
    """None when the paged-KV path covers this config; else the skip reason."""
    if cfg.mla is not None:
        return "MLA latent cache is already compressed; paged path covers GQA"
    if cfg.family not in ("dense", "moe"):
        return f"family {cfg.family!r} is not covered by the paged KV path"
    if _layer_split(cfg)[0]:
        return "unscanned prefix layers not supported by the paged cache"
    if cfg.sliding_window:
        return "sliding-window layers need windowed page masking"
    if cfg.enc_layers:
        return "cross-attention caches are not paged"
    return None


def init_paged_cache(cfg: ArchConfig, n_pages: int, page_size: int,
                     opts: RuntimeOptions = RuntimeOptions()):
    """Pooled KV pages: (n_layers, n_pages, Hkv, page_size, dh) per k/v.

    Head-major, so one (page, kv-head) block is a contiguous (page_size,
    dh) tile: the Pallas kernels' K/V blocks then meet the TPU's
    (sublane, lane) tiling in bf16 and int8 alike, and a head shard of
    the pool (DESIGN.md SS16) is a slice of whole tiles.
    ``opts.cache_dtype='int8'`` stores int8 pages with per-(layer, kv-head)
    scales (statically calibrated at the first prefill — the tiered-KV
    policy of DESIGN.md SS3 applied to the page pool)."""
    reason = paged_supported(cfg)
    if reason:
        raise NotImplementedError(f"paged KV cache: {reason}")
    quant = opts.cache_dtype == "int8"
    dtype = (jnp.int8 if quant else
             (jnp.dtype(opts.cache_dtype) if opts.cache_dtype else opts.jdtype))
    shape = (cfg.n_layers, n_pages, cfg.n_kv_heads, page_size, cfg.head_dim)
    c = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if quant:
        c["k_scale"] = jnp.ones((cfg.n_layers, cfg.n_kv_heads), jnp.float32)
        c["v_scale"] = jnp.ones((cfg.n_layers, cfg.n_kv_heads), jnp.float32)
    return {"stack": c}


def layer_dma_slices(cfg: ArchConfig) -> int:
    """Natural DMA slice count for layer-overlapped page migration
    (DESIGN.md SS17): the paged pool's leading axis is ``n_layers``, so a
    page's layer-``l`` slice — ``page_bytes / n_layers`` of k+v — is one
    contiguous region per pool array, fetchable as one link of a chained
    DMA descriptor. The layer loop (``lax.scan`` over ``params["stack"]``)
    consumes slices strictly in order, which is what lets the engine
    pipeline slice ``l``'s transfer under layer ``l-1``'s compute."""
    return max(int(cfg.n_layers), 1)


def page_layer_nbytes(cfg: ArchConfig, page_size: int,
                      dtype_bytes: int = 2) -> float:
    """Bytes of ONE layer's k+v slice of a page — the chained-descriptor
    slice granularity used by layer-overlapped migration."""
    per_tok = 2 * cfg.n_kv_heads * cfg.head_dim * dtype_bytes
    return float(per_tok * page_size)


def _amax_scale(val, axes):
    """Per-kv-head symmetric int8 scale: amax/127 reduced over ``axes``."""
    return jnp.maximum(jnp.abs(val.astype(jnp.float32)).max(axes),
                       1e-6) / 127.0


def _quantize_with(val, scale):
    """val: (..., Hkv, dh); scale: (..., Hkv) absolute per-head scales."""
    return jnp.clip(jnp.round(val.astype(jnp.float32)
                              / scale[..., :, None]), -127, 127)


def _head_shards(opts: RuntimeOptions, n_kv_heads: int) -> int:
    """Shard count of ``opts.kv_shard_mesh`` when it head-divides, else 0."""
    if opts.kv_shard_mesh is None:
        return 0
    from repro.kernels import sharded as ksh
    return ksh.head_shards(opts.kv_shard_mesh, n_kv_heads)


def _gather_pages(pages, page_table, layer=None):
    """Head-major (n_pages, Hkv, ps, dh) pool rows of each sequence's
    page table -> dense (B, n_pp * ps, Hkv, dh), for the XLA path.

    With ``layer``, ``pages`` is the whole (n_layers, n_pages, ...) pool
    and one gather indexes (layer, page): slicing the layer out first
    would copy it."""
    B, n_pp = page_table.shape
    Hkv, ps, dh = pages.shape[-3:]
    g = pages[page_table] if layer is None else pages[layer, page_table]
    return g.transpose(0, 1, 3, 2, 4).reshape(B, n_pp * ps, Hkv, dh)


def _scatter_pages(pages, pid, off, rows):
    """Write ``rows`` (N, Hkv, dh) into head-major pages at (page ``pid``,
    in-page offset ``off``), both (N,). Duplicate targets (pad rows on the
    null page) land in unspecified order; nothing reads them."""
    return pages.at[pid, :, off].set(rows)


def _window_pages(page_table, start, n_valid, C: int, ps: int):
    """The pages a (B, C) chunk at per-row ``start`` writes, whole.

    A chunk touches at most ``ceil((C + ps - 1) / ps)`` pages of each
    row, whatever its alignment (3 for C=32, ps=16). Returns (pid (B, W)
    physical page ids, row (B, W, ps) the chunk row that lands in each
    page slot, ok (B, W, ps) whether it does). A slot is written when
    its row lies in the chunk, before ``n_valid`` and inside the page
    table. A window page that no slot of its row writes goes to the null
    page: rewriting it unchanged could undo another row's write to a
    page the two tables share."""
    B, n_pp = page_table.shape
    W = (C + 2 * ps - 2) // ps
    start = jnp.broadcast_to(jnp.asarray(start, jnp.int32), (B,))
    blk = start[:, None] // ps + jnp.arange(W)[None, :]           # (B, W)
    pos = blk[:, :, None] * ps + jnp.arange(ps)                   # (B, W, ps)
    row = pos - start[:, None, None]
    ok = ((row >= 0) & (row < C) & (pos < n_valid[:, None, None])
          & (blk < n_pp)[:, :, None])
    # a block past the table has no slot in ``ok``; clamp it in range
    pid = jnp.take_along_axis(page_table, jnp.minimum(blk, n_pp - 1), axis=1)
    pid = jnp.where(ok.any(-1), pid, 0)
    return pid, row, ok


def _write_window(pool, layer, pid, row, ok, rows):
    """Merge a chunk's (B, C, Hkv, dh) ``rows`` into layer ``layer`` of
    the whole (n_layers, n_pages, Hkv, ps, dh) ``pool`` at the window of
    ``_window_pages``: gather the window's pages at (layer, pid), take
    the chunk's rows where ``ok`` holds, scatter whole pages back. Only
    pages move, so the pool is updated in place; a token-row scatter
    would have the compiler re-lay the pool out around it."""
    B, W, ps = row.shape
    C = rows.shape[1]
    src = rows[jnp.arange(B)[:, None], jnp.clip(row, 0, C - 1).reshape(B, -1)]
    new = src.reshape(B, W, ps, *rows.shape[2:]).transpose(0, 1, 3, 2, 4)
    pages = jnp.where(ok[:, :, None, :, None], new.astype(pool.dtype),
                      pool[layer, pid])
    return pool.at[layer, pid].set(pages)


def _chunk_attend(q, kp, vp, ksc, vsc, page_table, start, n_valid, *,
                  cfg: ArchConfig, opts: RuntimeOptions, layer=None):
    """Attend a (B, C, H', hd) query chunk over pooled pages.

    Head counts come from the operands, not ``cfg``, so the same body
    serves the replicated pool AND one head shard of it (the per-shard
    body under ``kernels.sharded.sharded_attend``). ``ksc``/``vsc`` are
    the int8 per-head scales matching kp/vp's head slice, or None.
    kp/vp are one layer's pages, or with ``layer`` the whole pool."""
    B, C, H, hd = q.shape
    Hkv, ps = kp.shape[-3], kp.shape[-2]
    n_pp = page_table.shape[1]
    quant = ksc is not None
    if opts.attn_impl == "pallas":
        from repro.kernels import ops as kops
        if layer is not None:
            # the kernels take one layer's pages: this slice copies it
            kp, vp = kp[layer], vp[layer]
        if jnp.ndim(start) == 1:
            # per-sequence window start => speculative-verify entry (SS14)
            return kops.spec_verify_attention(
                q, kp, vp, page_table, start,
                n_valid - jnp.asarray(start, jnp.int32), scale=hd ** -0.5,
                k_scale=ksc, v_scale=vsc, softcap=cfg.logit_softcap)
        return kops.chunk_prefill_attention(
            q, kp, vp, page_table, start, n_valid, scale=hd ** -0.5,
            k_scale=ksc, v_scale=vsc, softcap=cfg.logit_softcap)
    # XLA path: gather the pages densely, causal-mask by position
    kd = _gather_pages(kp, page_table, layer)
    vd = _gather_pages(vp, page_table, layer)
    if quant:
        kd = kd.astype(q.dtype) * ksc[None, None, :, None].astype(q.dtype)
        vd = vd.astype(q.dtype) * vsc[None, None, :, None].astype(q.dtype)
    else:
        kd, vd = kd.astype(q.dtype), vd.astype(q.dtype)
    start_v = jnp.asarray(start, jnp.int32)
    if start_v.ndim == 0:
        return cm.attention(q, kd, vd, mask_kind="causal", q_offset=start,
                            kv_valid=n_valid, softcap=cfg.logit_softcap,
                            impl="xla")
    # per-sequence window start (speculative verify, SS14):
    # cm.attention's q_offset is scalar-only, so build the (B, C, L)
    # mask explicitly — same numerics as its small path otherwise
    L = n_pp * ps
    group = H // Hkv
    qpos = start_v[:, None] + jnp.arange(C)[None, :]
    qpos = jnp.minimum(qpos, n_valid[:, None] - 1)   # clip pad rows
    m = jnp.arange(L)[None, None, :] <= qpos[:, :, None]
    qg = q.reshape(B, C, Hkv, group, hd)
    s = jnp.einsum("bshgd,blhd->bshgl", qg, kd,
                   preferred_element_type=jnp.float32) * (hd ** -0.5)
    if cfg.logit_softcap:
        s = jnp.tanh(s / cfg.logit_softcap) * cfg.logit_softcap
    s = jnp.where(m[:, :, None, None, :], s, cm.NEG_INF)
    pr = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bshgl,blhd->bshgd", pr.astype(vd.dtype), vd,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, C, H, hd).astype(q.dtype)


def prefill_paged(cfg: ArchConfig, params, tokens, cache, page_table,
                  true_len, opts: RuntimeOptions = RuntimeOptions(), *,
                  calibrate: bool = False):
    """Prefill that scatters KV into pool pages instead of a dense buffer.

    tokens: (B, S) right-padded prompts with S a multiple of page_size —
    causal masking keeps pad-token KV from influencing valid positions, and
    decode later masks reads by seq_lens. page_table: (B, S // page_size)
    physical pages owned by each prompt; true_len: (B,) actual prompt
    lengths. ``calibrate=True`` (first prefill only) sets the int8 scales
    from this batch; afterwards writes clip against the frozen scales.

    Returns (logits at position true_len-1 per sequence, new cache)."""
    logits, _, (_, kv_stack) = forward(cfg, params, tokens, opts,
                                       collect_kv=True)
    st = cache["stack"]
    ps = st["k"].shape[3]
    B, S = tokens.shape
    npp = S // ps
    flat_ids = page_table.reshape(-1)                   # (B * npp,)

    def chunked(val):              # (L,B,S,Hkv,dh) -> (L,B*npp,Hkv,ps,dh)
        nl = val.shape[0]
        return (val.reshape(nl, B * npp, ps, *val.shape[3:])
                .transpose(0, 1, 3, 2, 4))

    if "k_scale" in st:
        if calibrate:
            # pad rows beyond true_len carry garbage KV — keep them out of
            # the frozen per-(layer, head) scales
            pos_ok = (jnp.arange(S)[None] < true_len[:, None]
                      )[None, :, :, None, None]
            ksc = _amax_scale(jnp.where(pos_ok, kv_stack[0], 0), (1, 2, 4))
            vsc = _amax_scale(jnp.where(pos_ok, kv_stack[1], 0), (1, 2, 4))
        else:
            ksc, vsc = st["k_scale"], st["v_scale"]
        kq = _quantize_with(kv_stack[0], ksc[:, None, None])
        vq = _quantize_with(kv_stack[1], vsc[:, None, None])
        new = {"k": st["k"].at[:, flat_ids].set(chunked(kq).astype(jnp.int8)),
               "v": st["v"].at[:, flat_ids].set(chunked(vq).astype(jnp.int8)),
               "k_scale": ksc, "v_scale": vsc}
    else:
        new = {"k": st["k"].at[:, flat_ids].set(
                   chunked(kv_stack[0]).astype(st["k"].dtype)),
               "v": st["v"].at[:, flat_ids].set(
                   chunked(kv_stack[1]).astype(st["v"].dtype))}
    last = jnp.take_along_axis(
        logits, (true_len - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    return last, {"stack": new}


def _paged_chunk_attn(p, x, cfg: ArchConfig, opts: RuntimeOptions,
                      kpool, vpool, layer, scales, positions, page_table,
                      start, n_valid, *, calibrate: bool):
    """Chunk-prefill attention of layer ``layer`` against the whole
    pooled KV pages (n_layers, n_pages, Hkv, ps, dh). x: (B, C, d).

    Writes the chunk's KV into the pages covering ``positions`` first,
    whole pages at a time (``_write_window``), then attends causally (by
    absolute position) across every page the sequence owns — previously
    cached prefix pages included. ``scales``: this layer's int8 per-head
    scales ({"k_scale", "v_scale"}), or {}. Returns (out, kpool, vpool,
    scales)."""
    B, C, _ = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = cm.dense(p["wq"], x).reshape(B, C, H, hd)
    k = cm.dense(p["wk"], x).reshape(B, C, Hkv, hd)
    v = cm.dense(p["wv"], x).reshape(B, C, Hkv, hd)
    q = cm.apply_rope(q, positions)
    k = cm.apply_rope(k, positions)
    quant = bool(scales)

    if quant:
        if calibrate:
            # first chunk of the pool's life sets the frozen scales; keep
            # the chunk's right-padding out of them
            ok = (positions < n_valid[:, None])[..., None, None]
            ksc = _amax_scale(jnp.where(ok, k, 0), (0, 1, 3))
            vsc = _amax_scale(jnp.where(ok, v, 0), (0, 1, 3))
        else:
            ksc, vsc = scales["k_scale"], scales["v_scale"]
        k_store = _quantize_with(k, ksc[None, None]).astype(jnp.int8)
        v_store = _quantize_with(v, vsc[None, None]).astype(jnp.int8)
    else:
        ksc = vsc = None
        k_store, v_store = k, v

    # write the chunk's KV at absolute positions [start, start + C); pad
    # rows past n_valid are not written, and pages past the sequence's
    # reserve (table entries 0, or past the table) are the null page
    pid, row, ok = _window_pages(page_table, start, n_valid, C,
                                 kpool.shape[3])
    kpool = _write_window(kpool, layer, pid, row, ok, k_store)
    vpool = _write_window(vpool, layer, pid, row, ok, v_store)

    n_sh = _head_shards(opts, Hkv)
    if n_sh:
        # head-sharded attend (SS16): the pool/q head dims partition over
        # the mesh, the write above already ran shard-wise under GSPMD,
        # and the per-shard body below is this very function's replicated
        # path on an Hkv/N slice — bitwise identical after the gather.
        # It takes one layer's pages, so this slice copies the layer.
        from repro.kernels import sharded as ksh

        def attend(q_l, kp_l, vp_l, ks_l, vs_l, pt, st, nv):
            return _chunk_attend(q_l, kp_l, vp_l,
                                 ks_l if quant else None,
                                 vs_l if quant else None,
                                 pt, st, nv, cfg=cfg, opts=opts)
        ones = jnp.ones((Hkv,), jnp.float32)
        out = ksh.sharded_attend(
            opts.kv_shard_mesh, attend, q, kpool[layer], vpool[layer],
            ksc if quant else ones, vsc if quant else ones,
            (page_table, jnp.asarray(start, jnp.int32), n_valid),
            q_head_axis=2)
    else:
        out = _chunk_attend(q, kpool, vpool, ksc, vsc, page_table, start,
                            n_valid, cfg=cfg, opts=opts, layer=layer)
    out = cm.dense(p["wo"], out.reshape(B, C, H * hd))
    new_scales = {"k_scale": ksc, "v_scale": vsc} if quant else {}
    return out, kpool, vpool, new_scales


def _paged_chunk_layers(cfg: ArchConfig, params, x, cache, positions,
                        page_table, start, n_valid, opts: RuntimeOptions,
                        calibrate: bool):
    """The layer stack over a (B, C) chunk against the paged pool.

    The pool's k and v ride in the scan's carry and every layer writes
    them in place (``_paged_chunk_attn``): with the pool as scan xs/ys,
    each layer would slice its layer out and the scan would stack a
    fresh copy of the pool. The int8 per-layer scales, small, stay xs
    and ys. Returns (x, new cache)."""
    st = cache["stack"]
    scales = {n: st[n] for n in ("k_scale", "v_scale") if n in st}

    def scan_body(carry, xs):
        h, kp, vp = carry
        lp, layer, sc = xs
        h = cm.constrain(h, opts.residual_sharding)
        a, kp, vp, sc = _paged_chunk_attn(
            lp["attn"], cm.rms_norm(h, lp["ln1"]), cfg, opts, kp, vp,
            layer, sc, positions, page_table, start, n_valid,
            calibrate=calibrate)
        h = h + a
        f, _ = _ffn_apply(lp, cm.rms_norm(h, lp["ln2"]), cfg, opts)
        return (h + f, kp, vp), sc
    layers = jnp.arange(st["k"].shape[0], dtype=jnp.int32)
    (x, kp, vp), sc = jax.lax.scan(scan_body, (x, st["k"], st["v"]),
                                   (params["stack"], layers, scales))
    return x, {"stack": {"k": kp, "v": vp, **sc}}


def prefill_paged_chunk(cfg: ArchConfig, params, tokens, cache, page_table,
                        start, n_valid,
                        opts: RuntimeOptions = RuntimeOptions(), *,
                        calibrate: bool = False):
    """One fixed-size prefill chunk against the paged pool (DESIGN.md SS11).

    tokens: (B, C) the chunk's tokens, right-padded; page_table: (B,
    n_pages_per_seq) the sequence's full padded table; start: scalar int32
    absolute position of tokens[:, 0] (earlier positions already hold valid
    KV — from previous chunks or shared prefix pages); n_valid: (B,) total
    valid tokens once this chunk lands (= start + true chunk length).

    The fixed (B, C) shape is the point: every prompt, whatever its length
    or cache hit, prefills through this one compiled program instead of
    compiling per padded prompt length. ``calibrate=True`` (first chunk
    only) sets the int8 scales. Returns (logits (B, C, vocab), new cache).
    """
    B, C = tokens.shape
    x = _embed_tokens(cfg, params, tokens, None)
    start = jnp.asarray(start, jnp.int32)
    positions = jnp.broadcast_to(start + jnp.arange(C)[None, :], (B, C))
    x, cache = _paged_chunk_layers(cfg, params, x, cache, positions,
                                   page_table, start, n_valid, opts,
                                   calibrate)
    return _logits(cfg, params, x), cache


def copy_pages(cache, pairs):
    """Apply queued copy-on-write page copies to the pool.

    pairs: (N, 2) int32 (src, dst) physical page ids — the output of
    ``PagedKVManager.drain_copies``. Must run before the next KV write."""
    st = cache["stack"]
    src, dst = pairs[:, 0], pairs[:, 1]
    new = dict(st)
    new["k"] = st["k"].at[:, dst].set(st["k"][:, src])
    new["v"] = st["v"].at[:, dst].set(st["v"][:, src])
    return {"stack": new}


def _decode_attend(q, kp, vp, ksc, vsc, page_table, valid, *,
                   cfg: ArchConfig, opts: RuntimeOptions):
    """Attend a (B, 1, H', hd) single-position query over pooled pages.

    Head counts come from the operands (see ``_chunk_attend``) so the
    body runs unchanged on one head shard of the pool."""
    hd = q.shape[-1]
    if opts.attn_impl == "pallas":
        from repro.kernels import ops as kops
        return kops.paged_decode_attention(
            q[:, 0], kp, vp, page_table, valid, scale=hd ** -0.5,
            k_scale=ksc, v_scale=vsc,
            softcap=cfg.logit_softcap)[:, None]          # (B, 1, H, hd)
    # XLA path: gather the sequence's pages densely, mask by seq_lens
    kd = _gather_pages(kp, page_table)
    vd = _gather_pages(vp, page_table)
    if ksc is not None:
        kd = kd.astype(q.dtype) * ksc[None, None, :, None].astype(q.dtype)
        vd = vd.astype(q.dtype) * vsc[None, None, :, None].astype(q.dtype)
    else:
        kd, vd = kd.astype(q.dtype), vd.astype(q.dtype)
    return cm.attention(q, kd, vd, mask_kind="full", kv_valid=valid,
                        softcap=cfg.logit_softcap, impl="xla")


def _paged_decode_attn(p, x, cfg: ArchConfig, opts: RuntimeOptions,
                       cache_layer, seq_lens, page_table):
    """Single-token attention against pooled KV pages. x: (B, 1, d)."""
    B = x.shape[0]
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    positions = seq_lens[:, None]                       # ragged positions
    q = cm.dense(p["wq"], x).reshape(B, 1, H, hd)
    k = cm.dense(p["wk"], x).reshape(B, 1, Hkv, hd)
    v = cm.dense(p["wv"], x).reshape(B, 1, Hkv, hd)
    q = cm.apply_rope(q, positions)
    k = cm.apply_rope(k, positions)
    quant = "k_scale" in cache_layer
    kp, vp = cache_layer["k"], cache_layer["v"]
    ps = kp.shape[2]

    if quant:
        ksc, vsc = cache_layer["k_scale"], cache_layer["v_scale"]
        k_store = _quantize_with(k[:, 0], ksc[None]).astype(jnp.int8)
        v_store = _quantize_with(v[:, 0], vsc[None]).astype(jnp.int8)
    else:
        ksc = vsc = None
        k_store, v_store = k[:, 0].astype(kp.dtype), v[:, 0].astype(vp.dtype)

    # write the new token's KV at (page_table[b, len//ps], len % ps); the
    # page id collapses to the null page for inactive slots (pt == 0)
    pid = jnp.take_along_axis(page_table, (seq_lens // ps)[:, None],
                              axis=1)[:, 0]
    kp = _scatter_pages(kp, pid, seq_lens % ps, k_store)
    vp = _scatter_pages(vp, pid, seq_lens % ps, v_store)
    valid = seq_lens + 1

    n_sh = _head_shards(opts, Hkv)
    if n_sh:
        from repro.kernels import sharded as ksh

        def attend(q_l, kp_l, vp_l, ks_l, vs_l, pt, vl):
            return _decode_attend(q_l, kp_l, vp_l,
                                  ks_l if quant else None,
                                  vs_l if quant else None,
                                  pt, vl, cfg=cfg, opts=opts)
        ones = jnp.ones((Hkv,), jnp.float32)
        out = ksh.sharded_attend(
            opts.kv_shard_mesh, attend, q, kp, vp,
            ksc if quant else ones, vsc if quant else ones,
            (page_table, valid), q_head_axis=2)
    else:
        out = _decode_attend(q, kp, vp, ksc, vsc, page_table, valid,
                             cfg=cfg, opts=opts)
    out = cm.dense(p["wo"], out.reshape(B, 1, H * hd))
    new_cache = {"k": kp, "v": vp}
    if quant:
        new_cache["k_scale"] = cache_layer["k_scale"]
        new_cache["v_scale"] = cache_layer["v_scale"]
    return out, new_cache


def decode_step_paged(cfg: ArchConfig, params, token, seq_lens, page_table,
                      cache, opts: RuntimeOptions = RuntimeOptions()):
    """One ragged decode step over the paged pool.

    token: (B,) int32 last sampled token per slot; seq_lens: (B,) tokens
    already cached (the new token lands at this position); page_table:
    (B, n_pages_per_seq). Inactive slots (page_table rows all zero,
    seq_len 0) write to the null page and produce ignorable logits.
    Returns (logits (B, V), new cache)."""
    x = _embed_tokens(cfg, params, token[:, None], None)

    def scan_body(carry, xs):
        lp, cl = xs
        h = cm.constrain(carry, opts.residual_sharding)
        a, nc = _paged_decode_attn(lp["attn"], cm.rms_norm(h, lp["ln1"]),
                                   cfg, opts, cl, seq_lens, page_table)
        h = h + a
        f, _ = _ffn_apply(lp, cm.rms_norm(h, lp["ln2"]), cfg, opts)
        return h + f, nc
    x, new_stack = jax.lax.scan(scan_body, x, (params["stack"], cache["stack"]))
    logits = _logits(cfg, params, x)[:, 0]
    return logits, {"stack": new_stack}


# ------------------------ fused multi-step decode ---------------------- #
# DESIGN.md SS12: the decode hot loop pays one host round-trip per token
# when sampling happens on the host. The fused path scans K micro-steps on
# device — sample (greedy or stochastic from carried per-slot keys), write
# KV, advance lengths, latch an EOS/budget done-mask — and hands the host
# a (B, K) token block per sync.


def sample_greedy(logits, temperature: float = 0.0):
    """Back-compat shim over ``repro.models.sampling`` (the real home of
    on-device token choice since SS14). Greedy argmax matches ``np.argmax``
    exactly (both take the first maximum), which is what keeps the fused
    path token-identical to the host-sampled loop. Stochastic sampling
    needs a per-slot PRNG key — use ``sampling.sample(logits, keys, ...)``
    (threaded through the fused scan by ``decode_steps_paged(keys=...)``)."""
    if temperature != 0.0:
        raise ValueError(
            "sample_greedy is greedy-only; stochastic sampling lives in "
            "repro.models.sampling.sample and needs per-slot PRNG keys")
    return sampling_mod.sample_greedy(logits)


def decode_steps_paged(cfg: ArchConfig, params, tokens, seq_lens, page_table,
                       cache, n_steps: int,
                       opts: RuntimeOptions = RuntimeOptions(), *,
                       eos_id: Optional[int] = None, pad_id: int = 0,
                       temperature: float = 0.0, top_k: int = 0,
                       top_p: float = 1.0, keys=None, done=None, quota=None):
    """Fused K-step decode over the paged pool (DESIGN.md SS12).

    ``jax.lax.scan`` over ``n_steps`` micro-steps: each step writes the
    carried token's KV at its slot's current length, attends, samples the
    next token on device, and advances per-slot lengths — no host sync
    until the whole (B, n_steps) block is pulled. Every KV position the
    scan writes must be page-backed up front (``PagedKVManager.
    reserve_ahead``): the scan cannot allocate.

    tokens: (B,) last sampled token per slot (its KV is written by the
    first micro-step); seq_lens: (B,) tokens whose KV already landed;
    done: (B,) bool slots that start inactive (their page-table rows are
    masked to the null page, they emit ``pad_id``); quota: (B,) int32 max
    tokens each slot may emit this block (default ``n_steps``) — the
    device-side mirror of each request's remaining budget. A slot latches
    done after emitting EOS (``eos_id``) or exhausting its quota; latched
    slots stop advancing lengths and their writes land on the null page.

    Sampling: greedy argmax at ``temperature<=0``; otherwise
    temperature/top-k/top-p from ``keys`` — (B, 2) uint32 per-slot PRNG
    keys threaded through the scan carry (each micro-step splits its slot
    key, consuming one stream element per emitted token, so a request's
    randomness depends only on its own key lineage, never on batch
    composition). When ``keys`` is given the return is a 3-tuple
    ``(tokens, cache, advanced_keys)``; the caller must carry the
    advanced keys into the next block.

    With ``n_steps=1`` this is exactly ``decode_step_paged`` + host
    sampling (the K=1 engine equivalence guarantee). Returns ((B, n_steps)
    int32 token block, new cache[, advanced keys])."""
    B = tokens.shape[0]
    if temperature > 0.0 and keys is None:
        raise ValueError("stochastic fused decode needs per-slot PRNG keys "
                         "(keys=(B, 2) uint32)")
    if done is None:
        done = jnp.zeros((B,), bool)
    if quota is None:
        quota = jnp.full((B,), n_steps, jnp.int32)
    quota = jnp.asarray(quota, jnp.int32)
    stochastic = keys is not None and temperature > 0.0

    def micro_step(carry, _):
        tok, lens, dn, n_emit, ks, c = carry
        # latched slots write into (and read from) the null page only
        pt = jnp.where(dn[:, None], 0, page_table)
        logits, c = decode_step_paged(cfg, params, tok, lens, pt, c, opts)
        if stochastic:
            sub = sampling_mod.split_keys(ks, 2)          # (B, 2, 2)
            step_keys, ks = sub[:, 0], sub[:, 1]
            chosen = sampling_mod.sample(logits, step_keys,
                                         temperature=temperature,
                                         top_k=top_k, top_p=top_p)
        else:
            chosen = sampling_mod.sample_greedy(logits)
        nxt = jnp.where(dn, jnp.int32(pad_id), chosen)
        n_emit = n_emit + jnp.where(dn, 0, 1)
        new_dn = dn | (n_emit >= quota)
        if eos_id is not None:
            new_dn = new_dn | (~dn & (nxt == eos_id))
        lens = jnp.where(dn, lens, lens + 1)   # this step's write landed
        return (nxt, lens, new_dn, n_emit, ks, c), nxt

    init_keys = (jnp.asarray(keys, jnp.uint32) if keys is not None
                 else jnp.zeros((B, 2), jnp.uint32))
    init = (jnp.asarray(tokens, jnp.int32), jnp.asarray(seq_lens, jnp.int32),
            done, jnp.zeros((B,), jnp.int32), init_keys, cache)
    (_, _, _, _, out_keys, cache), toks = jax.lax.scan(micro_step, init, None,
                                                       length=n_steps)
    toks = jnp.moveaxis(toks, 0, 1)
    if keys is not None:
        return toks, cache, out_keys
    return toks, cache


# ------------------------- speculative decoding ------------------------ #
# DESIGN.md SS14: a draft (n-gram lookup or a small model) proposes up to
# K tokens; ONE paged multi-query verify pass scores the whole window
# against the target model; leftover/rejection sampling keeps the output
# distribution exactly the target's. Every accepted draft token amortizes
# a full weight + KV streaming pass — the bandwidth lever the paper's
# interactivity analysis asks for on constrained platforms.


def decode_verify_paged(cfg: ArchConfig, params, tokens, seq_lens, n_fed,
                        page_table, cache,
                        opts: RuntimeOptions = RuntimeOptions()):
    """One paged multi-query pass over a (B, C) token window (SS14).

    tokens: (B, C) window ``[t_last, d_1 .. d_{C-1}]`` per slot —
    t_last is the last committed token (its KV has NOT landed yet; the
    pass writes it, exactly like the first micro-step of the fused scan)
    followed by draft proposals; seq_lens: (B,) tokens whose KV already
    landed (the window starts there); n_fed: (B,) real window tokens per
    slot (<= C; shorter drafts right-pad). All C KV positions a slot may
    write must be page-backed (``reserve_ahead(draft_len + 1)``).

    Logits row j of slot b is the target distribution for the token AFTER
    window token j — rows 0..n_fed-2 verify the draft, row n_fed-1 is the
    correction/bonus row. Pad rows beyond the fed window write no KV.
    Returns (logits (B, C, vocab), new cache)."""
    B, C = tokens.shape
    x = _embed_tokens(cfg, params, jnp.asarray(tokens, jnp.int32), None)
    seq_lens = jnp.asarray(seq_lens, jnp.int32)
    n_valid = seq_lens + jnp.asarray(n_fed, jnp.int32)
    positions = seq_lens[:, None] + jnp.arange(C)[None, :]
    x, cache = _paged_chunk_layers(cfg, params, x, cache, positions,
                                   page_table, seq_lens, n_valid, opts,
                                   calibrate=False)
    return _logits(cfg, params, x), cache


def spec_decode_verify(cfg: ArchConfig, params, tokens, draft_len, seq_lens,
                       page_table, cache, keys,
                       opts: RuntimeOptions = RuntimeOptions(), *,
                       temperature: float = 0.0, top_k: int = 0,
                       top_p: float = 1.0, pad_id: int = 0):
    """Verify a draft window and accept/reject in one device round (SS14).

    tokens: (B, C) fed window ``[t_last, d_1 .. d_{C-1}]``; draft_len:
    (B,) real proposals per slot (<= C-1; the pass feeds draft_len + 1
    tokens); keys: (B, 2) per-slot PRNG keys (unused at temperature 0 and
    returned unchanged there). Emits ``n_acc + 1`` tokens per active slot
    — accepted draft prefix plus one corrected/bonus token — so progress
    is always >= 1 token per pass, and at temperature 0 the emitted
    stream is token-identical to non-speculative greedy decode.

    Returns (out (B, C) int32 [row: accepted drafts, correction, pads],
    n_acc (B,), advanced keys (B, 2), new cache)."""
    draft_len = jnp.asarray(draft_len, jnp.int32)
    logits, cache = decode_verify_paged(cfg, params, tokens, seq_lens,
                                        draft_len + 1, page_table, cache,
                                        opts)
    out, n_acc, new_keys = sampling_mod.spec_accept(
        logits, jnp.asarray(tokens, jnp.int32)[:, 1:], draft_len, keys,
        temperature=temperature, top_k=top_k, top_p=top_p, pad_id=pad_id)
    return out, n_acc, new_keys, cache
