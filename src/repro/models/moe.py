"""Mixture-of-experts FFN (pure JAX, EP-shardable).

Dispatch is sort-free and dense-einsum-free on the expert axis: tokens are
sorted by expert id and run through ``jax.lax.ragged_dot`` grouped GEMMs, so
compiled FLOPs equal routed FLOPs (top-k of E), which keeps the roofline's
MODEL_FLOPS/HLO_FLOPS honest. Supports DeepSeek-style shared experts and
Arctic-style parallel dense residual (configured via ``MoEConfig``).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import common as cm


def init_moe(key, cfg: ArchConfig, dtype):
    m = cfg.moe
    d, e = cfg.d_model, m.n_experts
    dff = m.d_ff_expert
    keys = jax.random.split(key, 8)
    n_up = 2 * dff if cfg.gated_mlp else dff
    p = {
        "router": cm.dense_init(keys[0], d, e, jnp.float32),
        "w_up": (jax.random.normal(keys[1], (e, d, n_up), jnp.float32)
                 * (d ** -0.5)).astype(dtype),
        "w_down": (jax.random.normal(keys[2], (e, dff, d), jnp.float32)
                   * (dff ** -0.5)).astype(dtype),
    }
    if m.n_shared:
        p["shared"] = init_dense_ffn(keys[3], cfg, dff * m.n_shared, dtype)
    if m.dense_residual:
        p["residual"] = init_dense_ffn(keys[4], cfg,
                                       m.d_ff_dense or cfg.d_ff, dtype)
    return p


def init_dense_ffn(key, cfg: ArchConfig, d_ff: int, dtype):
    k1, k2 = jax.random.split(key)
    n_up = 2 * d_ff if cfg.gated_mlp else d_ff
    return {"up": cm.dense_init(k1, cfg.d_model, n_up, dtype),
            "down": cm.dense_init(k2, d_ff, cfg.d_model, dtype)}


def dense_ffn(p, x, gated: bool):
    h = cm.dense(p["up"], x)
    if gated:
        gate, up = jnp.split(h, 2, axis=-1)
        h = cm.swiglu(gate, up)
    else:
        h = jax.nn.gelu(h)
    return cm.dense(p["down"], h)


def _act(h, gated: bool):
    if gated:
        gate, up = jnp.split(h, 2, axis=-1)
        return cm.swiglu(gate, up)
    return jax.nn.gelu(h)


def _ragged_path(p, xf, expert_ids, gate_vals, m, gated: bool):
    """Sort + ragged_dot grouped GEMM (true ragged; best on TPU runtime)."""
    T, k = expert_ids.shape
    flat_expert = expert_ids.reshape(-1)
    order = jnp.argsort(flat_expert)
    inv = jnp.argsort(order)
    xs = jnp.repeat(xf, k, axis=0)[order]
    group_sizes = jnp.bincount(flat_expert, length=m.n_experts).astype(
        jnp.int32)
    h = jax.lax.ragged_dot(xs, p["w_up"], group_sizes)
    h = _act(h, gated)
    ys = jax.lax.ragged_dot(h, p["w_down"], group_sizes)
    ys = ys[inv].reshape(T, k, -1)
    return jnp.einsum("tkd,tk->td", ys.astype(jnp.float32), gate_vals)


def _capacity_path(p, xf, expert_ids, gate_vals, m, gated: bool,
                   capacity_factor: float, expert_sharding=None,
                   out_sharding=None):
    """Capacity-dropped dispatch via batched expert GEMMs.

    Compiled FLOPs = E*C*ffn = tokens*top_k*capacity_factor*ffn — only the
    slack factor above routed FLOPs (ragged_dot's generic lowering counts
    dense T x E work, which would poison the roofline's useful-FLOPs ratio).
    """
    T, k = expert_ids.shape
    E = m.n_experts
    C = max(int(T * k * capacity_factor / E), 1)
    flat_expert = expert_ids.reshape(-1)                      # (T*k,)
    order = jnp.argsort(flat_expert)                          # slot -> T*k idx
    sorted_eid = flat_expert[order]
    group_start = jnp.cumsum(
        jnp.bincount(flat_expert, length=E)).astype(jnp.int32)
    start_of = jnp.concatenate([jnp.zeros((1,), jnp.int32), group_start[:-1]])
    rank = jnp.arange(T * k, dtype=jnp.int32) - start_of[sorted_eid]
    keep = rank < C
    # slot table (E, C): original replica index, or T*k (drop sentinel)
    dest = jnp.where(keep, sorted_eid * C + rank, E * C)  # E*C is OOB -> drop
    slot = jnp.full((E * C,), T * k, jnp.int32)
    slot = slot.at[dest].set(order, mode="drop").reshape(E, C)
    xpad = jnp.concatenate([xf, jnp.zeros((1,) + xf.shape[1:], xf.dtype)], 0)
    tok_idx = jnp.where(slot < T * k, slot // k, T)            # T = pad row
    xg = cm.constrain(xpad[tok_idx], expert_sharding)          # (E, C, d)
    h = jnp.einsum("ecd,edf->ecf", xg, p["w_up"])
    h = _act(h, gated)
    yg = cm.constrain(jnp.einsum("ecf,efd->ecd", h, p["w_down"]),
                      expert_sharding)                         # (E, C, d)
    # combine back by INVERSE GATHER (each token-replica reads its slot row)
    # — a scatter-add here materializes a replicated (T, d) f32 buffer and
    # an all-reduce over it per layer (~2.3 TB/step on arctic prefill_32k)
    slot_of = jnp.full((T * k,), E * C, jnp.int32).at[order].set(
        jnp.where(keep, dest, E * C))                          # (T*k,)
    ygpad = jnp.concatenate(
        [yg.reshape(E * C, -1),
         jnp.zeros((1, yg.shape[-1]), yg.dtype)], axis=0)
    ys = cm.constrain(ygpad[slot_of], out_sharding)            # (T*k, d)
    out = jnp.einsum("tkd,tk->td", ys.reshape(T, k, -1).astype(jnp.float32),
                     gate_vals)
    return cm.constrain(out, out_sharding)


def _shard_map_path(p, xf, m, gated: bool, capacity_factor: float, mesh):
    """Shard-local EP dispatch (SSPerf iteration 4, the fix that held).

    Everything is LOCAL: each data shard routes its own tokens and runs
    them through the model-sharded experts it co-hosts; the only
    collective is a psum of the (T_local, d) combine over "model"
    (~30 MB/layer vs ~65 GB/layer of f32 masked all-reduces that GSPMD
    emits for cross-shard dispatch gathers)."""
    E, k = m.n_experts, m.top_k
    ms = mesh.shape.get("model", 1)
    E_loc = E // ms
    ba = tuple(a for a in ("pod", "data") if a in mesh.shape) or None

    def body(xf_l, router_w, w_up_l, w_down_l):
        T_l, d = xf_l.shape
        C = max(int(T_l * k * capacity_factor / E), 1)
        logits = xf_l.astype(jnp.float32) @ router_w          # (T_l, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_ids = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)
        flat = expert_ids.reshape(-1)
        order = jnp.argsort(flat)
        sorted_eid = flat[order]
        start_of = jnp.concatenate([
            jnp.zeros((1,), jnp.int32),
            jnp.cumsum(jnp.bincount(flat, length=E)).astype(jnp.int32)[:-1]])
        rank = jnp.arange(T_l * k, dtype=jnp.int32) - start_of[sorted_eid]
        keep = rank < C
        dest = jnp.where(keep, sorted_eid * C + rank, E * C)
        slot = jnp.full((E * C,), T_l * k, jnp.int32)
        slot = slot.at[dest].set(order, mode="drop")
        i = jax.lax.axis_index("model")
        slot_loc = jax.lax.dynamic_slice_in_dim(
            slot, i * E_loc * C, E_loc * C).reshape(E_loc, C)
        xpad = jnp.concatenate(
            [xf_l, jnp.zeros((1, d), xf_l.dtype)], axis=0)
        tok_idx = jnp.where(slot_loc < T_l * k, slot_loc // k, T_l)
        xg = xpad[tok_idx]                                    # (E_loc, C, d)
        h = jnp.einsum("ecd,edf->ecf", xg, w_up_l)
        h = _act(h, gated)
        yg = jnp.einsum("ecf,efd->ecd", h, w_down_l)          # (E_loc, C, d)
        # local inverse-gather combine
        slot_of = jnp.full((T_l * k,), E * C, jnp.int32).at[order].set(
            jnp.where(keep, dest, E * C))
        e_of = slot_of // C
        local = (e_of >= i * E_loc) & (e_of < (i + 1) * E_loc)
        loc_idx = jnp.where(local, slot_of - i * E_loc * C, E_loc * C)
        ygpad = jnp.concatenate(
            [yg.reshape(E_loc * C, d),
             jnp.zeros((1, d), yg.dtype)], axis=0)
        ys = ygpad[jnp.minimum(loc_idx, E_loc * C)]
        ys = jnp.where(local[:, None], ys, 0)
        out = jnp.einsum("tkd,tk->td",
                         ys.reshape(T_l, k, d).astype(jnp.float32),
                         gate_vals)
        out = jax.lax.psum(out, "model")
        # aux stats (replicated over model; psum-free)
        me = probs.mean(axis=0)
        ce = jnp.zeros((E,)).at[flat].add(1.0) / (T_l * k)
        lb = E * jnp.sum(me * ce)
        rz = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
        return out, lb, rz

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(jax.sharding.PartitionSpec(ba, None),
                  jax.sharding.PartitionSpec(None, None),
                  jax.sharding.PartitionSpec("model", None, None),
                  jax.sharding.PartitionSpec("model", None, None)),
        out_specs=(jax.sharding.PartitionSpec(ba, None),
                   jax.sharding.PartitionSpec(),
                   jax.sharding.PartitionSpec()),
        check_vma=False)
    return fn(xf, p["router"]["w"], p["w_up"], p["w_down"])


def moe_ffn(p, x, cfg: ArchConfig, *, impl: str = "capacity",
            capacity_factor: float = 1.25, expert_sharding=None,
            out_sharding=None, shard_map_mesh=None):
    """x: (B, S, d) -> (B, S, d), plus aux-loss dict."""
    m = cfg.moe
    B, S, d = x.shape
    T, k = B * S, m.top_k
    xf = x.reshape(T, d)

    logits = cm.dense(p["router"], xf.astype(jnp.float32))      # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)              # (T, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    if impl == "shard_map" and shard_map_mesh is not None:
        out, lb, rz = _shard_map_path(p, xf, m, cfg.gated_mlp,
                                      capacity_factor, shard_map_mesh)
        out = out.astype(x.dtype)
        if m.n_shared:
            out = out + dense_ffn(p["shared"], xf, cfg.gated_mlp)
        if m.dense_residual:
            out = out + dense_ffn(p["residual"], xf, cfg.gated_mlp)
        return out.reshape(B, S, d), {"load_balance": lb, "router_z": rz}
    if impl == "ragged":
        out = _ragged_path(p, xf, expert_ids, gate_vals, m, cfg.gated_mlp)
    else:
        out = _capacity_path(p, xf, expert_ids, gate_vals, m, cfg.gated_mlp,
                             capacity_factor, expert_sharding, out_sharding)
    out = out.astype(x.dtype)

    if m.n_shared:
        out = out + dense_ffn(p["shared"], xf, cfg.gated_mlp)
    if m.dense_residual:
        out = out + dense_ffn(p["residual"], xf, cfg.gated_mlp)

    # load-balance aux loss (Switch-style)
    me = probs.mean(axis=0)
    ce = jnp.zeros((m.n_experts,)).at[expert_ids.reshape(-1)].add(
        1.0) / (T * k)
    aux = {"load_balance": m.n_experts * jnp.sum(me * ce),
           "router_z": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)}
    return out.reshape(B, S, d), aux
