"""Path-based sharding rules: DP / TP / EP / FSDP(ZeRO-3) over the
production mesh axes ("pod", "data", "model").

Conventions
-----------
* batch dims shard over ("pod","data") (all data-parallel axes).
* weight matrices: the "feature-out" dim shards over "model" (TP); with
  ``param_mode='fsdp'`` the other large dim additionally shards over
  ("pod","data") — GSPMD inserts the per-layer all-gathers (ZeRO-3),
  which is what makes the 236B/480B configs fit 16 GB HBM chips.
* MoE expert dim shards over "model" (EP).
* KV caches shard heads over "model" when divisible, else the LENGTH dim
  (sequence sharding — GSPMD turns the decode softmax into a collective).
* Small vectors (norms, biases, router) replicate.
"""
from __future__ import annotations

import re
from typing import Dict, Tuple

import jax
from jax.sharding import AxisType, Mesh, PartitionSpec as P

from repro.configs.base import ArchConfig


def auto_mesh(shape, axes, devices=None) -> Mesh:
    """A mesh whose axes are all ``Auto``: shardings propagate by GSPMD and
    ``with_sharding_constraint``, which is how every rule here is written
    (``jax.make_mesh`` defaults to ``Explicit`` axes, under which those
    constraints are refused)."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def mesh_axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.shape else 1


def batch_axes(mesh: Mesh):
    axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    return axes if axes else None


def _dp_size(mesh: Mesh) -> int:
    return mesh_axis_size(mesh, "pod") * mesh_axis_size(mesh, "data")


# stacked-layer prefixes get a leading None (scan) dim
_STACK_RE = re.compile(
    r"(stack|head_layers\[\d+\]|mamba|site_proj|enc_stack|dec_stack)")


def _path_str(path) -> str:
    out = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            out.append(str(p.key))
        elif isinstance(p, jax.tree_util.SequenceKey):
            out.append(f"[{p.idx}]")
        else:
            out.append(str(p))
    return "/".join(out)


def _weight_rule(path: str, shape, mesh: Mesh, mode: str,
                 cfg: ArchConfig) -> Tuple:
    """Spec for the trailing (non-stack) dims of one parameter."""
    model_ok = lambda n: n % mesh_axis_size(mesh, "model") == 0
    dp = _dp_size(mesh)
    ba = batch_axes(mesh)

    def fsdp_dim(spec_list, skip):
        """shard the largest remaining None dim over the DP axes."""
        if mode != "fsdp" or ba is None:
            return spec_list
        best, best_n = None, 0
        for i, s in enumerate(spec_list):
            if s is None and i != skip and shape[i] % dp == 0 and shape[i] > best_n:
                best, best_n = i, shape[i]
        if best is not None and best_n >= 1024:
            spec_list[best] = ba
        return spec_list

    nd = len(shape)
    # ---- embeddings ----
    if path.endswith("embed/emb"):
        return tuple(fsdp_dim(["model" if model_ok(shape[0]) else None, None],
                              0))
    if "lm_head" in path and nd == 2:
        return tuple(fsdp_dim([None, "model" if model_ok(shape[1]) else None],
                              1))
    if "pos_dec" in path:
        return (None,) * nd
    # ---- MoE expert stacks: (E, in, out) ----
    if re.search(r"moe/w_(up|down)", path) or (
            "w_up" in path or "w_down" in path) and nd == 3:
        e_sh = "model" if model_ok(shape[0]) else None
        return tuple(fsdp_dim([e_sh, None, None], 0))
    # ---- MLA per-head stacks: (H, r, d) ----
    if re.search(r"(k_up|v_up)$", path) and nd == 3:
        return tuple(fsdp_dim(
            ["model" if model_ok(shape[0]) else None, None, None], 0))
    # ---- generic 2D dense weights ----
    if nd == 2 and path.endswith("/w"):
        if re.search(r"(wq|wk|wv|q_up|q_down|kv_down|up|in_proj|fc1|router)",
                     path):
            col = "model" if model_ok(shape[1]) else None
            return tuple(fsdp_dim([None, col], 1))
        if re.search(r"(wo|o_proj|down|out_proj|fc2|site_proj)", path):
            row = "model" if model_ok(shape[0]) else None
            return tuple(fsdp_dim([row, None], 0))
        col = "model" if model_ok(shape[1]) else None
        return tuple(fsdp_dim([None, col], 1))
    # ---- biases of column-parallel layers ----
    if nd == 1 and path.endswith("/b"):
        return ("model",) if model_ok(shape[0]) and shape[0] >= 1024 else (None,)
    if "conv_w" in path and nd == 2:
        return (None, "model" if model_ok(shape[1]) else None)
    if "conv_b" in path and nd == 1:
        return ("model",) if model_ok(shape[0]) else (None,)
    return (None,) * nd


def param_pspecs(cfg: ArchConfig, params_shapes, mesh: Mesh,
                 mode: str = "fsdp"):
    """PartitionSpec tree mirroring the params tree.

    ``params_shapes``: pytree of ShapeDtypeStruct (jax.eval_shape output)."""
    def rule(path, leaf):
        ps = _path_str(path)
        shape = leaf.shape
        m = _STACK_RE.search(ps)
        lead = 0
        if m and m.group(1) != "site_proj" and "head_layers" not in m.group(1):
            lead = 1
        elif m and m.group(1) == "site_proj":
            lead = 1
        body = _weight_rule(ps, shape[lead:], mesh, mode, cfg)
        return P(*((None,) * lead + tuple(body)))
    return jax.tree_util.tree_map_with_path(rule, params_shapes)


def opt_state_pspec(pspec: P, shape, mesh: Mesh) -> P:
    """ZeRO-1: moments/master copies inherit the param spec, further
    sharding the largest replicated dim over the DP axes when divisible."""
    dp = _dp_size(mesh)
    ba = batch_axes(mesh)
    if ba is None:
        return pspec
    spec = list(pspec) + [None] * (len(shape) - len(pspec))
    flat = [s for s in spec]
    if any(s is not None and ("data" in (s if isinstance(s, tuple) else (s,)))
           for s in flat if s):
        return pspec  # already DP-sharded (fsdp param)
    best, best_n = None, 0
    for i, s in enumerate(spec):
        if s is None and shape[i] % dp == 0 and shape[i] > best_n:
            best, best_n = i, shape[i]
    if best is not None and best_n >= 256:
        spec[best] = ba
    return P(*spec)


def effective_batch_axes(mesh: Mesh, global_batch: int):
    """Batch sharding axes, or None when the batch doesn't divide DP."""
    ba = batch_axes(mesh)
    if ba is None or global_batch % _dp_size(mesh) != 0:
        return None
    return ba


def data_pspecs(cfg: ArchConfig, mesh: Mesh, kind: str,
                global_batch: int = 0) -> Dict[str, P]:
    """Input shardings for a shape cell."""
    ba = (effective_batch_axes(mesh, global_batch) if global_batch
          else batch_axes(mesh))
    if kind in ("train", "prefill"):
        d = {"tokens": P(ba, None)}
        if kind == "train":
            d["labels"] = P(ba, None)
        if cfg.family in ("vlm", "encdec"):
            d["prefix_emb"] = P(ba, None, None)
        return d
    return {"token": P(ba), "pos": P()}


def _len_or_head(mesh, n_heads: int, length: int):
    ms = mesh_axis_size(mesh, "model")
    if n_heads % ms == 0 and n_heads >= ms:
        return "heads"
    if length % ms == 0:
        return "length"
    return "none"


def paged_cache_pspecs(cache, mesh: Mesh):
    """Shardings for the serve engine's paged KV pool (DESIGN.md SS16).

    The pool k/v arrays are head-major, (n_layers, n_pages, Hkv,
    page_size, head_dim): the KV-head dim shards over "model" when
    divisible, everything else — including the pages axis, which the
    replicated page table indexes — replicates. The int8 per-(layer,
    kv-head) scales stay REPLICATED on purpose: calibration happens outside the shard_map body so every shard
    quantizes with bitwise-identical scales, and the shard body slices its
    own head block on entry."""
    ms = mesh_axis_size(mesh, "model")

    def rule(path, leaf):
        shape = leaf.shape
        if len(shape) == 5 and shape[2] % ms == 0 and shape[2] >= ms:
            return P(None, None, "model", None, None)
        return P(*([None] * len(shape)))
    return jax.tree_util.tree_map_with_path(rule, cache)


def cache_pspecs(cfg: ArchConfig, cache_shapes, mesh: Mesh,
                 global_batch: int = 0):
    """KV-cache / SSM-state shardings: batch over DP; heads over "model"
    when divisible, else sequence-shard the cache length (GSPMD then
    lowers the decode softmax to a cross-shard collective)."""
    ba = (effective_batch_axes(mesh, global_batch) if global_batch
          else batch_axes(mesh))
    ms = mesh_axis_size(mesh, "model")

    def mdl(n):
        return "model" if (n % ms == 0 and n >= ms) else None

    def rule(path, leaf):
        ps = _path_str(path)
        shape = leaf.shape
        nd = len(shape)
        # --- SSM recurrent states (check conv BEFORE the ssm catch-all:
        # the pytree path is .../ssm_states/{conv,ssm}) ------------------
        if "conv" in ps:
            # (L,B,K,C) or zamba (sites,per,B,K,C)
            lead = nd - 3
            return P(*([None] * lead), ba, None, mdl(shape[-1]))
        if "ssm" in ps:
            # (L,B,nh,hd,N) or zamba (sites,per,B,nh,hd,N)
            lead = nd - 4
            return P(*([None] * lead), ba, mdl(shape[lead + 1]), None, None)
        # --- MLA latent caches ----------------------------------------
        if ps.endswith("/c") or "k_rope" in ps:
            if nd == 4:  # (L,B,Lmax,width): sequence-shard the cache
                return P(None, ba, mdl(shape[2]), None)
            return P(ba, mdl(shape[1]), None)      # head-layer (B,Lmax,w)
        # --- attention KV caches --------------------------------------
        if nd == 5:      # (L,B,Lmax,H,hd) / zamba (sites,B,Lmax,H,hd)
            if mdl(shape[3]):
                return P(None, ba, None, "model", None)
            return P(None, ba, mdl(shape[2]), None, None)
        if nd == 4:      # unstacked head-layer cache (B,Lmax,Hkv,hd)
            if mdl(shape[2]):
                return P(ba, None, "model", None)
            return P(ba, mdl(shape[1]), None, None)
        return P(*([None] * nd))
    return jax.tree_util.tree_map_with_path(rule, cache_shapes)
