"""Multi-pod dry-run (deliverable e) + roofline extraction (deliverable g).

For every (architecture x input-shape x mesh) cell:
    jit(step).lower(**ShapeDtypeStructs).compile()
then record memory_analysis(), cost_analysis(), and the trip-count-aware
HLO costs (FLOPs / bytes / collective bytes) into artifacts/dryrun/*.json.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --all
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
    ... [--multi-pod] [--variant tp|fsdp] [--force]
"""
import argparse
import gc
import json
import os
import pathlib
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ASSIGNED_ARCHS, get_config
from repro.core import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.models import (RuntimeOptions, SHAPES, cell_runnable, decode_step,
                          init_cache, init_params, input_specs, prefill,
                          train_loss)
from repro.optim import AdamWConfig, adamw_init, adamw_update
from repro.sharding import (cache_pspecs, data_pspecs, opt_state_pspec,
                            param_pspecs)


def cm_constrain(x, mesh, ba):
    """Keep microbatch slices batch-sharded after the reshape."""
    spec = P(ba, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

ART = pathlib.Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def _sharded_specs(tree, pspecs, mesh):
    return jax.tree.map(
        lambda s, p: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                          sharding=NamedSharding(mesh, p)),
        tree, pspecs)


def _ns(mesh, pspecs):
    """PartitionSpec tree -> NamedSharding tree (jit out_shardings)."""
    return jax.tree.map(lambda p: NamedSharding(mesh, p), pspecs,
                        is_leaf=lambda x: isinstance(x, P))


def build_cell(arch: str, shape: str, mesh, *, variant: str = "fsdp",
               opts: RuntimeOptions = None):
    """Returns (fn, example_args_with_shardings, out_shardings)."""
    import dataclasses

    from repro.sharding.rules import effective_batch_axes
    cfg = get_config(arch)
    sp = SHAPES[shape]
    opts = opts or RuntimeOptions()
    ba_eff = effective_batch_axes(mesh, sp.global_batch)
    ms = mesh.shape.get("model", 1)
    params_shapes = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), opts))
    p_specs = param_pspecs(cfg, params_shapes, mesh, mode=variant)
    moe_kw = {}
    # shard-local EP dispatch (moe_impl="shard_map"): tokens stay on their
    # data shard, experts on model shards, combine = one small psum.
    # (GSPMD-constraint and gather-combine variants both measured WORSE —
    # see EXPERIMENTS.md SSPerf iterations 1-2 for arctic prefill_32k.)
    if (cfg.moe is not None and cfg.moe.n_experts % ms == 0
            and not os.environ.get("REPRO_NO_MOE_SHARD")):
        moe_kw = {"moe_impl": "shard_map", "moe_shard_map_mesh": mesh}
    z3_kw = {}
    if sp.kind in ("train", "prefill") and variant == "fsdp" and \
            not os.environ.get("REPRO_NO_ZERO3_GATHER"):
        def _nodata(spec):
            def clean(ax):
                if ax is None:
                    return None
                axes = ax if isinstance(ax, tuple) else (ax,)
                kept = tuple(a for a in axes if a not in ("data", "pod"))
                return (kept[0] if len(kept) == 1 else (kept or None))
            return P(*(clean(a) for a in spec))
        entries = []
        flat = jax.tree_util.tree_flatten_with_path(p_specs)[0]
        for path, spec in flat:
            ps = "/".join(str(q.key) if hasattr(q, "key") else str(q.idx)
                          for q in path)
            if "stack" not in ps or len(spec) < 2:
                continue
            body = P(*tuple(spec)[1:])          # drop the scan dim
            nd = _nodata(body)
            if nd != body:                       # only data-sharded weights
                entries.append((ps.split("stack/", 1)[-1],
                                NamedSharding(mesh, nd)))
        if entries:
            z3_kw = {"zero3_gather": tuple(entries)}
    seq_kw = {}
    if (sp.kind == "decode" and cfg.mla is None
            and cfg.family in ("dense", "moe", "vlm")
            and cfg.n_kv_heads % ms != 0
            and not os.environ.get("REPRO_NO_SEQ_SHARD")):
        seq_kw = {"seq_shard_attn": True, "seq_shard_mesh": mesh}
    # sequence parallelism (SSPerf iteration 5): shard the residual stream's
    # sequence dim over "model" for big-token kinds — row-parallel
    # all-reduces become reduce-scatter+all-gather at half the traffic,
    # and norms/elementwise run 1/model_size of the tokens
    # OPT-IN: refuted as a default — with GQA kv-heads < model-axis size
    # the attention replicates over "model" and the memory term explodes
    # (EXPERIMENTS.md SSPerf arctic iteration 5)
    seq_dim_shard = (sp.kind in ("train", "prefill")
                     and sp.seq_len % ms == 0
                     and bool(os.environ.get("REPRO_SEQPAR")))
    res_spec = (P(ba_eff, "model", None) if seq_dim_shard
                else P(ba_eff, None, None))
    opts = dataclasses.replace(
        opts, residual_sharding=NamedSharding(mesh, res_spec),
        **moe_kw, **seq_kw, **z3_kw)
    params_in = _sharded_specs(params_shapes, p_specs, mesh)
    d_specs = data_pspecs(cfg, mesh, sp.kind, sp.global_batch)
    inputs = input_specs(cfg, shape, opts)
    data_in = {k: jax.ShapeDtypeStruct(
        v.shape, v.dtype, sharding=NamedSharding(mesh, d_specs[k]))
        for k, v in inputs.items() if k in d_specs}

    if sp.kind == "train":
        ocfg = AdamWConfig()
        opt_shapes = jax.eval_shape(partial(adamw_init), params_shapes)
        opt_specs = {
            "step": P(),
            "m": jax.tree.map(lambda ps, s: opt_state_pspec(ps, s.shape, mesh),
                              p_specs, opt_shapes["m"]),
            "v": jax.tree.map(lambda ps, s: opt_state_pspec(ps, s.shape, mesh),
                              p_specs, opt_shapes["v"]),
            "master": jax.tree.map(
                lambda ps, s: opt_state_pspec(ps, s.shape, mesh),
                p_specs, opt_shapes["master"]),
        }
        opt_in = _sharded_specs(opt_shapes, opt_specs, mesh)

        # gradient accumulation: bounds activation memory (per-micro local
        # batch ~4 sequences) — and is how 1M-token global steps run at
        # 1000+-node scale anyway.
        dp = 1
        for a in ("pod", "data"):
            if a in mesh.shape:
                dp *= mesh.shape[a]
        local_b = max(sp.global_batch // dp, 1)
        n_micro = int(os.environ.get("REPRO_MICROBATCH", "0")) or max(
            1, local_b // 4)

        def train_step(params, opt_state, batch):
            def loss_fn(p, mb):
                return train_loss(cfg, p, mb, opts)

            def micro(carry, mb):
                g_acc, loss_acc = carry
                mb = jax.tree.map(
                    lambda x: cm_constrain(x, mesh, ba_eff), mb)
                (loss, _), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, mb)
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), g_acc, g)
                return (g_acc, loss_acc + loss), None

            mbs = jax.tree.map(
                lambda x: x.reshape(n_micro, x.shape[0] // n_micro,
                                    *x.shape[1:]), batch)
            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              params)
            (g, loss), _ = jax.lax.scan(micro, (g0, jnp.zeros((), jnp.float32)),
                                        mbs)
            g = jax.tree.map(lambda x: x / n_micro, g)
            new_p, new_s, om = adamw_update(ocfg, params, g, opt_state)
            return loss / n_micro, new_p, new_s

        fn = jax.jit(train_step,
                     out_shardings=(NamedSharding(mesh, P()),
                                    _ns(mesh, p_specs), _ns(mesh, opt_specs)))
        return fn, (params_in, opt_in, data_in)

    # decode cache length: +slack, rounded to a multiple of 256 so the
    # length dim divides any mesh axis (seq-sharded caches)
    max_len = (sp.seq_len if sp.kind != "decode"
               else ((sp.seq_len + 8 + 255) // 256) * 256)
    cache_shapes = jax.eval_shape(
        lambda: init_cache(cfg, sp.global_batch, max_len, opts))
    c_specs = cache_pspecs(cfg, cache_shapes, mesh, sp.global_batch)
    cache_in = _sharded_specs(cache_shapes, c_specs, mesh)

    if sp.kind == "prefill":
        def prefill_step(params, tokens_batch, cache):
            return prefill(cfg, params, tokens_batch["tokens"], cache, opts,
                           prefix_emb=tokens_batch.get("prefix_emb"))
        ba = d_specs["tokens"][0]
        fn = jax.jit(prefill_step,
                     out_shardings=(NamedSharding(mesh, P(ba, None)),
                                    _ns(mesh, c_specs)))
        return fn, (params_in, data_in, cache_in)

    def serve_step(params, token, pos, cache):
        return decode_step(cfg, params, token, pos, cache, opts)
    ba = d_specs["token"][0]
    fn = jax.jit(serve_step, donate_argnums=(3,),
                 out_shardings=(NamedSharding(mesh, P(ba, None)),
                                _ns(mesh, c_specs)))
    return fn, (params_in, data_in["token"],
                jax.ShapeDtypeStruct((), jnp.int32), cache_in)


def roofline_terms(costs: hlo_analysis.HloCosts, cfg, shape: str,
                   n_dev: int = 256) -> dict:
    """Three-term roofline (terms per device; ratio vs GLOBAL HLO flops)."""
    from repro.core import tpu_roofline as tr
    sp = SHAPES[shape]
    t = tr.terms_from_costs(
        costs, n_dev=n_dev,
        model_flops=tr.model_flops_for(cfg, sp.kind, sp.seq_len,
                                       sp.global_batch))
    out = {
        "compute_s": t.compute_s, "memory_s": t.memory_s,
        "collective_s": t.collective_s,
        "bottleneck": t.bottleneck,
        "model_flops": t.model_flops,
        "hlo_flops_global": t.hlo_flops_global,
        "model_flops_ratio": t.model_flops_ratio,
        "step_time_lower_bound_s": t.step_lower_bound_s,
        "roofline_fraction": t.roofline_fraction,
    }
    if sp.kind == "decode":
        out["memory_floor_s"] = tr.decode_floor_seconds(
            cfg, sp.seq_len, sp.global_batch, n_dev)
    return out


def run_cell(arch: str, shape: str, *, multi_pod: bool, variant: str,
             force: bool = False, opts: RuntimeOptions = None,
             tag: str = "") -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    stem = f"{arch}.{shape}.{mesh_name}.{variant}{('.' + tag) if tag else ''}"
    out_path = ART / f"{stem}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())
    cfg = get_config(arch)
    skip = cell_runnable(cfg, shape)
    rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
           "variant": variant, "tag": tag}
    if skip:
        rec["skipped"] = skip
        ART.mkdir(parents=True, exist_ok=True)
        out_path.write_text(json.dumps(rec, indent=1))
        return rec
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        n_dev = 512 if multi_pod else 256
        with mesh:
            fn, args = build_cell(arch, shape, mesh, variant=variant,
                                  opts=opts)
            lowered = fn.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            ca = compiled.cost_analysis() or {}
            text = compiled.as_text()
            costs = hlo_analysis.analyze(text)
        rec.update({
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "devices": n_dev,
            "memory": {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
                "output_bytes": getattr(mem, "output_size_in_bytes", 0),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
                "peak_bytes": (getattr(mem, "argument_size_in_bytes", 0)
                               + getattr(mem, "temp_size_in_bytes", 0)),
            },
            "xla_cost_analysis": {k: ca.get(k) for k in
                                  ("flops", "bytes accessed")},
            "hlo_costs": {
                "flops": costs.flops,
                "bytes": costs.bytes,
                "collective_bytes": costs.collective_bytes,
                "collective_breakdown": costs.collective_counts,
            },
            "roofline": roofline_terms(costs, cfg, shape, n_dev),
        })
    except Exception as e:
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    ART.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(rec, indent=1))
    # free compiler memory between heavy cells
    gc.collect()
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="")
    ap.add_argument("--shape", default="")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--variant", default="fsdp", choices=["fsdp", "tp"])
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--attn-impl", default="xla")
    ap.add_argument("--remat", default="block")
    ap.add_argument("--block-q", type=int, default=512)
    ap.add_argument("--block-kv", type=int, default=1024)
    ap.add_argument("--flash-acc", default="float32")
    ap.add_argument("--cache-dtype", default="")
    args = ap.parse_args()
    if not os.environ.get("XLA_FLAGS"):
        # the production meshes need 512 host placeholder devices; the
        # backend reads the flag when it starts, at the first mesh below
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

    opts = RuntimeOptions(attn_impl=args.attn_impl, remat=args.remat,
                          block_q=args.block_q, block_kv=args.block_kv,
                          flash_acc=args.flash_acc,
                          cache_dtype=args.cache_dtype)
    archs = [args.arch] if args.arch else ASSIGNED_ARCHS
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = ([False, True] if args.both_meshes
              else [args.multi_pod])

    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                t0 = time.time()
                rec = run_cell(arch, shape, multi_pod=mp,
                               variant=args.variant, force=args.force,
                               opts=opts, tag=args.tag)
                status = ("SKIP " + rec["skipped"][:40] if "skipped" in rec
                          else ("ERR " + rec["error"][:80] if "error" in rec
                                else f"ok {rec['compile_s']:.0f}s "
                                f"peak={rec['memory']['peak_bytes']/2**30:.2f}GiB"
                                f" bott={rec['roofline']['bottleneck']}"))
                print(f"[{arch} x {shape} x "
                      f"{'2x16x16' if mp else '16x16'} x {args.variant}] "
                      f"{status} ({time.time()-t0:.0f}s)", flush=True)


if __name__ == "__main__":
    main()
