"""Sharding rules, HLO analyzer, and a small-mesh dry-run integration test."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.core import hlo_analysis as ha
from repro.models import RuntimeOptions, init_cache, init_params
from repro.sharding import cache_pspecs, opt_state_pspec, param_pspecs

OPTS = RuntimeOptions()


class FakeMesh:
    """Duck-typed mesh: only .shape is consulted by the rules."""
    def __init__(self, **axes):
        self.shape = axes


MESH = FakeMesh(data=16, model=16)
MESH3 = FakeMesh(pod=2, data=16, model=16)


def _pspecs(arch, mesh=MESH, mode="fsdp"):
    cfg = get_config(arch)
    shapes = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), OPTS))
    return cfg, param_pspecs(cfg, shapes, mesh, mode=mode), shapes


def test_dense_weight_tp_and_fsdp():
    cfg, specs, shapes = _pspecs("yi-6b")
    wq = specs["stack"]["attn"]["wq"]["w"]
    assert wq == P(None, None, "model") or wq == P(None, ("data",), "model")
    # d_model=4096 divides dp=16 -> fsdp shards the replicated dim
    assert "data" in str(wq)
    wo = specs["stack"]["attn"]["wo"]["w"]
    assert str(wo).count("model") == 1


def test_moe_expert_parallelism():
    cfg, specs, shapes = _pspecs("deepseek-v2-236b")
    w_up = specs["stack"]["moe"]["w_up"]
    # (layers, E, d, ff): experts (160) sharded over model
    assert w_up[1] == "model"


def test_vocab_sharding_and_tied_embed():
    cfg, specs, _ = _pspecs("gemma3-1b")
    emb = specs["embed"]["emb"]
    assert emb[0] == "model"          # 262144 % 16 == 0


def test_tp_mode_has_no_data_sharding():
    cfg, specs, _ = _pspecs("yi-6b", mode="tp")
    leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert all("data" not in str(s) for s in leaves)


def test_opt_state_zero1_shards_replicated_dim():
    out = opt_state_pspec(P(None, "model"), (4096, 11008), MESH)
    assert out == P(("data",), "model")
    # already-fsdp param spec is left alone
    out2 = opt_state_pspec(P(("data",), "model"), (4096, 11008), MESH)
    assert out2 == P(("data",), "model")


def test_cache_heads_vs_length_sharding():
    cfg = get_config("zamba2-2.7b")      # 32 kv heads: shardable
    shapes = jax.eval_shape(lambda: init_cache(cfg, 128, 1024, OPTS))
    specs = cache_pspecs(cfg, shapes, MESH, 128)
    assert specs["attn"]["k"][3] == "model"
    cfg2 = get_config("qwen2.5-3b")      # kv=2 -> sequence sharding
    shapes2 = jax.eval_shape(lambda: init_cache(cfg2, 128, 32768, OPTS))
    specs2 = cache_pspecs(cfg2, shapes2, MESH, 128)
    assert specs2["stack"]["k"][2] == "model"
    assert specs2["stack"]["k"][3] is None


def test_batch1_never_shards_batch():
    cfg = get_config("zamba2-2.7b")
    shapes = jax.eval_shape(lambda: init_cache(cfg, 1, 4096, OPTS))
    specs = cache_pspecs(cfg, shapes, MESH, 1)
    assert specs["attn"]["k"][1] is None


# --------------------------- HLO analyzer ------------------------------ #

def test_hlo_analyzer_counts_scan_trips():
    def f(x, ws):
        def body(c, w):
            return jnp.tanh(c @ w), None
        return jax.lax.scan(body, x, ws)[0]
    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    ws = jax.ShapeDtypeStruct((10, 256, 256), jnp.float32)
    res = ha.analyze(jax.jit(f).lower(x, ws).compile().as_text())
    want = 2 * 128 * 256 * 256 * 10
    assert want <= res.flops <= want * 1.1


def test_hlo_analyzer_tuple_comment_types():
    """Result types with /*index=N*/ comments must still parse (the bug
    that silently dropped every while body in train graphs)."""
    def f(x):
        def body(c, _):
            a, b = c
            return (a @ b, b + 1.0), None
        (a, b), _ = jax.lax.scan(body, (x, x), None, length=5)
        return a + b
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    res = ha.analyze(jax.jit(f).lower(x).compile().as_text())
    want = 2 * 64 * 64 * 64 * 5
    assert res.flops >= want * 0.9


def test_hlo_analyzer_collectives():
    mesh = jax.make_mesh((1,), ("x",))
    from jax.sharding import NamedSharding

    def f(x):
        return jax.lax.with_sharding_constraint(
            x.sum(0, keepdims=True), NamedSharding(mesh, P()))
    # single-device: no collectives expected; just exercise the path
    x = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    res = ha.analyze(jax.jit(f).lower(x).compile().as_text())
    assert res.collective_bytes == 0.0


# ----------------------- small-mesh dry-run ----------------------------- #

@pytest.mark.slow
def test_dryrun_cell_compiles_on_8_devices(tmp_path):
    """End-to-end: lower+compile a full-config decode cell on a small host
    mesh in a subprocess (proves build_cell works outside the 512-dev run)."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
from repro.launch import dryrun
from repro.launch.mesh import make_host_mesh
from repro.models import RuntimeOptions
mesh = make_host_mesh()
assert dict(mesh.shape) == {"data": 2, "model": 4}
with mesh:
    fn, args = dryrun.build_cell("qwen2.5-3b", "decode_32k", mesh,
                                 variant="tp", opts=RuntimeOptions())
    compiled = fn.lower(*args).compile()
    print("PEAK", compiled.memory_analysis().temp_size_in_bytes)
"""
    out = subprocess.run([sys.executable, "-c", code], env={
        **os.environ, "PYTHONPATH": "src"}, capture_output=True, text=True,
        timeout=560, cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr[-2000:]
    assert "PEAK" in out.stdout
