"""A cell's ``chips`` reaches the engine: on four virtual CPU devices a
four-chip tiny cell serves head-sharded, with the weights replicated in
place and the pool split over the KV heads, compiles nothing in its
window, and passes its check; the float8 control fails that check, and
so does a run whose exchange between chips is left out."""
import json
import os
import subprocess
import sys
import textwrap

from conftest import ROOT

FOUR = '''
import dataclasses, json, sys, time
sys.path[:0] = [{tests!r}]
import jax
from conftest import tiny_cell
from benchmarks.chip import harness, traffic
cell = tiny_cell(0.5)
cfg = dict(cell.config, num_attention_heads=8, num_key_value_heads=4,
           hidden_size=256)
cell = dataclasses.replace(cell, chips=4, config=cfg)
fam = cell.family
params = fam.served_params(fam.dims(cfg), 3,
                           sharding=harness.weight_sharding(cell))
eng = harness.build_engine(cell, params)
w = traffic.wave(cell.mix, cfg["vocab_size"], 3, 0)
eng.serve_continuous(w.prompts, w.max_new_tokens)
k = eng.pool["stack"]["k"]
got = {{"shards": eng.shards,
       "in_place": all(a is b for a, b in zip(jax.tree.leaves(eng.params),
                                              jax.tree.leaves(params))),
       "pool": list(k.shape),
       "pool_shards": sorted([list(s.data.shape), s.device.id]
                             for s in k.addressable_shards)}}
del eng, params
out = harness.measure(cell, 2**31 + 7, 0.01, False, time.perf_counter(),
                      {{"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}},
                      devices=jax.devices()[:4], compiles=harness.Compiles())
got.update(correct=out["correct"], attempted=out["attempted"])
print(json.dumps(got))
'''


def test_four_chip_cell_serves_head_sharded():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    script = textwrap.dedent(FOUR.format(tests=str(ROOT / "tests"
                                                   / "bench_chip")))
    p = subprocess.run([sys.executable, "-c", script], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["shards"] == 4 and got["in_place"] is True
    # (layers, pages, kv heads, page size, head dim): a KV head a device
    assert got["pool"] == [4, 80, 4, 16, 32]
    assert got["pool_shards"] == [[[4, 80, 1, 16, 32], d] for d in range(4)]
    assert got["correct"] is True and got["attempted"] == 4
    assert "compiles_in_window=0 " in p.stderr


BROKEN = '''
import dataclasses, json, sys, time
sys.path[:0] = [{tests!r}]
import jax
import jax.numpy as jnp
from conftest import tiny_cell
from benchmarks.chip import control, harness
cell = tiny_cell(0.5)
cfg = dict(cell.config, num_attention_heads=8, num_key_value_heads=4,
           hidden_size=256)
cell = dataclasses.replace(cell, chips=4, config=cfg)
got = control.readings(cell, [11, 12, 13], {{11, 12, 13}},
                       log=lambda line: None)
out = {{"sound": [r["max_logit_gap"] for r in got],
       "control": [r["control_gap"] for r in got]}}


def no_exchange(x, axis_name, *, axis=0, tiled=False, **kw):
    # each chip keeps its own heads where the others' should land
    return jnp.concatenate([x] * 4, axis=axis)


jax.lax.all_gather = no_exchange
run = harness.measure(cell, 11, 0.01, False, time.perf_counter(),
                      {{"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}})
out.update(correct=run["correct"], gap=run["check"]["max_logit_gap"]["value"])
print(json.dumps(out))
'''


def test_four_chip_check_fails_the_control_and_a_missing_exchange():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    script = textwrap.dedent(BROKEN.format(tests=str(ROOT / "tests"
                                                     / "bench_chip")))
    p = subprocess.run([sys.executable, "-c", script], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert max(got["sound"]) <= 0.5 < min(got["control"])
    assert got["correct"] is False and got["gap"] > 0.5
