"""Model families: every configuration resolves to a module with the
whole interface, an unknown family is refused with the known ones, the
dense family's counts and weights are those the benchmark had before
families existed, and a later family is new files only."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from conftest import ROOT, TINY_MIX, tiny_config

from benchmarks.chip import counts, families, weights
from benchmarks.chip.families import dense

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def _config(name: str) -> dict:
    file = {c["name"]: c["file"] for c in BENCH["configs"]}[name]
    return json.loads((ROOT / file).read_text())


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_every_config_resolves_to_a_family_with_the_interface(name):
    cfg = _config(name)
    fam = families.of(cfg)
    assert fam.__name__ == f"benchmarks.chip.families.{cfg['family']}"
    for f in families.INTERFACE:
        assert callable(getattr(fam, f)), f
    m = fam.dims(cfg)
    assert fam.kv_bytes_per_token(m) > 0
    arch = fam.arch_config(cfg)
    assert arch.vocab == cfg["vocab_size"]
    assert arch.max_context == cfg["max_position_embeddings"]


@pytest.mark.parametrize("family", ["moe", None])
def test_unknown_or_missing_family_is_refused_with_the_known_ones(family):
    cfg = tiny_config()
    if family is None:
        del cfg["family"]
    else:
        cfg["family"] = family
    with pytest.raises(ValueError, match=r"known families: \[.*'dense'.*\]"):
        families.of(cfg)


# counts at each cell's shapes, as the benchmark computed them before it
# had families: a 32-token chunk at position 0, the last chunk before
# max_len - 32 with its logits, a decode token over max_len - 1
# positions, and a decode step over max_batch sequences 7 positions apart
PINNED = {
    "qwen3b-longctx": dict(
        n_params=3_085_938_688, kv=36_864, pf_first=177_725_767_680,
        pf_last=298_540_072_960, dec=9_945_972_736,
        step_bytes=9_939_525_632),
    "yi6b-docqa": dict(
        n_params=6_061_035_520, kv=65_536, pf_first=354_611_625_984,
        pf_last=427_076_616_192, dec=13_878_427_648,
        step_bytes=16_106_135_552),
    "qwen3b-chat": dict(
        n_params=3_085_938_688, kv=36_864, pf_first=177_725_767_680,
        pf_last=189_823_713_280, dec=6_548_586_496,
        step_bytes=7_553_835_008),
}


@pytest.mark.parametrize("workload", sorted(PINNED))
def test_dense_counts_are_unchanged_at_each_cell(workload):
    wl = {w["name"]: w for w in BENCH["workloads"]}[workload]
    cfg = _config(wl["config"])
    cell = json.loads((ROOT / "benchmarks" / "chip" / "cells"
                       / f"{workload}.json").read_text())
    fam = families.of(cfg)
    assert fam is dense
    m = fam.dims(cfg)
    L, B = cell["max_len"], cell["max_batch"]
    got = dict(
        n_params=counts.n_params(m), kv=fam.kv_bytes_per_token(m),
        pf_first=fam.prefill_flops(m, 0, 32, last=False),
        pf_last=fam.prefill_flops(m, L - 64, L - 32, last=True),
        dec=fam.decode_flops(m, L - 1),
        step_bytes=fam.decode_step_bytes(m, [L - 1 - 7 * i
                                             for i in range(B)]))
    assert got == PINNED[workload]


def _digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("tied,want", [
    (False, "31e48f9f59844eeb75c5aa2bedc05517005346f930e90ead7465809d961a66d0"),
    (True, "17719540ecb70b03c5952a689ee7a606d46b1bc81f0ffba9c18e73e9490313df"),
])
def test_dense_served_params_are_unchanged(tied, want):
    # every leaf drawn from its own fold-in id (weights._LEAF_IDS), at a
    # tiny size on a seed past 32 bits; digests from before families
    m = counts.Dims(layers=2, d=64, heads=4, kv_heads=2, head_dim=16,
                    d_ff=96, vocab=256, qkv_bias=not tied, tied=tied)
    assert dense.served_params is weights.served_params
    assert _digest(dense.served_params(m, 2**31 + 5)) == want


TOY_FAMILY = '''
"""A family brought as a new file: the dense model under another name,
counting the prefill chunks it is asked about."""
from benchmarks.chip.families.dense import (arch_config, decode_flops,
                                            decode_step_bytes, dims,
                                            kv_bytes_per_token, logits,
                                            padded_len, served_params)
from benchmarks.chip.families import dense

asked = []


def prefill_flops(m, start, end, last):
    asked.append((start, end))
    return dense.prefill_flops(m, start, end, last)
'''

TOY_RUN = '''
import json, sys, time
from benchmarks.chip import harness
from benchmarks.chip.families import toy
cell = harness.load_cell("toy-cell")
out = harness.measure(cell, 2**31 + 3, 0.01, True, time.perf_counter(),
                      {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
print(json.dumps({"family": cell.family.__name__, "asked": len(toy.asked),
                  "correct": out["correct"], "attempted": out["attempted"],
                  "metrics": sorted(out["metrics"])}))
'''


def test_a_new_family_is_new_files_only(tmp_path):
    """A copy of the benchmark takes a new family, configuration, mix and
    cell as new files and runs the cell, traced, on the CPU: nothing the
    benchmark already has is edited."""
    bench_dir = tmp_path / "benchmarks" / "chip"
    shutil.copytree(ROOT / "benchmarks" / "chip", bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p.relative_to(bench_dir): p.read_bytes()
              for p in bench_dir.rglob("*") if p.is_file()}
    (bench_dir / "families" / "toy.py").write_text(TOY_FAMILY)
    cfg = dict(tiny_config(), name="toy", family="toy")
    (bench_dir / "configs" / "toy.json").write_text(json.dumps(cfg))
    (bench_dir / "traffic" / "toymix.json").write_text(json.dumps(TINY_MIX))
    (bench_dir / "cells" / "toy-cell.json").write_text(json.dumps(
        {"max_batch": 4, "max_len": 256, "n_pages": 80, "prefill_budget": 64,
         "check": {"sample_tokens": 400, "max_logit_gap": 0.5}}))
    bench = dict(BENCH)
    bench["configs"] = [{"name": "toy", "source": "none", "reduced": [],
                         "file": "benchmarks/chip/configs/toy.json"}]
    bench["workloads"] = [{"name": "toy-cell", "config": "toy",
                           "traffic": "toymix", "chips": 1, "why": "test"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    after = {p.relative_to(bench_dir): p.read_bytes()
             for p in bench_dir.rglob("*")
             if p.is_file() and "__pycache__" not in p.parts}
    assert all(after[k] == v for k, v in before.items())
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(tmp_path), str(ROOT / "src")]))
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(TOY_RUN)],
                       cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["family"] == "benchmarks.chip.families.toy"
    assert got["correct"] is True and got["attempted"] == 4
    # the traced wave's prefill chunks were counted by the new family
    assert got["asked"] > 0
    assert "decode_occupancy" in got["metrics"]
