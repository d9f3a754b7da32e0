"""Shared latency-statistics helpers and the sectioned BENCH_serve.json
writer (DESIGN.md SS15 satellites): one percentile implementation for
engine + benchmarks, and a merge that can never clobber another
benchmark's section."""
import json
import os
import sys

import numpy as np
import pytest

from repro.serving import metrics
from repro.serving.engine import ServeStats

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
from benchmarks.common import (BENCH_SECTIONS, goodput_summary,  # noqa: E402
                               merge_bench_json)


# ------------------------- percentile helpers -------------------------- #

def test_percentile_matches_numpy():
    rng = np.random.default_rng(0)
    xs = rng.uniform(0, 1, size=37).tolist()
    for q in (0, 25, 50, 95, 99.9, 100):
        assert metrics.percentile(xs, q) == pytest.approx(
            float(np.percentile(np.asarray(xs), q)))


def test_percentile_empty_is_zero():
    assert metrics.percentile([], 50) == 0.0
    assert metrics.percentile((), 95) == 0.0


def test_percentile_rejects_bad_q():
    with pytest.raises(ValueError):
        metrics.percentile([1.0], -1)
    with pytest.raises(ValueError):
        metrics.percentile([1.0], 100.5)


def test_pct_ms_converts_and_rounds():
    # 12.3456 ms with the default 3-digit rounding
    assert metrics.pct_ms([0.0123456], 50) == 12.346
    assert metrics.pct_ms([0.0123456], 50, ndigits=1) == 12.3
    assert metrics.pct_ms([], 95) == 0.0


def test_latency_summary_ms_fields():
    out = metrics.latency_summary_ms([0.010, 0.020, 0.030])
    assert out["n"] == 3
    assert out["p50_ms"] == pytest.approx(20.0)
    assert out["mean_ms"] == pytest.approx(20.0)
    assert out["max_ms"] == pytest.approx(30.0)
    empty = metrics.latency_summary_ms([])
    assert empty == {"p50_ms": 0.0, "p95_ms": 0.0, "mean_ms": 0.0,
                     "max_ms": 0.0, "n": 0}


def test_serve_stats_uses_shared_percentile():
    """ServeStats percentile properties must be bit-identical to the
    shared helper (the pre-SS15 duplication is gone)."""
    s = ServeStats()
    assert s.ttft_p50 == 0.0 and s.itl_p95 == 0.0     # empty convention
    s.ttft = [0.01, 0.02, 0.05, 0.3]
    s.itl = [0.001, 0.002, 0.009]
    assert s.ttft_p95 == metrics.percentile(s.ttft, 95)
    assert s.itl_p50 == metrics.percentile(s.itl, 50)


# --------------------- BENCH_serve.json merge writer -------------------- #

def _payload(section):
    return {k: {} for k in BENCH_SECTIONS[section]}


def test_merge_preserves_other_sections(tmp_path):
    path = str(tmp_path / "BENCH_serve.json")
    merge_bench_json(path, "serve_bench", _payload("serve_bench"))
    merge_bench_json(path, "hbs_sweep", _payload("hbs_sweep"))
    merge_bench_json(path, "spec_sweep", _payload("spec_sweep"))
    with open(path) as f:
        doc = json.load(f)
    assert set(doc) == {"serve_bench", "hbs_sweep", "spec_sweep"}
    # re-running one benchmark replaces only its own section
    pl = _payload("serve_bench")
    pl["derived"] = {"marker": 1}
    merge_bench_json(path, "serve_bench", pl)
    with open(path) as f:
        doc = json.load(f)
    assert doc["serve_bench"]["derived"] == {"marker": 1}
    assert set(doc) == {"serve_bench", "hbs_sweep", "spec_sweep"}


def test_merge_rejects_unknown_section(tmp_path):
    with pytest.raises(ValueError, match="unknown"):
        merge_bench_json(str(tmp_path / "b.json"), "mystery", {})


def test_merge_validates_required_keys(tmp_path):
    path = str(tmp_path / "b.json")
    bad = _payload("spec_sweep")
    del bad["ngram"]
    with pytest.raises(ValueError, match="missing required keys"):
        merge_bench_json(path, "spec_sweep", bad)
    assert not os.path.exists(path)          # nothing written on failure


def test_merge_rejects_legacy_top_level_layout(tmp_path):
    path = str(tmp_path / "b.json")
    with open(path, "w") as f:
        json.dump({"workload": {}, "derived": {}}, f)   # pre-SS15 layout
    with pytest.raises(ValueError, match="non-section top-level"):
        merge_bench_json(path, "serve_bench", _payload("serve_bench"))


def test_merge_rejects_corrupt_file(tmp_path):
    path = str(tmp_path / "b.json")
    with open(path, "w") as f:
        f.write("{ not json")
    with pytest.raises(ValueError, match="not valid JSON"):
        merge_bench_json(path, "serve_bench", _payload("serve_bench"))


def test_goodput_summary_counts_blame():
    rep = {"goodput_frac": 0.5, "n_met_slo": 2, "n_requests": 4,
           "violators": [{"blame": "stall"}, {"blame": "stall"},
                         {"blame": "queue"}]}
    out = goodput_summary(rep)
    assert out["violator_blame"] == {"stall": 2, "queue": 1}
    assert out["goodput_frac"] == 0.5


def test_bench_harness_exits_nonzero_on_module_error(monkeypatch, capsys):
    """A benchmark module that raises is reported as an ERROR row and the
    harness exits non-zero after the remaining modules have run."""
    import types

    from benchmarks import run as harness

    def boom(emit):
        raise RuntimeError("boom")
    fake = types.ModuleType("benchmarks.boom")
    fake.run = boom
    ok = types.ModuleType("benchmarks.fine")
    ok.run = lambda emit: "fine"
    monkeypatch.setitem(sys.modules, "benchmarks.boom", fake)
    monkeypatch.setitem(sys.modules, "benchmarks.fine", ok)
    monkeypatch.setattr(harness, "MODULES", ("boom", "fine"))
    monkeypatch.setattr(sys, "argv", ["run"])
    assert harness.main() == 1
    out = capsys.readouterr().out
    assert "boom.total" in out and "ERROR:RuntimeError:boom" in out
    assert "fine.total" in out
