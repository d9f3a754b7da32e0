"""The comparison that decides ``correct``, driven through the rest of a
run on the CPU (the harness's look for a chip is the only part left
out): a sound run passes, a run whose tokens are altered where they are
produced fails, and the control (the reference computed in float8, put
in the engine's place) fails.

The tiny cell's limit, 0.5, was set from readings of this tiny cell on
the CPU: sound runs read 0.059 to 0.175 over seeds 0-11 (lower reading
0.175), the float8 control 0.991 to 1.593 on seeds 0-5 (upper reading
0.991)."""
import time

import pytest
from conftest import tiny_cell

from benchmarks.chip import control, harness

LIMIT = 0.5
PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def _run(seed, trace=False, per_layer=()):
    cell = tiny_cell(LIMIT, per_layer=per_layer)
    return harness.measure(cell, seed, 0.01, trace, time.perf_counter(),
                           PEAK)


@pytest.mark.parametrize("seed", [3, 2**31 + 9])
def test_sound_run_is_correct(seed):
    out = _run(seed)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] == 4
    assert set(out["metrics"]) == {"tok_s", "ttft_p90_s", "tpot_p90_ms",
                                   "setup_s"}
    gap = out["check"]["max_logit_gap"]
    assert gap["limit"] == LIMIT and 0.0 <= gap["value"] <= LIMIT
    assert list(out)[-1] == "check"


def test_traced_run_reads_the_counters():
    out = _run(4, trace=True, per_layer=(
        "prefix_hit_share", "decode_occupancy", "host_syncs_per_tok",
        "idle_share", "step_mfu", "prefill_mfu", "decode_roofline"))
    assert out["correct"] is True
    m = out["metrics"]
    # the tiny mix shares 2 documents among 4 requests
    assert 0.0 < m["prefix_hit_share"]["value"] < 100.0
    assert 0.0 < m["decode_occupancy"]["value"] <= 100.0
    assert m["host_syncs_per_tok"]["value"] > 0.0
    # the CPU has no device trace: those readers find nothing to read
    for name in ("idle_share", "step_mfu", "prefill_mfu", "decode_roofline"):
        assert name not in m


def test_altered_token_is_caught(monkeypatch):
    import jax.numpy as jnp

    from repro.models import sampling

    def altered(logits):
        return ((jnp.argmax(logits, axis=-1) + 1)
                % logits.shape[-1]).astype(jnp.int32)
    monkeypatch.setattr(sampling, "sample_greedy", altered)
    out = _run(3)
    assert out["correct"] is False
    assert out["check"]["max_logit_gap"]["value"] > LIMIT


def test_control_fails_where_sound_runs_pass():
    got = control.readings(tiny_cell(LIMIT), [0, 1, 2], {0, 1, 2},
                           log=lambda line: None)
    for r in got:
        assert r["failed"] == 0 and r["tokens"] == 400
        assert r["max_logit_gap"] <= LIMIT < r["control_gap"]
