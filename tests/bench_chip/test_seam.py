"""The wall-clock seam: one stamp per emitted token, and the engine's
tokens and trace audit unchanged by it. The run command refuses a
machine without a TPU."""
import os
import subprocess
import sys

from conftest import ROOT, tiny_cell

from benchmarks.chip import counts, harness, spans, traffic, weights
from benchmarks.chip.families import dense


def _serve(eng, w, sink=None):
    outs = eng.serve_continuous(w.prompts, w.max_new_tokens)
    eng.pool = None
    return outs, eng.trace_report


def test_seam_stamps_each_token_once_and_changes_nothing():
    cell = tiny_cell(1.0)
    m = counts.Dims.of(cell.config)
    w = traffic.wave(cell.mix, m.vocab, 5, 0)
    plain = harness.build_engine(cell, weights.served_params(m, 5))
    want, want_report = _serve(plain, w)
    with spans.stamped() as seam:
        eng = harness.build_engine(cell, weights.served_params(m, 5))
        sink = spans.Sink()
        with seam.collecting(sink):
            got, report = _serve(eng, w)
    assert got == want
    assert report["ok"] and want_report["ok"]
    for k in ("n_requests", "n_tokens", "failures"):
        assert report[k] == want_report[k]
    assert sorted(sink.tokens) == list(range(len(w.prompts)))
    for rid, out in enumerate(got):
        stamps = sink.tokens[rid]
        assert len(stamps) == len(out) == w.max_new_tokens
        assert stamps == sorted(stamps)
    # every program the engine ran was noted, prefill chunks with their
    # token ranges and decode blocks with their step counts
    progs = [e for e in sink.events if e[0] == "program"]
    assert {e[2] for e in progs} == {spans.PREFILL, spans.DECODE}
    assert all("tokens" in e[3] for e in progs if e[2] == spans.PREFILL)
    # the seam is gone after the run
    import repro.serving.engine as engine_mod
    from repro.serving.trace import TraceRecorder
    assert engine_mod.TraceRecorder is TraceRecorder


def test_run_command_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "qwen3b-chat", "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "needs a TPU" in p.stderr


def test_work_of_the_traced_programs_by_hand():
    m = counts.Dims(layers=2, d=8, heads=2, kv_heads=1, head_dim=4, d_ff=16,
                    vocab=32, qkv_bias=False, tied=True)
    P, D = spans.PREFILL, spans.DECODE
    ev = [("program", 0.0, P, {"rid": 0, "tokens": [0, 32]}),
          ("program", 0.1, P, {"rid": 0, "tokens": [32, 40]}),
          ("token", 0.2, 0),                      # request 0's first token
          ("program", 0.3, D, {"n_steps": 2}),
          ("token", 0.4, 0), ("token", 0.4, 0),   # two decode tokens
          ("program", 0.5, D, {"n_steps": 1}),
          ("token", 0.6, 0)]
    # traced: the second chunk and the first decode block only
    w = spans.work(ev, [(1, 2), (3, 4)], [40], dense, m)
    assert w.prefill_flops == counts.prefill_flops(m, 32, 40, last=True)
    # the block's steps attend over 41 and 42 positions (prompt 40, one
    # token emitted before it)
    assert w.decode_flops == (counts.decode_flops(m, 41)
                              + counts.decode_flops(m, 42))
    assert w.decode_bytes == (counts.decode_step_bytes(m, [41])
                              + counts.decode_step_bytes(m, [42]))
    assert w.per_program == {P: 1, D: 1}
