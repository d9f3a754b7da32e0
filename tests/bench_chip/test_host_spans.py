"""The engine's wall-clock stamps and host phases as the benchmark reads
them: the in-program token stamps agree with the seam's, a profiled
serve writes ``engine.*`` phases that ``trace_reduce.read`` returns one
after another, a serve that compiles nothing marks no run phase as
compiled, and the three host readers give hand-computed values on
synthetic intervals and on a small trace recorded on a TPU v5e (and
nothing on the older trace, which holds no engine phase)."""
import gc
import pathlib
import shutil
import time
from types import SimpleNamespace

import pytest
from conftest import tiny_cell

from benchmarks.chip import (counts, harness, host_spans, spans,
                             trace_reduce, traffic, weights)

DATA = pathlib.Path(__file__).resolve().parent / "data"
OLD_TRACE = DATA / "tiny_v5e.xplane.pb"
ENGINE_TRACE = DATA / "tiny_v5e_engine.xplane.pb"
READERS = ("host_ms_per_program", "kv_sched_ms_per_program",
           "idle_host_share")


def _wave(seed=5):
    cell = tiny_cell(1.0)
    m = counts.Dims.of(cell.config)
    return cell, m, traffic.wave(cell.mix, m.vocab, seed, 0)


def test_wall_stamps_agree_with_the_seam():
    """Both stamps are taken on the same call, one after the other. The
    collector is held off, and the wave is short, so that a collection
    pause or a descheduling of the process between the two reads of the
    clock does not part them."""
    cell, m, w = _wave()
    with spans.stamped() as seam:
        eng = harness.build_engine(cell, weights.served_params(m, 5))
        sink = spans.Sink()
        gc.disable()
        try:
            with seam.collecting(sink):
                outs = eng.serve_continuous(w.prompts, 8)
        finally:
            gc.enable()
    for rid, out in enumerate(outs):
        seam_t = sink.tokens[rid]
        wall = eng.trace.wall(rid)
        assert len(wall["token_t"]) == len(seam_t) == len(out)
        assert max(abs(a - b) for a, b in zip(wall["token_t"], seam_t)) < 1e-3
        assert 0.0 <= wall["queue_s"] <= wall["ttft_s"]


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """Two serves of one wave on one engine, each profiled inside a
    slice annotation: the first compiles the programs, the second runs
    the same shapes."""
    import jax
    cell, m, w = _wave()
    eng = harness.build_engine(cell, weights.served_params(m, 5))
    root = tmp_path_factory.mktemp("profiles")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    out = []
    for i in range(2):
        jax.profiler.start_trace(str(root / f"serve{i}"),
                                 profiler_options=opts)
        with jax.profiler.TraceAnnotation(trace_reduce.SLICE):
            eng.serve_continuous(w.prompts, w.max_new_tokens)
        jax.profiler.stop_trace()
        (path,) = trace_reduce.find(str(root / f"serve{i}"))
        out.append((path, dict(eng.trace.host_n), dict(eng.trace.compiles)))
    return out


def _engine_events(path):
    """(name, start_ns, end_ns, stats) of every host ``engine.*`` event."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(host_spans.PHASE):
                        out.append((ev.name, ev.start_ns, ev.end_ns,
                                    dict(ev.stats)))
    return sorted(out, key=lambda e: e[1])


def test_profiled_serve_writes_engine_phases_one_after_another(profiled):
    from repro.serving.trace import HOST_PHASES
    path, host_n, _ = profiled[1]
    t = trace_reduce.read(path)
    phases = sorted(((n, s, e) for n, s, e in t.host
                     if n.startswith(host_spans.PHASE)), key=lambda ev: ev[1])
    # every phase the recorder counted, in the profiler's host plane
    assert len(phases) == sum(host_n.get(p, 0) for p in HOST_PHASES)
    assert phases[0][0] == "engine.setup"
    assert phases[-1][0] == "engine.finish"
    for (_, _, e0), (_, s1, _) in zip(phases, phases[1:]):
        assert s1 >= e0                 # never nested, never overlapping
    # each layer span lies inside one phase
    layers = [(n, s, e) for n, s, e in t.host
              if n.startswith(host_spans.LAYERS)]
    assert layers
    for _, s, e in layers:
        assert any(ps <= s and e <= pe for _, ps, pe in phases)
    # run phases carry their program's arguments
    runs = [ev for ev in _engine_events(path)
            if ev[0] == "engine.prefill.run"]
    assert runs and all({"rid", "start", "n"} <= set(ev[3]) for ev in runs)


def test_same_shape_serve_marks_no_run_phase_compiled(profiled):
    (first, _, c1), (second, _, c2) = profiled

    def compiled(path):
        return [ev for ev in _engine_events(path) if "compiled" in ev[3]]
    assert {"prefill_paged_chunk", "decode_steps_paged"} <= set(c1)
    assert any(ev[0].endswith(".run") for ev in compiled(first))
    assert c2 == {} and compiled(second) == []


def _hand_trace():
    """One slice of 10 s: a prefill chunk in [1, 3] and a decode block in
    [5, 6] on the device; the host's phases tile [0, 9]."""
    host = [("engine.prefill.prep", 0.0, 1.0), ("kv.residency", 0.5, 0.8),
            ("engine.prefill.run#rid=0,start=0,n=32#", 1.0, 3.5),
            ("engine.prefill.commit", 3.5, 4.0),
            ("kv.register_prefix", 3.6, 3.9),
            ("engine.decode.prep", 4.0, 5.0), ("sched.reserve", 4.1, 4.3),
            ("kv.register_prefix", 4.2, 4.4),  # nested in sched.reserve
            ("engine.decode.run", 5.0, 6.2), ("engine.decode.emit", 6.2, 9.0),
            ("engine.admit", 11.0, 12.0)]      # outside the slice
    return trace_reduce.Trace(
        windows=[(0.0, 10.0)],
        ops={0: [("%a", 1.0, 3.0), ("%b", 5.0, 6.0)]},
        modules={0: [("prefill_paged_chunk", 1.0, 3.0),
                     ("decode_steps_paged", 5.0, 6.0)]},
        host=host)


def _run_of(traces):
    return SimpleNamespace(
        reduced=trace_reduce.reduce(traces, harness.PROGRAMS))


def test_host_readers_by_hand(monkeypatch):
    t = _hand_trace()
    monkeypatch.setattr(host_spans, "of",
                        lambda run: host_spans.reduce([t]))
    run = _run_of([t])
    got = {name: harness.reader(name)(run) for name in READERS}
    # host phases outside the runs: 1 + 0.5 + 1 + 2.8 s over 2 programs
    assert got["host_ms_per_program"] == pytest.approx(1e3 * 5.3 / 2)
    # kv/sched spans: [0.5, 0.8], [3.6, 3.9], [4.1, 4.4] (nested once)
    assert got["kv_sched_ms_per_program"] == pytest.approx(1e3 * 0.9 / 2)
    # device idle [0, 1], [3, 5], [6, 10] = 7 s; under non-run phases
    # 1 + 0.5 + 1 + 2.8 s; under runs 0.5 + 0.2; under none [9, 10]
    assert got["idle_host_share"] == pytest.approx(100.0 * 5.3 / 7.0)
    h = host_spans.reduce([t])
    assert h.idle_under == pytest.approx({
        "prefill.prep": 1.0, "prefill.run": 0.5, "prefill.commit": 0.5,
        "decode.prep": 1.0, "decode.run": 0.2, "decode.emit": 2.8})
    assert h.layer_s == pytest.approx({"kv.residency": 0.3,
                                       "kv.register_prefix": 0.5,
                                       "sched.reserve": 0.2})


def _readings(monkeypatch, tmp_path, trace_file):
    shutil.copy(trace_file, tmp_path / trace_file.name)
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    run = _run_of([trace_reduce.read(str(tmp_path / trace_file.name))])
    return run, {name: harness.reader(name)(run) for name in READERS}


def test_readers_read_nothing_in_a_trace_without_engine_phases(
        monkeypatch, tmp_path):
    run, got = _readings(monkeypatch, tmp_path, OLD_TRACE)
    # six runs, two of which start before the slice on the device clock
    assert host_spans.program_runs(run) == 4
    assert got == {name: None for name in READERS}


def test_host_readers_on_a_recorded_v5e_trace(monkeypatch, tmp_path):
    """One request of 20 tokens, 3 out, served by a one-layer tiny model
    on a TPU v5e inside one slice annotation (the profile's
    ``/host:metadata`` plane, the compiled programs' HLO, dropped to keep
    the file small). The values were worked out from the file's events
    by plain interval arithmetic, apart from these readers: 11 phases,
    10 layer spans, 9.38 ms of device idle in a 9.47 ms slice."""
    run, got = _readings(monkeypatch, tmp_path, ENGINE_TRACE)
    assert run.reduced.program_runs == {"prefill_paged_chunk": 1,
                                        "decode_steps_paged": 1}
    assert got["host_ms_per_program"] == pytest.approx(3.5769190000000015)
    assert got["kv_sched_ms_per_program"] == pytest.approx(
        0.11146450000000183)
    assert got["idle_host_share"] == pytest.approx(76.19400376789129)
    h = host_spans.of(run)
    assert h.idle_s == pytest.approx(0.009384559999999972)
    assert sum(h.idle_under.values()) / h.idle_s == pytest.approx(
        0.9833341147587107)


def test_traced_cpu_run_reports_no_host_reading_without_a_device(
        monkeypatch, tmp_path):
    """The CPU has no device plane: the readers find no program run and
    no device idle time, and the run still ends correct. (The profiles go
    to a directory of this test's own.)"""
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "trace")
    out = harness.measure(tiny_cell(0.5, per_layer=READERS), 4, 0.01, True,
                          time.perf_counter(),
                          {"bf16_flops_per_s": 1e12,
                           "hbm_bytes_per_s": 1e11})
    assert out["correct"] is True
    assert not set(READERS) & set(out["metrics"])
