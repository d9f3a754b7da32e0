"""Structured tracing + latency attribution (DESIGN.md SS15).

Recorder units (tiling, clamping, recompute split, SLO blame, Chrome
structure), a hypothesis property that span accounting conserves time
under arbitrary engine-like event schedules (per-request phase sums ==
end-to-end latency; absorbed stalls == the stats counter), and golden
engine runs asserting event ordering, valid Chrome trace-event output
and strict trace/ServeStats reconciliation on the real serve loop."""
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.reduce import reduced
from repro.serving.trace import (DECODE, DRAFT, PHASES, PREFILL, STALL,
                                 TraceRecorder, validate_chrome_trace)


def _sum_phases(bd):
    return sum(bd[f"{p}_s"] for p in PHASES)


# --------------------------- recorder units ----------------------------- #

def test_span_tiling_fills_gaps_as_queue():
    tr = TraceRecorder()
    tr.submit(0, 10.0)
    tr.admit(0, 11.0)
    tr.span(0, PREFILL, 12.0, 13.0)      # 11 -> 12 gap becomes queue
    tr.retire(0, 13.5)                   # trailing gap too
    bd = tr.breakdown(0)
    assert bd["queue_s"] == pytest.approx(2.5)
    assert bd["prefill_s"] == pytest.approx(1.0)
    assert bd["e2e_s"] == pytest.approx(3.5)
    assert _sum_phases(bd) == pytest.approx(bd["e2e_s"])


def test_span_overlap_clamps_instead_of_double_counting():
    """A decode span launched at a block start whose stall span already
    tiled the barrier must only contribute its uncovered tail."""
    tr = TraceRecorder()
    tr.submit(0, 0.0)
    tr.span(0, STALL, 0.0, 1.0)
    tr.span(0, DECODE, 0.0, 3.0)         # overlaps [0, 1)
    bd = tr.breakdown(0)
    assert bd["stall_s"] == pytest.approx(1.0)
    assert bd["decode_s"] == pytest.approx(2.0)
    assert bd["e2e_s"] == pytest.approx(3.0)


def test_span_fully_covered_is_dropped():
    tr = TraceRecorder()
    tr.submit(0, 0.0)
    tr.span(0, DECODE, 0.0, 2.0)
    tr.span(0, STALL, 0.5, 1.5)          # entirely inside tiled time
    bd = tr.breakdown(0)
    assert bd["stall_s"] == 0.0
    assert bd["decode_s"] == pytest.approx(2.0)


def test_unknown_phase_rejected():
    tr = TraceRecorder()
    tr.submit(0, 0.0)
    with pytest.raises(ValueError, match="unknown phase"):
        tr.span(0, "gpu", 0.0, 1.0)


def test_prefill_span_recompute_split():
    """Re-prefill below the computed-extent high-water mark is labelled
    recompute; fresh tokens stay prefill; mixed chunks split
    proportionally in time."""
    tr = TraceRecorder()
    tr.submit(0, 0.0)
    tr.prefill_span(0, 0.0, 1.0, 0, 32)      # first pass: all prefill
    tr.preempt(0, 1.0, n_valid=32)           # KV lost, extent remembered
    tr.prefill_span(0, 2.0, 3.0, 0, 32)      # full re-prefill: recompute
    tr.prefill_span(0, 3.0, 4.0, 32, 48)     # fresh extension: prefill
    bd = tr.breakdown(0)
    assert bd["recompute_s"] == pytest.approx(1.0)
    assert bd["prefill_s"] == pytest.approx(2.0)
    assert bd["queue_s"] == pytest.approx(1.0)       # preempted wait
    assert bd["n_preemptions"] == 1


def test_prefill_span_partial_recompute_proportional():
    tr = TraceRecorder()
    tr.submit(0, 0.0)
    tr.preempt(0, 0.0, n_valid=8)
    tr.prefill_span(0, 0.0, 1.0, 0, 16)      # half old, half new
    bd = tr.breakdown(0)
    assert bd["recompute_s"] == pytest.approx(0.5)
    assert bd["prefill_s"] == pytest.approx(0.5)


def test_ttft_itl_derived_from_token_instants():
    tr = TraceRecorder()
    tr.submit(3, 1.0)
    tr.token(3, 1.5, 42)
    tr.token(3, 1.7, 43)
    tr.token(3, 2.0, 44)
    tr.retire(3, 2.0)
    bd = tr.breakdown(3)
    assert bd["ttft_s"] == pytest.approx(0.5)
    assert bd["itl_s"] == pytest.approx([0.2, 0.3])
    assert bd["n_tokens"] == 3


def test_slo_report_blames_dominant_window_phase():
    """TTFT violators are blamed on the dominant phase of their
    [submit, first token] window — here a fetch stall."""
    tr = TraceRecorder()
    tr.submit(0, 0.0)
    tr.span(0, STALL, 0.0, 1.0)
    tr.span(0, DECODE, 1.0, 1.2)
    tr.token(0, 1.1, 5)
    tr.retire(0, 1.2)
    tr.submit(1, 0.0)                        # meets the target
    tr.span(1, DECODE, 0.0, 0.1)
    tr.token(1, 0.05, 5)
    tr.retire(1, 0.1)
    rep = tr.slo_report(ttft_target_s=0.5)
    assert rep["n_requests"] == 2 and rep["n_met_slo"] == 1
    assert rep["goodput_frac"] == 0.5
    (v,) = rep["violators"]
    assert v["rid"] == 0 and v["blame"] == "stall"
    assert v["blame_window_ms"]["stall"] == pytest.approx(1000.0)
    # no targets -> everything counts as goodput
    assert tr.slo_report()["goodput_frac"] == 1.0


def test_reconcile_strict_raises_on_drift():
    tr = TraceRecorder()
    tr.submit(0, 0.0)
    tr.span(0, DECODE, 0.0, 1.0)
    tr.token(0, 1.0, 9)
    tr.retire(0, 1.0)
    tr.finalize(1.0)
    ok = tr.reconcile(stall_s=0.0, ttft=[1.0], itl=[], new_tokens=1)
    assert ok["ok"] and not ok["failures"]
    with pytest.raises(AssertionError, match="drift"):
        tr.reconcile(stall_s=0.25, ttft=[1.0], itl=[], new_tokens=1)
    bad = tr.reconcile(stall_s=0.25, ttft=[0.9], itl=[0.1], new_tokens=2,
                       strict=False)
    assert not bad["ok"] and len(bad["failures"]) == 4


def test_chrome_export_structure_and_validation():
    tr = TraceRecorder()
    tr.submit(0, 5.0)
    tr.admit(0, 5.1)
    tr.span(0, DECODE, 5.1, 5.3)
    tr.token(0, 5.2, 7)
    tr.retire(0, 5.3)
    tr.engine_span("decode_block", 5.1, 5.3, {"n_steps": 2})
    tr.device_span("in", 5.0, 5.05, 4096)
    tr.absorbed_stall(5.05, 0.01)
    doc = tr.to_chrome()
    counts = validate_chrome_trace(doc)
    assert counts["X"] >= 4 and counts["i"] >= 3 and counts["M"] >= 6
    ev = doc["traceEvents"]
    # timestamps are rebased: everything non-negative, µs scale
    assert all(e["ts"] >= 0 for e in ev if e["ph"] != "M")
    names = {e["name"] for e in ev}
    assert {"admit", "first_token", "retire", "decode", "decode_block",
            "fetch", "stall", "process_name", "thread_name"} <= names
    assert doc["metadata"]["breakdowns"]["0"]["n_tokens"] == 1


def test_validate_chrome_trace_rejects_malformed():
    with pytest.raises(ValueError):
        validate_chrome_trace({"no": "events"})
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": []})
    with pytest.raises(ValueError, match="unsupported ph"):
        validate_chrome_trace({"traceEvents": [
            {"ph": "B", "pid": 1, "tid": 0, "name": "x", "ts": 0}]})
    with pytest.raises(ValueError, match="bad dur"):
        validate_chrome_trace({"traceEvents": [
            {"ph": "M", "pid": 1, "tid": 0, "name": "process_name",
             "args": {"name": "p"}},
            {"ph": "X", "pid": 1, "tid": 0, "name": "x", "ts": 0,
             "dur": -1}]})
    with pytest.raises(ValueError, match="no track-naming"):
        validate_chrome_trace({"traceEvents": [
            {"ph": "i", "pid": 1, "tid": 0, "name": "x", "ts": 0}]})


# ---------------------- conservation property test ---------------------- #

def _replay_random_schedule(rng):
    """Replay an arbitrary engine-like schedule — staggered submits,
    barrier stalls with per-request attribution, prefill/decode/draft
    blocks whose spans overlap the stall tiles the way real engine
    blocks do (launched at the block start), token emission — against a
    shadow ServeStats-style accumulator. Conservation must hold: every
    request's phase partition sums to its e2e latency, the trace's stall
    total equals the accumulated stat, and reconcile() passes strictly."""
    n = int(rng.integers(1, 5))
    tr = TraceRecorder()
    stats_stall = 0.0
    stall_by_rid = {}
    ttft, itl, last_tok = [], [], {}
    t = 100.0
    submit_t = {}
    for rid in range(n):
        t += float(rng.uniform(0.0, 0.01))
        submit_t[rid] = t
        tr.submit(rid, t)
    for _ in range(int(rng.integers(1, 11))):
        k = int(rng.integers(1, n + 1))
        rids = rng.choice(n, size=k, replace=False).tolist()
        t0 = t
        # fetch-wait barrier: the batch absorbs the max of per-request
        # waits, each request is blamed for its own
        per = {rid: (float(rng.uniform(0.0, 0.02))
                     if rng.random() < 0.5 else 0.0) for rid in rids}
        s = max(per.values())
        if s > 0:
            stats_stall += s
            tr.absorbed_stall(t0, s)
        for rid, v in per.items():
            if v > 0:
                stall_by_rid[rid] = stall_by_rid.get(rid, 0.0) + v
                tr.span(rid, STALL, t0, t0 + v)
        t = t0 + s + float(rng.uniform(0.001, 0.02))
        phase = (PREFILL, DECODE, DRAFT)[int(rng.integers(3))]
        for rid in rids:
            tr.span(rid, phase, t0, t)
            if phase == DECODE:
                if rid in last_tok:
                    itl.append(t - last_tok[rid])
                else:
                    ttft.append(t - submit_t[rid])
                last_tok[rid] = t
                tr.token(rid, t, 7)
    for rid in range(n):
        tr.retire(rid, t)
    tr.finalize(t)
    rep = tr.reconcile(stall_s=stats_stall, ttft=ttft, itl=itl,
                       new_tokens=len(ttft) + len(itl),
                       stall_by_rid=stall_by_rid)
    assert rep["ok"]
    for rid in range(n):
        bd = tr.breakdown(rid)
        assert abs(_sum_phases(bd) - bd["e2e_s"]) < 1e-9
        assert bd["e2e_s"] == pytest.approx(t - submit_t[rid])
    assert validate_chrome_trace(tr.to_chrome())["M"] >= 5 + n


def test_span_accounting_conserves_time_seeded():
    """Deterministic fallback sweep of the conservation property (always
    runs, even without hypothesis)."""
    for seed in range(32):
        _replay_random_schedule(np.random.default_rng(seed))


def test_hypothesis_span_accounting_conserves_time():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=100, deadline=None)
    @given(seed=st.integers(0, 2**32 - 1))
    def run(seed):
        _replay_random_schedule(np.random.default_rng(seed))

    run()


# ------------------------- golden engine traces ------------------------- #

@pytest.fixture(scope="module")
def small_model():
    import jax
    from repro.models import RuntimeOptions, init_params

    cfg = reduced(get_config("llama3.2-1b"), d_model=64, n_layers=2,
                  vocab=128)
    opts = RuntimeOptions(dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0), opts)
    return cfg, opts, params


def _offload_hierarchy(cfg, fast_pages, page_size=8):
    from repro.core import hbs, lpddr6, npu_hierarchy
    from repro.serving.kv_manager import page_bytes

    pb = page_bytes(cfg, page_size, 4)
    return npu_hierarchy(lpddr6(capacity_gb=fast_pages * pb / 1e9),
                         hbs(8.0, latency_us=20.0, capacity_gb=1.0))


def test_golden_trace_offload_run(small_model):
    """Deterministic small serve with a stingy offload tier: the trace
    must reconcile strictly, export valid Chrome JSON, keep per-request
    events ordered (admit <= first_token <= retire), tile each request
    track without overlap, and conserve time in every breakdown."""
    from repro.serving import ServeEngine

    cfg, opts, params = small_model
    rng = np.random.default_rng(3)
    reqs = [rng.integers(1, cfg.vocab, size=n).tolist()
            for n in (20, 9, 14)]
    hier = _offload_hierarchy(cfg, fast_pages=4)
    eng = ServeEngine(cfg, params, opts, max_len=40,
                      scheduler="continuous", page_size=8, max_batch=3,
                      prefill_budget=96, hierarchy=hier, hbs_gbps=1e-3,
                      hbs_latency_us=500.0)
    eng.serve([r[:] for r in reqs], 8)

    tr = eng.trace
    assert eng.trace_report["ok"], eng.trace_report["failures"]
    doc = tr.to_chrome()
    counts = validate_chrome_trace(doc)
    assert counts["X"] > 0 and counts["i"] > 0
    ev = doc["traceEvents"]
    names = {e["name"] for e in ev}
    assert {"admit", "first_token", "retire", "prefill_chunk",
            "decode_block", "fetch", "stall"} <= names

    for rid in range(len(reqs)):
        inst = {e["name"]: e["ts"] for e in ev
                if e["ph"] == "i" and e["pid"] == 1 and e["tid"] == rid}
        assert inst["admit"] <= inst["first_token"] <= inst["retire"]
        spans = sorted((e["ts"], e["ts"] + e["dur"]) for e in ev
                       if e["ph"] == "X" and e["pid"] == 1
                       and e["tid"] == rid)
        for (_, e0), (s1, _) in zip(spans, spans[1:]):
            assert s1 >= e0 - 1e-3          # contiguous tiling (µs tol)

    for rid, bd in tr.breakdowns().items():
        assert abs(_sum_phases(bd) - bd["e2e_s"]) <= 1e-6
        assert bd["n_tokens"] == 8
    # the stingy tier stalls for real, and the trace attributes it
    agg = tr.aggregate_breakdown_ms()
    assert agg["stall_ms"] > 0
    assert eng.stats.stall_s * 1e3 == pytest.approx(
        tr.stall_total * 1e3)

    # goodput report: impossible targets blame every request, absent
    # targets pass every request
    rep = tr.slo_report(1e-9, 1e-9)
    assert rep["goodput_frac"] == 0.0
    assert len(rep["violators"]) == len(reqs)
    assert all(v["blame"] in PHASES for v in rep["violators"])
    assert tr.slo_report()["goodput_frac"] == 1.0


def test_trace_spec_decode_draft_phase(small_model):
    """Speculative serve: draft proposal overhead lands in the DRAFT
    phase and the spec_propose/spec_commit instants appear."""
    from repro.serving import ServeEngine

    cfg, opts, params = small_model
    rng = np.random.default_rng(0)
    doc = rng.integers(1, cfg.vocab, size=32).tolist()
    reqs = [doc + rng.integers(1, cfg.vocab, size=4).tolist()
            for _ in range(2)]
    eng = ServeEngine(cfg, params, opts, max_len=72,
                      scheduler="continuous", page_size=8, max_batch=2,
                      spec_mode="ngram", spec_k=4)
    eng.serve([r[:] for r in reqs], 16)
    assert eng.trace_report["ok"], eng.trace_report["failures"]
    names = {e["name"] for e in eng.trace.to_chrome()["traceEvents"]}
    assert {"spec_propose", "spec_verify", "spec_commit"} <= names
    agg = eng.trace.aggregate_breakdown_ms()
    assert agg["draft_ms"] > 0
    assert agg["decode_ms"] > 0


def test_trace_preemption_recompute_attribution(small_model):
    """A pool too small for everyone's lookahead windows preempts LIFO;
    without the prefix cache the re-prefill is honest recompute and the
    trace labels it so."""
    from repro.serving import ServeEngine

    cfg, opts, params = small_model
    reqs = [list(range(1, 5)), list(range(5, 9))]
    eng = ServeEngine(cfg, params, opts, max_len=32,
                      scheduler="continuous", page_size=4, max_batch=2,
                      n_pages=6, decode_lookahead=4, prefix_cache=False)
    eng.serve([r[:] for r in reqs], 12)
    assert eng.stats.preemptions >= 1
    assert eng.trace_report["ok"], eng.trace_report["failures"]
    names = {e["name"] for e in eng.trace.to_chrome()["traceEvents"]}
    assert "preempt" in names
    bds = eng.trace.breakdowns()
    assert sum(bd["n_preemptions"] for bd in bds.values()) \
        == eng.stats.preemptions
    assert any(bd["recompute_s"] > 0 for bd in bds.values())


def test_second_serve_on_same_engine_reconciles(small_model):
    """ServeStats accumulates across serve() calls; the per-serve trace
    must reconcile against the deltas, not the lifetime totals."""
    from repro.serving import ServeEngine

    cfg, opts, params = small_model
    rng = np.random.default_rng(7)
    reqs = [rng.integers(1, cfg.vocab, size=12).tolist() for _ in range(2)]
    eng = ServeEngine(cfg, params, opts, max_len=32,
                      scheduler="continuous", page_size=8, max_batch=2)
    eng.serve([r[:] for r in reqs], 6)
    first = eng.trace
    eng.serve([r[:] for r in reqs], 6)
    assert eng.trace is not first                  # fresh recorder
    assert eng.trace_report["ok"], eng.trace_report["failures"]
    assert len(eng.stats.ttft) == 4                # totals kept growing


# ------------------- host phases on the wall clock ----------------------- #

def _host_phase_engine(small_model, path):
    """A serve down one path of the loop: plain, prefix-shared,
    preempting or speculative."""
    from repro.serving import ServeEngine

    cfg, opts, params = small_model
    rng = np.random.default_rng(11)
    kw = dict(max_len=72, scheduler="continuous", page_size=8, max_batch=2)
    new = 8
    if path == "plain":
        reqs = [rng.integers(1, cfg.vocab, size=n).tolist() for n in (13, 21)]
    elif path == "prefix":
        doc = rng.integers(1, cfg.vocab, size=40).tolist()
        reqs = [doc + rng.integers(1, cfg.vocab, size=3).tolist()
                for _ in range(3)]
    elif path == "preempt":
        reqs = [list(range(1, 5)), list(range(5, 9))]
        kw.update(max_len=32, page_size=4, n_pages=6, decode_lookahead=4,
                  prefix_cache=False)
        new = 12
    else:
        doc = rng.integers(1, cfg.vocab, size=32).tolist()
        reqs = [doc + rng.integers(1, cfg.vocab, size=4).tolist()
                for _ in range(2)]
        kw.update(spec_mode="ngram", spec_k=4)
        new = 16
    eng = ServeEngine(cfg, params, opts, **kw)
    eng.serve([r[:] for r in reqs], new)
    return eng


@pytest.mark.parametrize("path", ["plain", "prefix", "preempt", "spec"])
def test_host_phases_tile_every_serve(small_model, path):
    """One host phase is open from entry to reconcile, so the phases'
    seconds sum to the serve's wall time; each run phase ran once per
    program the virtual trace shows, and each path's own layer spans
    appear."""
    from repro.serving.trace import HOST_PHASES, LAYER_SPANS

    eng = _host_phase_engine(small_model, path)
    tr, rep = eng.trace, eng.trace_report
    assert rep["ok"], rep["failures"]
    assert rep["wall_s"] > 0
    tiled = sum(tr.host_s.get(p, 0.0) for p in HOST_PHASES)
    assert abs(tiled - rep["wall_s"]) <= 1e-6
    assert set(tr.host_s) <= set(HOST_PHASES) | set(LAYER_SPANS)
    assert set(tr.host_s) == set(tr.host_n)
    assert tr.host_n["setup"] == tr.host_n["finish"] == 1
    ev = tr.to_chrome()["traceEvents"]

    def n_spans(name):
        return sum(1 for e in ev if e["ph"] == "X" and e["name"] == name)
    assert tr.host_n["prefill.run"] == n_spans("prefill_chunk")
    assert tr.host_n.get("decode.run", 0) == n_spans("decode_block")
    assert tr.host_n.get("spec.run", 0) == n_spans("spec_verify")
    # a preempted request's re-prefill ends in a token pull too
    assert tr.host_n["first_token"] >= len(eng.stats.ttft)
    assert {"sched.admit", "kv.copies", "kv.residency"} <= set(tr.host_s)
    # nested layer spans lie inside the phases
    layers = sum(tr.host_s[s] for s in ("sched.admit", "kv.residency"))
    assert layers <= tiled
    if path == "prefix":
        assert eng.stats.cached_prefix_tokens > 0
        assert tr.host_n["kv.register_prefix"] >= tr.host_n["prefill.run"]
    if path == "preempt":
        assert eng.stats.preemptions >= 1
        assert "sched.reserve" in tr.host_s
    if path == "spec":
        assert tr.host_n["spec.propose"] >= tr.host_n["spec.run"] > 0
        assert "decode.run" not in tr.host_s
    # wall stamps: one per token, in order, after each request's submit
    for rid, out in enumerate(eng.trace.breakdowns().values()):
        w = tr.wall(rid)
        assert len(w["token_t"]) == out["n_tokens"]
        assert w["token_t"] == sorted(w["token_t"])
        assert 0 <= w["queue_s"] <= w["ttft_s"] <= rep["wall_s"]


def test_reconcile_raises_when_a_host_phase_is_left_out():
    tr = TraceRecorder()
    tr.phase("setup")
    tr.phase("admit")
    with tr.layer("sched.admit"):
        pass
    tr.phase("finish")
    rep = tr.reconcile(stall_s=0.0, ttft=[], itl=[], new_tokens=0)
    assert rep["ok"] and rep["wall_s"] >= tr.host_s["admit"]
    assert tr.host_n == {"setup": 1, "admit": 1, "sched.admit": 1,
                         "finish": 1}
    with pytest.raises(RuntimeError, match="ended"):
        tr.phase("admit")               # phases end at reconcile

    tr = TraceRecorder()
    tr.phase("setup")
    tr.phase("admit")
    tr.phase("finish")
    tr.end_phases()
    del tr.host_s["admit"]              # a phase's seconds went missing
    with pytest.raises(AssertionError, match="host phases"):
        tr.reconcile(stall_s=0.0, ttft=[], itl=[], new_tokens=0)
    with pytest.raises(ValueError, match="unknown host phase"):
        TraceRecorder().phase("idle")
    with pytest.raises(ValueError, match="unknown layer span"):
        TraceRecorder().layer("kv.other")


def _lowered_module(small_model, program):
    """The module name of one engine program, lowered at a tiny size."""
    import re

    import jax
    import jax.numpy as jnp
    from repro.models import init_cache, init_paged_cache
    from repro.serving import ServeEngine

    cfg, opts, params = small_model
    eng = ServeEngine(cfg, params, opts, max_len=32, page_size=8,
                      max_batch=2, scheduler="continuous",
                      temperature=0.5)
    B, npp, V = 2, 4, cfg.vocab
    pool = init_paged_cache(cfg, 9, 8, opts)
    dense = init_cache(cfg, B, 32, opts)
    def i32(*shape):
        return jnp.zeros(shape, jnp.int32)
    keys = eng._block_keys(i32(B), i32(B))
    calls = {
        "_prefill": lambda: eng._prefill.lower(params, i32(B, 8), dense),
        "_decode": lambda: eng._decode.lower(params, i32(B), jnp.int32(8),
                                             dense),
        "_decode_block": lambda: eng._decode_block.lower(
            params, i32(B), jnp.int32(8), dense, n_steps=2),
        "_prefill_chunk": lambda: eng._prefill_chunk.lower(
            params, i32(1, 16), pool, i32(1, npp), jnp.int32(0),
            i32(1), calibrate=False),
        "_decode_fused": lambda: eng._decode_fused.lower(
            params, i32(B), i32(B), i32(B, npp), pool, n_steps=2,
            keys=keys, done=jnp.zeros((B,), bool), quota=i32(B)),
        "_spec_verify": lambda: eng._spec_verify.lower(
            params, i32(B, 3), i32(B), i32(B), i32(B, npp), pool, keys),
        "_copy_pages": lambda: eng._copy_pages.lower(pool, i32(2, 2)),
        "_block_keys": lambda: eng._block_keys.lower(i32(B), i32(B)),
        "_sample1": lambda: eng._sample1.lower(
            jnp.zeros((1, V), jnp.float32), keys[:1]),
    }
    text = calls[program]().as_text()
    return re.search(r"module @(\S+)", text).group(1)


@pytest.mark.parametrize("program,function", [
    ("_prefill", "prefill"), ("_decode", "decode_step"),
    ("_decode_block", "decode_steps"),
    ("_prefill_chunk", "prefill_paged_chunk"),
    ("_decode_fused", "decode_steps_paged"),
    ("_spec_verify", "spec_decode_verify"), ("_copy_pages", "copy_pages"),
    ("_block_keys", "block_keys"), ("_sample1", "sample")])
def test_engine_programs_lower_to_their_names(small_model, program,
                                              function):
    """A jitted bare ``functools.partial`` lowers to ``jit__unknown``;
    every engine program carries its function's name instead, which is
    the name a device trace shows for it."""
    assert _lowered_module(small_model, program) == f"jit_{function}"


def test_second_same_shape_serve_compiles_no_program(small_model):
    """The compile listener counts by program while a serve runs: the
    first serve compiles the engine's programs, a second of the same
    shapes compiles nothing, and the listener is gone afterwards."""
    from jax._src import monitoring
    from repro.serving import ServeEngine

    cfg, opts, params = small_model
    rng = np.random.default_rng(5)
    reqs = [rng.integers(1, cfg.vocab, size=n).tolist() for n in (9, 17)]
    eng = ServeEngine(cfg, params, opts, max_len=48,
                      scheduler="continuous", page_size=8, max_batch=2,
                      decode_lookahead=3)
    before = len(monitoring.get_event_duration_listeners())
    eng.serve([r[:] for r in reqs], 6)
    first = dict(eng.trace.compiles)
    eng.serve([r[:] for r in reqs], 6)
    assert {"prefill_paged_chunk", "decode_steps_paged"} <= set(first)
    assert eng.trace.compiles == {}
    assert eng.trace.n_compiles == 0
    assert len(monitoring.get_event_duration_listeners()) == before


def test_failed_serve_ends_its_phases(small_model):
    """A serve that raises mid-loop still closes its open phase and
    drops its compile listener."""
    from jax._src import monitoring
    from repro.serving import ServeEngine

    cfg, opts, params = small_model
    eng = ServeEngine(cfg, params, opts, max_len=32,
                      scheduler="continuous", page_size=8, max_batch=2)

    def broken(*args, **kwargs):
        raise RuntimeError("device lost")
    eng._prefill_chunk = broken
    before = len(monitoring.get_event_duration_listeners())
    with pytest.raises(RuntimeError, match="device lost"):
        eng.serve([[1, 2, 3]], 4)
    assert len(monitoring.get_event_duration_listeners()) == before
    assert eng.trace.host_n["prefill.run"] == 1
    with pytest.raises(RuntimeError, match="ended"):
        eng.trace.phase("admit")
