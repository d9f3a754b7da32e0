"""Trace reduction on synthetic intervals, and on a small trace recorded
on a TPU v5e when one is committed beside this file."""
import pathlib

import pytest

from benchmarks.chip import trace_reduce as tr

DATA = pathlib.Path(__file__).resolve().parent / "data"


def test_union_merges_overlaps_and_touches():
    got = tr.union([(5, 6), (0, 2), (1, 3), (3, 4), (8, 9)])
    assert got == [(0, 4), (5, 6), (8, 9)]


def test_busy_and_gaps_inside_windows():
    ops = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (9.0, 12.0)]
    windows = [(0.5, 5.0), (10.0, 11.0)]
    # busy: [0.5, 2] + [3, 4] in the first window, [10, 11] in the second
    assert tr.busy_seconds(ops, windows) == pytest.approx(1.5 + 1.0 + 1.0)
    assert tr.gaps(ops, windows) == [(2.0, 3.0), (4.0, 5.0)]
    # an empty window is all gap
    assert tr.gaps([], [(1.0, 2.0)]) == [(1.0, 2.0)]


def test_gaps_are_named_by_the_host_event_that_overlaps_most():
    idle = [(2.0, 3.0), (4.0, 4.5)]
    host = [(tr.SLICE, 0.0, 10.0), ("PjitFunction(decode)", 2.1, 2.3),
            ("np.asarray", 2.3, 2.95), ("other", 9.0, 9.5)]
    got = tr.label_gaps(idle, host)
    assert got == [["np.asarray", pytest.approx(1.0)],
                   ["idle", pytest.approx(0.5)]]


def test_modules_are_named_by_their_dispatch():
    # a jitted functools.partial runs as "jit__unknown"; its host dispatch
    # event names the function. Named modules keep their own names.
    mods = [("jit__unknown(7)", 0.1, 1.0), ("jit__unknown(9)", 1.6, 2.5),
            ("jit_convert_element_type(1)", 2.9, 2.95),
            ("jit__unknown(7)", 3.1, 4.0)]
    disp = [(0.0, "prefill_paged_chunk"), (1.5, "decode_steps_paged"),
            (2.89, "convert_element_type"), (3.0, "prefill_paged_chunk")]
    names = ["prefill_paged_chunk", "decode_steps_paged",
             "convert_element_type", "prefill_paged_chunk"]
    assert [m[0] for m in tr.label_modules(mods, disp)] == names
    # the device clock may run early against the host's: order decides
    early = [(n, s - 0.0011, e - 0.0011) for n, s, e in mods]
    assert [m[0] for m in tr.label_modules(early, disp)] == names
    # with a dispatch missing, time decides, and the runs of one program
    # (fingerprint 7) take the name most of them got
    more = early + [("jit__unknown(7)", 5.0, 5.5)]
    got = tr.label_modules(more, disp + [(4.9, "prefill_paged_chunk"),
                                         (4.95, "copy_pages")])
    assert [m[0] for m in got] == names + ["prefill_paged_chunk"]
    assert tr.label_modules([("jit_x(1)", 0.0, 1.0)], []) == [
        ("x", 0.0, 1.0)]
    # nested duplicate dispatch events count once
    assert tr.dedupe([(0.0, "a"), (0.000001, "a"), (1.0, "a")]) == [
        (0.0, "a"), (1.0, "a")]


def test_program_seconds_and_qualified_ops():
    mods = [("prefill_paged_chunk", 0.0, 1.0),
            ("decode_steps_paged", 1.5, 2.5),
            ("prefill_paged_chunk", 3.0, 4.0),
            ("prefill_paged_chunk_other", 5.0, 6.0)]
    secs, n = tr.program_seconds(mods, "prefill_paged_chunk", [(0.0, 3.5)])
    assert (secs, n) == (pytest.approx(1.5), 2)
    ops = [("%fusion.1", 0.1, 0.2), ("%fusion.1", 1.6, 1.7), ("%copy", 2.7, 2.8)]
    assert [o[0] for o in tr.qualify(ops, mods)] == [
        "prefill_paged_chunk/%fusion.1", "decode_steps_paged/%fusion.1",
        "%copy"]


def test_op_names_are_shortened_and_loops_left_out_of_the_breakdown():
    full = ("%fusion.167 = bf16[12800,2,16,128]{3,1,2,0:T(2,128)(2,1)} "
            "fusion(bf16[7900,2,16,128]{3,1,2,0:T(2,128)} %f), kind=kCustom")
    assert tr._short(full) == "%fusion.167 = bf16[12800,2,16,128] fusion"
    loop = tr._short("%while.4 = (s32[]{:T(128)}) while((s32[]) %t), body=%b")
    ops = [("p/" + loop, 0.0, 2.0), ("p/%fusion.1 = f32[2] fusion", 0.5, 1.0)]
    assert tr.op_seconds(ops, [(0.0, 2.0)]) == {
        "p/%fusion.1 = f32[2] fusion": pytest.approx(0.5)}
    # the loop still counts as busy device time
    assert tr.busy_seconds([(s, e) for _, s, e in ops], [(0.0, 3.0)]) == \
        pytest.approx(2.0)


def test_reduce_averages_busy_over_chips():
    t = tr.Trace(windows=[(0.0, 10.0)],
                 ops={0: [("%a", 0.0, 4.0)], 1: [("%a", 0.0, 2.0)]},
                 modules={0: [("p", 0.0, 4.0)], 1: [("p", 0.0, 2.0)]},
                 host=[("h", 4.0, 10.0)])
    r = tr.reduce([t, t], ["p"])
    assert r.window_s == pytest.approx(20.0)
    assert r.busy_s == pytest.approx(2 * 3.0)
    assert r.program_s["p"] == pytest.approx(6.0)
    assert r.program_runs["p"] == 4
    assert r.device_ops == [["p/%a", pytest.approx(8.0)]]
    assert r.idle_gaps[0] == ["h", pytest.approx(6.0)]


def test_recorded_v5e_trace():
    """Three runs each of two jitted functions named like the engine's
    programs, recorded on a TPU v5e inside one slice annotation. The
    device clock there sits about 1.1 ms before the host's, so the first
    runs fall just before the annotation's window: count over the whole
    trace."""
    files = tr.find(str(DATA))
    if not files:
        pytest.skip("no recorded trace committed")
    t = tr.read(files[0])
    assert len(t.windows) == 1 and sorted(t.ops) == [0]
    everything = [(0.0, 1e9)]
    for name in ("prefill_paged_chunk", "decode_steps_paged"):
        secs, n = tr.program_seconds(t.modules[0], name, everything)
        assert n == 3 and 0.0 < secs
    r = tr.reduce([t], ["prefill_paged_chunk", "decode_steps_paged"])
    assert 0.0 < r.busy_s < r.window_s
    assert 0.0 < sum(r.program_s.values()) <= r.window_s
    assert r.device_ops and r.idle_gaps
    assert all(n.split("/")[0] in ("prefill_paged_chunk",
                                   "decode_steps_paged")
               for n, _ in r.device_ops)
