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
    # runs of one chip: each chip runs every program of the mesh once
    assert r.program_runs["p"] == 2
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


def test_four_chip_trace_counts_one_chip_runs_and_averages_busy():
    # each chip runs the mesh's programs once: two prefill runs and one
    # decode run on every one of four chips, busy 3, 4, 5 and 6 s
    def chip(busy):
        return [("%fusion.1 = bf16[2] fusion", 0.0, busy)]
    mods = [("prefill_paged_chunk", 0.0, 1.0),
            ("prefill_paged_chunk", 1.0, 2.0),
            ("decode_steps_paged", 2.0, 3.0)]
    t = tr.Trace(windows=[(0.0, 10.0)],
                 ops={c: chip(3.0 + c) for c in range(4)},
                 modules={c: list(mods) for c in range(4)})
    r = tr.reduce([t], ["prefill_paged_chunk", "decode_steps_paged"])
    assert r.program_runs == {"prefill_paged_chunk": 2,
                              "decode_steps_paged": 1}
    assert r.busy_s == pytest.approx((3 + 4 + 5 + 6) / 4)
    assert r.program_s["prefill_paged_chunk"] == pytest.approx(2.0)
    assert r.collective_s == 0.0


@pytest.mark.parametrize("op,want", [
    ("%all-gather.3 = bf16[1,32,32,128] all-gather", True),
    ("%all-gather-start.1 = ", True),        # a tuple type left no kind
    ("%ag = bf16[8] all-gather-done", True),
    ("%all-reduce.2 = f32[8] all-reduce", True),
    ("%reduce-scatter.1 = f32[2] reduce-scatter", True),
    ("%all-to-all = f32[8] all-to-all", True),
    ("%collective-permute-start.4 = ", True),
    ("%fusion.12 = bf16[8,128] fusion", False),
    ("%copy.3 = bf16[8] copy", False),
    ("%all-gatherish = f32[2] custom-call", False),
])
def test_collectives_by_op_kind(op, want):
    assert tr.is_collective(op) is want


def test_collective_share_by_hand():
    from types import SimpleNamespace

    from benchmarks.chip import harness
    read = harness.reader("collective_share")
    # two chips in a 10 s slice. Chip 0: compute 0-4 s, an all-gather
    # 4-5 s, its async done half 4.5-5.5 s (overlapping: 1.5 s of
    # collectives), compute 6-8 s; busy 7.5 s. Chip 1: compute 0-5 s, an
    # all-reduce 5-5.5 s; busy 5.5 s.
    t = tr.Trace(windows=[(0.0, 10.0)], ops={
        0: [("%fusion.1 = bf16[2] fusion", 0.0, 4.0),
            ("%all-gather-start.1 = ", 4.0, 5.0),
            ("%all-gather-done.1 = bf16[4] all-gather-done", 4.5, 5.5),
            ("%fusion.2 = bf16[2] fusion", 6.0, 8.0)],
        1: [("%fusion.1 = bf16[2] fusion", 0.0, 5.0),
            ("%all-reduce.1 = f32[2] all-reduce", 5.0, 5.5)]})
    r = tr.reduce([t], [])
    assert r.busy_s == pytest.approx((7.5 + 5.5) / 2)
    assert r.collective_s == pytest.approx((1.5 + 0.5) / 2)
    assert r.collective_ops == {"%all-gather-start.1 = ": pytest.approx(1.0),
                                "%all-gather-done.1 = bf16[4] all-gather-done":
                                pytest.approx(1.0)}
    run = SimpleNamespace(reduced=r)
    assert read(run) == pytest.approx(100.0 * 1.0 / 6.5)
    # no collective, or no trace: nothing to read
    assert read(SimpleNamespace(reduced=tr.reduce(
        [tr.Trace(windows=[(0.0, 1.0)],
                  ops={0: [("%fusion.1 = f32[2] fusion", 0.0, 0.5)]})],
        []))) is None
    assert read(SimpleNamespace(reduced=None)) is None


def test_roofline_readers_divide_by_chips_times_one_chips_peak():
    from types import SimpleNamespace

    from benchmarks.chip import harness, spans
    peak = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    work = spans.Work(prefill_flops=4e11, decode_flops=1e11,
                      decode_bytes=2e11)
    reduced = tr.Reduced(window_s=10.0, busy_s=8.0,
                         program_s={"prefill_paged_chunk": 2.0,
                                    "decode_steps_paged": 4.0})

    def reads(chips):
        run = SimpleNamespace(cell=SimpleNamespace(chips=chips), peak=peak,
                              reduced=reduced, work=work)
        return [harness.reader(n)(run)
                for n in ("step_mfu", "prefill_mfu", "decode_roofline")]
    # one chip: 5e11 / 10 s / 1e12; 4e11 / 2 s / 1e12; 2e11 B / 1e11 / 4 s
    assert reads(1) == [pytest.approx(5.0), pytest.approx(20.0),
                        pytest.approx(50.0)]
    assert reads(4) == [pytest.approx(v / 4) for v in reads(1)]
