"""Reduction of a profiler trace to device busy time, program time and a
breakdown.

The JAX profiler writes ``<dir>/plugins/profile/<stamp>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it with nothing but JAX. A TPU shows
one plane per chip (``/device:TPU:<n>``) whose ``XLA Ops`` line holds one
event per operation run (a ``while`` loop's event holds its body's) and
whose ``XLA Modules`` line holds one event per program run. A module is
named ``jit_<function>(<fingerprint>)``, but a jitted
``functools.partial``, as the engine builds its programs, is named
``jit__unknown``; the host's dispatch event ``PjitFunction(<function>)``
does carry the function's name. So each module is labelled with the
function of the last dispatch that began before it. Host threads sit on
``/host:CPU``. The benchmark opens each traced slice with a host
annotation (``SLICE``), which gives the slice's window on the trace's own
clock.

All times here are seconds on that clock.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]
SLICE = "bench_traced_slice"


@dataclass
class Trace:
    """Events of one trace file, as (name, start, end) in seconds."""
    windows: List[Interval] = field(default_factory=list)
    ops: Dict[int, List[Tuple[str, float, float]]] = field(
        default_factory=dict)               # chip id -> operations
    modules: Dict[int, List[Tuple[str, float, float]]] = field(
        default_factory=dict)               # chip id -> program runs
    host: List[Tuple[str, float, float]] = field(default_factory=list)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping or touching intervals; sorted, disjoint."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def busy_seconds(intervals: Iterable[Interval],
                 windows: Sequence[Interval]) -> float:
    """Seconds inside ``windows`` in which at least one interval runs."""
    merged = union(intervals)
    return sum(e - s for w in windows for s, e in clip(merged, w))


def gaps(intervals: Iterable[Interval],
         windows: Sequence[Interval]) -> List[Interval]:
    """Idle stretches inside ``windows``: no interval runs."""
    merged = union(intervals)
    out = []
    for lo, hi in windows:
        t = lo
        for s, e in clip(merged, (lo, hi)):
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if hi > t:
            out.append((t, hi))
    return out


def label_gaps(idle: Sequence[Interval],
               host: Sequence[Tuple[str, float, float]],
               top: int = 10) -> List[List[object]]:
    """The ``top`` longest gaps, each named by the host event that
    overlaps it most (``"idle"`` when none does). Events that cover the
    whole gap and more, such as the slice annotation itself, are the
    least specific and lose ties to shorter ones."""
    out = []
    for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:top]:
        best, key = "idle", (0.0, 0.0)
        for name, hs, he in host:
            ov = min(e, he) - max(s, hs)
            if ov > 0 and name != SLICE:
                k = (ov, -(he - hs))
                if k > key:
                    best, key = name, k
        out.append([best, e - s])
    return out


def op_seconds(ops: Iterable[Tuple[str, float, float]],
               windows: Sequence[Interval]) -> Dict[str, float]:
    """Device seconds of each leaf operation inside ``windows``."""
    tot: Dict[str, float] = {}
    for name, s, e in ops:
        if not _is_leaf(name.rpartition("/")[2]):
            continue
        d = sum(b - a for w in windows for a, b in clip([(s, e)], w))
        if d > 0:
            tot[name] = tot.get(name, 0.0) + d
    return tot


def program_seconds(modules: Iterable[Tuple[str, float, float]], program,
                    windows) -> Tuple[float, int]:
    """Device seconds and run count of the program labelled ``program``
    (``label_modules``), inside ``windows``."""
    secs, n = 0.0, 0
    for name, s, e in modules:
        if name == program:
            d = sum(b - a for w in windows for a, b in clip([(s, e)], w))
            if d > 0:
                secs += d
                n += 1
    return secs, n


def qualify(ops: Sequence[Tuple[str, float, float]],
            modules: Sequence[Tuple[str, float, float]]):
    """Prefix each operation with the program it ran in (the module event
    that holds its start), since operation names repeat across
    programs."""
    mods = sorted(modules, key=lambda m: m[1])
    out, j = [], 0
    for name, s, e in sorted(ops, key=lambda o: o[1]):
        while j < len(mods) and mods[j][2] < s:
            j += 1
        if j < len(mods) and mods[j][1] <= s:
            name = f"{mods[j][0]}/{name}"
        out.append((name, s, e))
    return out


def describe(path: str) -> List[str]:
    """One line per plane and line of a trace file, with event counts and
    the most frequent names: the hand read a new trace starts from."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            names: Dict[str, int] = {}
            for ev in line.events:
                names[ev.name] = names.get(ev.name, 0) + 1
            common = sorted(names.items(), key=lambda kv: -kv[1])[:6]
            out.append(f"{plane.name} | {line.name} | "
                       f"{sum(names.values())} | {common}")
    return out


def _short(op: str) -> str:
    """``%fusion.3 = bf16[8,128]{...} fusion(...)`` -> ``%fusion.3 =
    bf16[8,128] fusion``: the op, its result type and its kind."""
    lhs, _, rhs = op.partition(" = ")
    rhs = re.sub(r"\{[^{}]*\}", "", rhs)
    return f"{lhs} = {rhs.split('(')[0]}"[:120] if rhs else lhs[:120]


def _is_leaf(op: str) -> bool:
    """Control-flow ops hold their body's ops; only leaves are counted."""
    kind = op.split(" = ")[0].lstrip("%").split(".")[0]
    return kind not in ("while", "conditional", "call")


COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def is_collective(op: str) -> bool:
    """An exchange between chips, by its HLO op kind (the last word of a
    ``_short`` name; its instruction name where a tuple type left the
    kind out), asynchronous ``-start`` and ``-done`` halves included."""
    lhs, _, rhs = op.partition(" = ")
    words = [lhs.lstrip("%").split(".")[0]] + rhs.split()[-1:]
    for w in words:
        for suffix in ("-start", "-done"):
            w = w[:-len(suffix)] if w.endswith(suffix) else w
        if w in COLLECTIVES:
            return True
    return False


SKEW_S = 0.002   # device events may sit this far before their dispatch


def _function(module: str) -> str:
    """``jit_<function>(<fingerprint>)`` -> ``<function>``."""
    name = module.split("(")[0]
    return name[4:] if name.startswith("jit_") else name


def label_modules(modules, dispatches):
    """Name each module event after the function that ran it. A module
    named ``jit__unknown`` (a jitted ``functools.partial``) takes the
    name of its ``PjitFunction(<name>)`` host dispatch, among the
    dispatches of functions that have no module name of their own: by
    order where the counts agree, else the last that began before it,
    allowing for the device clock sitting up to ``SKEW_S`` early against
    the host's (1.1 ms on a v5e trace). Runs of one compiled program
    (one fingerprint) then all take the name most of them got."""
    mods = sorted(modules, key=lambda m: m[1])
    named = {_function(m[0]) for m in mods} - {"_unknown"}
    cands = [d for d in dispatches if d[1] not in named]
    unknown = [m for m in mods if _function(m[0]) == "_unknown"]
    if len(unknown) == len(cands):
        guess = {id(m): d[1] for d, m in zip(cands, unknown)}
    else:
        starts = [t for t, _ in cands]
        guess = {}
        for m in unknown:
            i = bisect.bisect_right(starts, m[1] + SKEW_S) - 1
            guess[id(m)] = cands[i][1] if i >= 0 else "_unknown"
    votes: Dict[str, Dict[str, int]] = {}
    for m in unknown:
        v = votes.setdefault(m[0], {})
        v[guess[id(m)]] = v.get(guess[id(m)], 0) + 1
    best = {raw: max(v, key=v.get) for raw, v in votes.items()}
    return [(best.get(raw, _function(raw)), s, e) for raw, s, e in mods]


def dedupe(dispatches):
    """The profiler writes each dispatch twice, nested; keep one."""
    out = []
    for t, name in sorted(dispatches):
        if not (out and out[-1][1] == name and t - out[-1][0] < 1e-5):
            out.append((t, name))
    return out


def _chip_id(plane_name: str) -> int:
    return int(plane_name.rsplit(":", 1)[1])


def read(path: str) -> Trace:
    """Events of one ``.xplane.pb`` file; modules labelled by dispatch."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    tr = Trace()
    dispatches = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            chip = _chip_id(plane.name)
            for line in plane.lines:
                if line.name == "XLA Ops":
                    tr.ops[chip] = [(_short(ev.name), ev.start_ns * 1e-9,
                                     ev.end_ns * 1e-9) for ev in line.events]
                elif line.name == "XLA Modules":
                    tr.modules[chip] = [(ev.name, ev.start_ns * 1e-9,
                                         ev.end_ns * 1e-9)
                                        for ev in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    item = (ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                    if ev.name == SLICE:
                        tr.windows.append(item[1:])
                    elif ev.duration_ns > 0:
                        tr.host.append(item)
                    if ev.name.startswith("PjitFunction("):
                        dispatches.append((item[1], ev.name[13:-1]))
    dispatches = dedupe(dispatches)
    tr.modules = {c: label_modules(m, dispatches)
                  for c, m in tr.modules.items()}
    return tr


def find(log_dir: str) -> List[str]:
    """Every trace file under ``log_dir`` (one profiler session each)."""
    return sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))


@dataclass
class Reduced:
    """What the per-layer readers take from the traces of one run."""
    window_s: float = 0.0
    busy_s: float = 0.0                     # averaged over chips
    collective_s: float = 0.0               # averaged over chips
    program_s: Dict[str, float] = field(default_factory=dict)  # averaged
    program_runs: Dict[str, int] = field(default_factory=dict)  # one chip's
    collective_ops: Dict[str, float] = field(default_factory=dict)  # first
    device_ops: List[List[object]] = field(default_factory=list)
    idle_gaps: List[List[object]] = field(default_factory=list)


def reduce(traces: Sequence[Trace], programs: Sequence[str],
           top: int = 10) -> Reduced:
    """Busy and idle time, per-program device time and the breakdown
    over every slice of every trace (each trace on its own clock).
    Seconds are averaged over the chips; a program's runs are those of
    one chip, since every chip runs each program of the mesh once."""
    r = Reduced()
    per_op: Dict[str, float] = {}
    labelled: List[List[object]] = []
    for tr in traces:
        win = tr.windows
        r.window_s += sum(e - s for s, e in win)
        chips = sorted(tr.ops)
        for chip in chips:
            iv = [(s, e) for _, s, e in tr.ops[chip]]
            r.busy_s += busy_seconds(iv, win) / len(chips)
            coll = [(s, e) for n, s, e in tr.ops[chip] if is_collective(n)]
            r.collective_s += busy_seconds(coll, win) / len(chips)
            for p in programs:
                secs, n = program_seconds(tr.modules.get(chip, []), p, win)
                r.program_s[p] = r.program_s.get(p, 0.0) + secs / len(chips)
                if chip == chips[0]:
                    r.program_runs[p] = r.program_runs.get(p, 0) + n
        if chips:
            first = qualify(tr.ops[chips[0]], tr.modules.get(chips[0], []))
            for name, t in op_seconds(first, win).items():
                per_op[name] = per_op.get(name, 0.0) + t
                if is_collective(name.rpartition("/")[2]):
                    r.collective_ops[name] = (r.collective_ops.get(name, 0.0)
                                              + t)
            idle = gaps([(s, e) for _, s, e in first], win)
            labelled += label_gaps(idle, tr.host, top)
    r.device_ops = [[n, t] for n, t in
                    sorted(per_op.items(), key=lambda kv: -kv[1])[:top]]
    r.idle_gaps = sorted(labelled, key=lambda g: -g[1])[:top]
    return r
