"""The engine loop's host phases in the traced slices, and the device's
idle time under them.

The engine marks its loop with profiler annotations on the host plane:
``engine.<phase>`` phases that follow one another and never nest, and
``sched.*`` / ``kv.*`` spans of single layers inside them. A span may
carry metadata, written as a ``#k=v,...#`` suffix of its name. They sit
on the trace's own clock beside the device's operations, so the idle
stretches between operations (``trace_reduce.gaps``) can be read by the
phase the host was in.

``of(run)`` reads the traced run's profiles once (they are still under
``harness.TRACE_DIR`` while the metric readers run) and returns
``None`` where they hold no ``engine.*`` event, as from an engine
without the annotations.
"""
from __future__ import annotations

import sys
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks.chip import trace_reduce

PHASE = "engine."
LAYERS = ("sched.", "kv.")
RUN = ".run"            # a phase that dispatches a program and waits for it

Event = Tuple[str, float, float]


@dataclass
class HostTime:
    """Seconds inside the slices of every trace of one run."""
    phase_s: Dict[str, float] = field(default_factory=dict)
    layer_s: Dict[str, float] = field(default_factory=dict)
    layer_union_s: float = 0.0      # any sched.* or kv.* span running
    idle_s: float = 0.0             # no operation on the first chip
    idle_under: Dict[str, float] = field(default_factory=dict)

    @property
    def host_s(self) -> float:
        """Seconds of the phases other than ``*.run``."""
        return sum(v for p, v in self.phase_s.items() if not is_run(p))

    @property
    def idle_host_s(self) -> float:
        """Idle seconds under a phase other than ``*.run``."""
        return sum(v for p, v in self.idle_under.items() if not is_run(p))


def is_run(phase: str) -> bool:
    return phase.endswith(RUN)


def strip(name: str) -> str:
    """``engine.prefill.run#rid=3,n=32#`` -> ``engine.prefill.run``."""
    return name.split("#", 1)[0]


def clip_all(events: Sequence[Event], windows) -> List[Event]:
    """The parts of ``events`` inside ``windows``, sorted by start."""
    out = []
    for name, s0, e0 in events:
        for w in windows:
            for s, e in trace_reduce.clip([(s0, e0)], w):
                out.append((name, s, e))
    return sorted(out, key=lambda ev: ev[1])


def overlap_by_name(events: Sequence[Event],
                    intervals: Sequence[Tuple[float, float]]
                    ) -> Dict[str, float]:
    """Seconds of ``intervals`` (sorted, disjoint) under each name of
    ``events`` (sorted by start, not overlapping one another)."""
    out: Dict[str, float] = {}
    j = 0
    for name, s, e in events:
        while j < len(intervals) and intervals[j][1] <= s:
            j += 1
        k = j
        while k < len(intervals) and intervals[k][0] < e:
            ov = min(e, intervals[k][1]) - max(s, intervals[k][0])
            if ov > 0:
                out[name] = out.get(name, 0.0) + ov
            k += 1
    return out


def reduce(traces: Sequence[trace_reduce.Trace]) -> Optional[HostTime]:
    """Phase, layer and idle seconds over the slices of ``traces``;
    ``None`` when no trace holds an ``engine.*`` event."""
    h = HostTime()
    seen = False
    for tr in traces:
        events = [(strip(n), s, e) for n, s, e in tr.host
                  if n.startswith(PHASE) or n.startswith(LAYERS)]
        seen |= any(n.startswith(PHASE) for n, _, _ in events)
        events = clip_all(events, tr.windows)
        phases = [(n[len(PHASE):], s, e) for n, s, e in events
                  if n.startswith(PHASE)]
        layers = [ev for ev in events if not ev[0].startswith(PHASE)]
        for name, s, e in phases:
            h.phase_s[name] = h.phase_s.get(name, 0.0) + (e - s)
        for name, s, e in layers:
            h.layer_s[name] = h.layer_s.get(name, 0.0) + (e - s)
        h.layer_union_s += sum(
            e - s for s, e in trace_reduce.union((s, e) for _, s, e in layers))
        chips = sorted(tr.ops)
        if chips:
            idle = trace_reduce.gaps([(s, e) for _, s, e in tr.ops[chips[0]]],
                                     tr.windows)
            h.idle_s += sum(e - s for s, e in idle)
            for name, v in overlap_by_name(phases, idle).items():
                h.idle_under[name] = h.idle_under.get(name, 0.0) + v
    return h if seen else None


def of(run) -> Optional[HostTime]:
    """``reduce`` over the traced run's profiles, read once per run (the
    reading is kept on ``run``)."""
    if "_host_time" in vars(run):
        return run._host_time
    from benchmarks.chip import harness
    t0 = time.perf_counter()
    got = reduce([trace_reduce.read(p)
                  for p in trace_reduce.find(str(harness.TRACE_DIR))])
    run._host_time = got
    if got is not None:
        under = sum(got.idle_under.values())
        print(f"[bench] host_spans: read_s={time.perf_counter() - t0!r} "
              f"idle_s={got.idle_s!r} idle_under_phases_s={under!r} "
              f"phase_s={got.phase_s} idle_under={got.idle_under} "
              f"layer_s={got.layer_s} layer_union_s={got.layer_union_s!r}",
              file=sys.stderr, flush=True)
    return got


def program_runs(run) -> int:
    """Runs of the prefill and decode programs in the slices."""
    return sum(run.reduced.program_runs.values()) if run.reduced else 0
