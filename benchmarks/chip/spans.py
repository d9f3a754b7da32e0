"""The wall-clock seam on the engine's trace recorder, and the work it
saw.

The engine builds a fresh ``TraceRecorder`` per ``serve_continuous``
call, from the name it imported (``repro.serving.engine.TraceRecorder``).
``stamped()`` puts a subclass there for the length of a run. Its
``token()`` takes ``time.perf_counter()`` and then calls the recorder's
own, and its ``engine_span()`` calls the recorder's own and then notes
the program that just returned (name and arguments) on the host clock.
The engine calls both right after the host sync that ends the program or
pulls the token, so the stamps are when the host had the result. Nothing
else about the engine changes; the seam only holds while the recorder
keeps these two methods and their arguments.

``Work`` turns the noted programs of the traced slices into the
operations and bytes they needed (the counts of the model's family,
``families/``).
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from types import ModuleType
from typing import Callable, Dict, List, Optional, Sequence, Tuple

PREFILL, DECODE = "prefill_chunk", "decode_block"


class Sink:
    """What the recorders of one wave saw, in call order: ``("token",
    t, rid)`` and ``("program", t, name, args)``."""

    def __init__(self, on_program: Optional[Callable[["Sink"], None]] = None):
        self.events: List[tuple] = []
        self.tokens: Dict[int, List[float]] = {}
        self.on_program = on_program

    def token(self, rid: int) -> None:
        t = time.perf_counter()
        self.tokens.setdefault(rid, []).append(t)
        self.events.append(("token", t, rid))

    def program(self, name: str, args: Optional[dict]) -> None:
        self.events.append(("program", time.perf_counter(), name,
                            dict(args or {})))
        if self.on_program is not None:
            self.on_program(self)


class Seam:
    """Where the stamping recorders of one run send their stamps: the
    sink of the wave being served, or nowhere."""

    def __init__(self):
        self.sink: Optional[Sink] = None

    @contextmanager
    def collecting(self, sink: Sink):
        """Route the recorders' stamps into ``sink`` (one wave)."""
        self.sink = sink
        try:
            yield sink
        finally:
            self.sink = None


def _recorder(base, seam: Seam):
    class StampingRecorder(base):
        def token(self, rid, t, tok):
            if seam.sink is not None:
                seam.sink.token(rid)
            super().token(rid, t, tok)

        def engine_span(self, name, t0, t1, args=None, track="engine"):
            super().engine_span(name, t0, t1, args, track=track)
            if seam.sink is not None:
                seam.sink.program(name, args)
    return StampingRecorder


@contextmanager
def stamped():
    """Install the stamping recorder in the engine module for a run;
    yields the run's ``Seam``."""
    import repro.serving.engine as engine_mod
    saved = engine_mod.TraceRecorder
    seam = Seam()
    engine_mod.TraceRecorder = _recorder(saved, seam)
    try:
        yield seam
    finally:
        engine_mod.TraceRecorder = saved


@dataclass
class Work:
    """Operations and bytes the programs of the traced slices needed."""
    prefill_flops: int = 0
    decode_flops: int = 0
    decode_bytes: int = 0
    per_program: Dict[str, int] = field(default_factory=dict)


def work(events: Sequence[tuple], ranges: Sequence[Tuple[int, int]],
         prompt_lens: Sequence[int], family: ModuleType, m) -> Work:
    """Work of the programs whose events lie in ``ranges`` (half-open
    index ranges into ``events``, one per traced slice), by the counts of
    ``family`` at its shapes ``m``.

    A decode block's participants are the requests whose tokens follow
    it before the next program; request ``r`` with ``m0`` tokens before
    the block and ``q`` in it attends over ``P + m0 + j`` positions at
    its step ``j``."""
    w = Work()
    inside = set()
    for a, b in ranges:
        inside.update(range(a, b))
    emitted: Dict[int, int] = {}
    i = 0
    while i < len(events):
        ev = events[i]
        if ev[0] == "token":
            emitted[ev[2]] = emitted.get(ev[2], 0) + 1
            i += 1
            continue
        name, args = ev[2], ev[3]
        j = i + 1
        block: Dict[int, int] = {}
        while j < len(events) and events[j][0] == "token":
            block[events[j][2]] = block.get(events[j][2], 0) + 1
            j += 1
        if i in inside and name == PREFILL:
            rid = args["rid"]
            s, e = args["tokens"]
            w.prefill_flops += family.prefill_flops(
                m, s, e, last=e == prompt_lens[rid])
        elif i in inside and name == DECODE:
            ctx = {r: prompt_lens[r] + emitted.get(r, 0) for r in block}
            for step in range(max(block.values(), default=0)):
                live = [ctx[r] + step for r, q in block.items() if q > step]
                w.decode_bytes += family.decode_step_bytes(m, live)
                w.decode_flops += sum(family.decode_flops(m, c) for c in live)
        w.per_program[name] = w.per_program.get(name, 0) + (i in inside)
        for r, q in block.items():
            emitted[r] = emitted.get(r, 0) + q
        i = j
    return w
