"""Runs one cell of the benchmark once: set-up, the measured window, the
check, and the result line.

Everything that belongs to one configuration, traffic mix, cell or
metric is a file of its own, found by the names in ``BENCHMARK.json``:

* ``configs/<config>.json``: the model as run (published keys), with
  the ``family`` whose module (``families/<family>.py``) gives its
  shapes, engine config, weights, reference and counts;
* ``traffic/<traffic>.json``: the mix, read by ``traffic.py``;
* ``cells/<workload>.json``: the engine's sizes for that pair, the
  check's sample size and its limit;
* ``metrics/<metric>.py``: a reader ``read(run) -> float | None`` of one
  end-to-end or per-layer metric (``None``: nothing to read here, and
  the metric is left out of the line).

So a later change adds a family, a configuration, a mix, a cell or a
metric as new files and an entry in ``BENCHMARK.json``, and edits
nothing here. A cell's ``chips`` is the engine's head-shard count: the
weights are replicated over the chips and the KV pool split over its KV
heads.

A run: make the weights from the seed on the device, build the engine,
warm every program the cell's traffic drives, then serve waves back to
back (a wave is ``wave`` requests handed to one ``serve_continuous``
call; the next wave goes when it returns) until ``--seconds`` have
passed; the window ends when the last wave that started inside it
returns. ``--trace 1`` serves one wave instead, with the profiler on in
slices, and reports the per-layer metrics. Then the engine is freed and
the served tokens of a sample are checked against the reference.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import pathlib
import shutil
import sys
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from benchmarks.chip import check, families, spans, trace_reduce, traffic

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
PROGRAMS = ("prefill_paged_chunk", "decode_steps_paged")
TRACE_DIR = ROOT / ".bench_trace"   # emptied by each traced run
SLICE_S = 1.0          # traced seconds per slice
SLICE_GAP_S = 20.0     # untraced seconds between the end of a slice and
                       # the start of the next


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict            # configs/<config>.json
    mix: dict               # traffic/<traffic>.json
    sizes: dict             # cells/<workload>.json
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def family(self):
        return families.of(self.config)


def load_cell(name: str, bench_file=ROOT / "BENCHMARK.json") -> Cell:
    bench = load_json(bench_file)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(by_name)}")
    wl = by_name[name]
    cfg_file = {c["name"]: c["file"] for c in bench["configs"]}[wl["config"]]

    def applies(metric):
        return name in metric.get("workloads", [name])
    return Cell(name=name, chips=wl["chips"],
                config=load_json(ROOT / cfg_file),
                mix=load_json(HERE / "traffic" / f"{wl['traffic']}.json"),
                sizes=load_json(HERE / "cells" / f"{name}.json"),
                end_to_end=[m for m in bench["end_to_end"] if applies(m)],
                per_layer=[m for m in bench["per_layer"] if applies(m)])


def reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"_bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Compiles:
    """Backend compiles and persistent-cache loads seen by this process."""

    def __init__(self):
        import jax.monitoring as mon
        self.compiles = 0
        self.cache_loads = 0

        def on_duration(event, duration, *args, **kwargs):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def on_event(event, *args, **kwargs):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_loads += 1
        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)

    def snapshot(self):
        return self.compiles, self.cache_loads


@dataclass
class WaveRecord:
    t_issue: float
    t_end: float
    prompts: List[List[int]]
    max_new: int
    outs: List[List[int]]
    tokens: Dict[int, List[float]]      # rid -> host stamps
    events: List[tuple]


@dataclass
class Run:
    """What the metric readers read."""
    cell: Cell
    dims: object            # the family's shapes (``family.dims``)
    peak: dict              # one chip's
    setup_s: float = 0.0
    window_s: float = 0.0
    waves: List[WaveRecord] = field(default_factory=list)
    counters: Dict[str, int] = field(default_factory=dict)
    reduced: Optional[trace_reduce.Reduced] = None
    work: Optional[spans.Work] = None


COUNTERS = ("new_tokens", "requests", "prefill_tokens_computed",
            "cached_prefix_tokens", "host_syncs", "decode_steps",
            "preemptions", "cow_copies")


def weight_sharding(cell: Cell):
    """Where the weights go: ``None`` (the default device) on one chip;
    on more, replicated over the mesh the engine builds for
    ``shards=chips`` (an equal mesh, so the engine's own placement of
    the weights finds them in place)."""
    if cell.chips == 1:
        return None
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.kernels.sharded import AXIS
    from repro.sharding.rules import auto_mesh
    mesh = auto_mesh((cell.chips,), (AXIS,),
                     devices=jax.devices()[:cell.chips])
    return NamedSharding(mesh, PartitionSpec())


def build_engine(cell: Cell, params):
    from repro.models import RuntimeOptions
    from repro.serving.engine import ServeEngine
    s = cell.sizes
    return ServeEngine(cell.family.arch_config(cell.config), params=params,
                       opts=RuntimeOptions(dtype="bfloat16"),
                       scheduler="continuous",
                       max_batch=s["max_batch"], max_len=s["max_len"],
                       n_pages=s["n_pages"],
                       prefill_budget=s.get("prefill_budget"),
                       shards=cell.chips)


def serve(eng, seam: spans.Seam, prompts, max_new, sink: spans.Sink):
    """The wave's outputs, and where its KV pool lay: (device id, shape)
    of each chip's part of the k pool."""
    with seam.collecting(sink):
        outs = eng.serve_continuous(prompts, max_new)
    layout = sorted((s.device.id, s.data.shape)
                    for s in eng.pool["stack"]["k"].addressable_shards)
    eng.pool = None         # the next call builds its own pool
    return outs, layout


def warm_up(eng, seam: spans.Seam, cell: Cell, seed: int) -> None:
    """Compile every program the window drives, on prompts from a stream
    the measured waves never use: the prefill chunk, the fused decode
    block at each step count a block can take (8, 4, 2, 1 with the
    default lookahead of 8), and the copy-on-write page copies at each
    padded batch size up to twice the slots, on a pool placed as the
    engine places it."""
    import jax
    import jax.numpy as jnp
    vocab = cell.config["vocab_size"]
    C, B = eng.prefill_chunk, eng.max_batch
    k = eng.decode_lookahead
    steps = sorted({min(k, 1 << i) for i in range(k.bit_length() + 1)})
    rng = traffic.rng_for(seed, traffic.WARMUP)
    shared = rng.integers(1, vocab, C + C // 2).tolist()
    for n in reversed(steps):
        prompts = [shared + rng.integers(1, vocab, 3).tolist()
                   for _ in range(B)]
        serve(eng, seam, prompts, n + 1, spans.Sink())
    from repro.models import init_paged_cache
    pool = init_paged_cache(eng.cfg, eng.n_pages, eng.page_size, eng.opts)
    if eng.mesh is not None:
        from jax.sharding import NamedSharding

        from repro.sharding import rules
        pool = jax.device_put(pool, jax.tree_util.tree_map(
            lambda s: NamedSharding(eng.mesh, s),
            rules.paged_cache_pspecs(pool, eng.mesh)))
    n = 1
    while n <= 2 * B:
        # built as the engine builds them: a list of (src, dst) pairs,
        # whose conversion compiles once per length
        pool = eng._copy_pages(pool, jnp.asarray([(0, 0)] * n, jnp.int32))
        n *= 2
    del pool


class Slicer:
    """Turns the profiler on and off between programs of the traced wave:
    a slice of ``SLICE_S`` starts ``SLICE_GAP_S`` after the last ended
    (starting and stopping the profiler stalls the host for about two
    seconds, so the gap keeps that stall near a tenth of the traced
    wave), the
    first at the wave's first program; until a decode block has been
    traced a slice stays open for the next one, so that every traced run
    reads both programs. The engine syncs with the host after each
    program, so a slice holds whole programs only."""

    def __init__(self, log_dir: pathlib.Path):
        import jax
        self.jax = jax
        self.log_dir = log_dir
        self.ranges: List[tuple] = []
        self.open_at: Optional[int] = None
        self.t_open = self.t_closed = 0.0
        self.ann = None
        self.traced_decode = False

    def __call__(self, sink: spans.Sink) -> None:
        now = time.perf_counter()
        if self.open_at is None:
            if not self.ranges or now - self.t_closed >= SLICE_GAP_S:
                self.start(sink)
            return
        self.traced_decode |= sink.events[-1][2] == spans.DECODE
        if now - self.t_open >= SLICE_S and self.traced_decode:
            self.stop(sink)

    def start(self, sink: spans.Sink) -> None:
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        self.jax.profiler.start_trace(
            str(self.log_dir / f"slice{len(self.ranges)}"),
            profiler_options=opts)
        self.ann = self.jax.profiler.TraceAnnotation(trace_reduce.SLICE)
        self.ann.__enter__()
        self.open_at = len(sink.events)
        self.t_open = time.perf_counter()

    def stop(self, sink: spans.Sink) -> None:
        if self.open_at is None:
            return
        self.ann.__exit__(None, None, None)
        self.jax.profiler.stop_trace()
        self.ranges.append((self.open_at, len(sink.events)))
        self.open_at = None
        self.t_closed = time.perf_counter()


def _counters(eng) -> Dict[str, int]:
    return {k: getattr(eng.stats, k) for k in COUNTERS}


def _delta(after, before):
    return {k: after[k] - before[k] for k in after}


def measure(cell: Cell, seed: int, seconds: float, trace: bool,
            t_start: float, peak: dict, devices=(), compiles=None) -> dict:
    """One run of a cell; returns the result line's fields. ``devices``:
    the cell's chips, whose fullest is ``memory_peak_bytes``."""
    import jax
    fam = cell.family
    dims = fam.dims(cell.config)
    run = Run(cell=cell, dims=dims, peak=peak)
    params = fam.served_params(dims, seed, sharding=weight_sharding(cell))
    jax.block_until_ready(params)
    with spans.stamped() as seam:
        eng = build_engine(cell, params)
        del params
        warm_up(eng, seam, cell, seed)
        jax.effects_barrier()
        before = _counters(eng)
        c0 = compiles.snapshot() if compiles else (0, 0)
        t_win = time.perf_counter()
        run.setup_s = t_win - t_start
        slicer = None
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            slicer = Slicer(TRACE_DIR)
        index = 0
        while True:
            if index and (trace or time.perf_counter() - t_win >= seconds):
                break
            w = traffic.wave(cell.mix, cell.config["vocab_size"], seed, index)
            sink = spans.Sink(on_program=slicer)
            t_issue = time.perf_counter()
            outs, layout = serve(eng, seam, w.prompts, w.max_new_tokens,
                                 sink)
            t_end = time.perf_counter()
            if not index:
                say(f"pool_shards={layout}")
            if slicer is not None:
                slicer.stop(sink)
            run.waves.append(WaveRecord(t_issue, t_end, w.prompts,
                                        w.max_new_tokens, outs, sink.tokens,
                                        sink.events))
            index += 1
        run.window_s = run.waves[-1].t_end - t_win
        run.counters = _delta(_counters(eng), before)
        c1 = compiles.snapshot() if compiles else (0, 0)
        say(f"waves={len(run.waves)} window_s={run.window_s!r} "
            f"setup_s={run.setup_s!r} compiles_in_window={c1[0] - c0[0]} "
            f"cache_loads_in_window={c1[1] - c0[1]} "
            f"counters={json.dumps(run.counters)}")
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    mem_peak = max(peaks) if peaks and None not in peaks else None
    eng.params = eng.pool = None        # free the device before the check
    del eng
    gc.collect()
    if trace:
        wave0 = run.waves[0]
        run.reduced = trace_reduce.reduce(
            [trace_reduce.read(p) for p in trace_reduce.find(str(TRACE_DIR))],
            PROGRAMS)
        run.work = spans.work(wave0.events, slicer.ranges,
                              [len(p) for p in wave0.prompts], fam, dims)
        say(f"slices={len(slicer.ranges)} window_s={run.reduced.window_s!r} "
            f"busy_s={run.reduced.busy_s!r} "
            f"program_s={json.dumps(run.reduced.program_s)} "
            f"program_runs_trace={json.dumps(run.reduced.program_runs)} "
            f"collective_ops={json.dumps(run.reduced.collective_ops)} "
            f"programs_noted={json.dumps(run.work.per_program)}")
    return finish(run, seed, trace, mem_peak)


def finish(run: Run, seed: int, trace: bool, mem_peak) -> dict:
    """Metrics, the check and the result line's fields."""
    cell = run.cell
    attempted = sum(len(w.prompts) for w in run.waves)
    done, failed = [], 0
    for w in run.waves:
        for p, o in zip(w.prompts, w.outs):
            if len(o) == w.max_new:
                done.append((p, o))
            else:
                failed += 1
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    chk = cell.sizes["check"]
    picked = check.sample(done, seed, chk["sample_tokens"])
    t0 = time.perf_counter()
    got = check.readings(cell.config, seed, [done[i] for i in picked],
                         pad_to=cell.family.padded_len(
                             cell.sizes["max_len"]))
    say(f"check: {len(picked)} requests, {got['tokens']} served tokens, "
        f"reference_s={time.perf_counter() - t0!r}")
    gap, limit = got["max_logit_gap"], chk["max_logit_gap"]
    out = {"correct": failed == 0 and gap <= limit, "attempted": attempted,
           "failed": failed, "metrics": metrics}
    out["device"] = {}
    if mem_peak is not None:
        out["device"]["memory_peak_bytes"] = mem_peak
    if trace and run.reduced is not None:
        out["device"]["busy_s"] = run.reduced.busy_s
        out["device"]["window_s"] = run.reduced.window_s
        out["breakdown"] = {"device_ops": run.reduced.device_ops,
                            "idle_gaps": run.reduced.idle_gaps}
    out["check"] = {"max_logit_gap": {"value": gap, "limit": limit},
                    "failed_requests": {"value": failed, "limit": 0}}
    return out


def main(argv, t_start: float) -> int:
    ap = argparse.ArgumentParser(
        description="Run one benchmark cell once on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="copy the traced run's profiles to DIR")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    cell = load_cell(args.workload)
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"benchmark: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"benchmark: {cell.name} needs {cell.chips} chips; JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    peaks = load_json(HERE / "peaks.json")
    if dev.device_kind not in peaks:
        print(f"benchmark: no peaks for device kind {dev.device_kind!r} in "
              f"peaks.json", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import use_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    say(f"device: {dev.platform} {dev.device_kind} x{len(devices)} "
        f"compile_cache={use_compile_cache()} "
        f"bytes_limit={(dev.memory_stats() or {}).get('bytes_limit')}")
    out = measure(cell, args.seed, args.seconds, bool(args.trace), t_start,
                  peaks[dev.device_kind], devices=devices[:cell.chips],
                  compiles=Compiles())
    if args.trace and args.keep_trace:
        shutil.copytree(TRACE_DIR, args.keep_trace, dirs_exist_ok=True)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    out["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(devices), **out["device"]}
    chk = out.pop("check")
    out["check"] = chk
    for k, v in chk.items():
        print(f"check {k}={v['value']!r} limit={v['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0
