"""Readings of the check over many seeds, with the controls beside them:
how each cell's limit was set. Not part of a benchmark run.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3 \\
        [--fp8-seeds 1]

One process builds the engine once; for each seed it draws that seed's
weights, serves the cell's first wave, frees the pool, and reads
``max_logit_gap`` over the same sample a run checks. For the fp8 seeds
the reference computed in float8 is put in the engine's place on the
same positions (``control_gap``). One JSON line per seed, then a summary
line: the largest program reading (the lower end of a limit) and the
smallest control reading (the upper end).
"""
import os
import pathlib
import sys

_ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402

from benchmarks.chip import check, harness, spans, traffic  # noqa: E402


def _serve_sample(eng, seam, cell, seed, w):
    t0 = time.perf_counter()
    outs, _ = harness.serve(eng, seam, w.prompts, w.max_new_tokens,
                            spans.Sink())
    wave_s = time.perf_counter() - t0
    done = [(p, o) for p, o in zip(w.prompts, outs)
            if len(o) == w.max_new_tokens]
    picked = check.sample(done, seed, cell.sizes["check"]["sample_tokens"])
    return [done[i] for i in picked], wave_s, len(w.prompts) - len(done)


def readings(cell, seeds, fp8_seeds, log=print):
    import jax
    fam = cell.family
    m = fam.dims(cell.config)
    vocab = cell.config["vocab_size"]
    pad = fam.padded_len(cell.sizes["max_len"])
    sharding = harness.weight_sharding(cell)
    out = []
    with spans.stamped() as seam:
        eng = None
        for seed in seeds:
            params = fam.served_params(m, seed, sharding=sharding)
            if eng is None:
                eng = harness.build_engine(cell, params)
                harness.warm_up(eng, seam, cell, seed)
            w = traffic.wave(cell.mix, vocab, seed, 0)
            eng.params = params
            seqs, wave_s, failed = _serve_sample(eng, seam, cell, seed, w)
            eng.params = None
            del params
            gc.collect()
            jax.effects_barrier()
            r = check.readings(cell.config, seed, seqs, pad_to=pad,
                               control=seed in fp8_seeds)
            r.update(seed=seed, wave_s=wave_s, failed=failed,
                     requests=len(seqs))
            log(json.dumps(r))
            out.append(r)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fp8-seeds", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    fp8 = {int(s) for s in args.fp8_seeds.split(",") if s}
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    got = readings(harness.load_cell(args.workload), seeds, fp8)
    summary = {"workload": args.workload, "seeds": len(got),
               "lower": max(r["max_logit_gap"] for r in got)}
    vals = [r["control_gap"] for r in got if "control_gap" in r]
    if vals:
        summary["control_gap"] = {"min": min(vals), "readings": vals}
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
