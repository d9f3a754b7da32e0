"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. ``us_per_call`` times the analytical
evaluation itself (the paper's artifact is the model, so its evaluation cost
is the honest per-call number); ``derived`` carries the reproduced claim.

Run: PYTHONPATH=src python -m benchmarks.run [--only fig1,table1]

A module that raises is reported as an ``ERROR:`` row (with its traceback
on stderr) and the run exits non-zero after the remaining modules.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from benchmarks import common

MODULES = ("table1", "fig1", "fig2", "fig3", "fig4",
           "beyond_tpu_tiers", "roofline_tpu")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="comma-separated module names")
    args = ap.parse_args()
    only = set(filter(None, args.only.split(",")))

    failed = []
    for name in MODULES:
        if only and name not in only:
            continue
        mod = __import__(f"benchmarks.{name}", fromlist=["run"])
        t0 = time.perf_counter()
        try:
            derived = mod.run(common.emit)
        except Exception as e:  # report it, run the rest, then fail
            traceback.print_exc()
            derived = f"ERROR:{type(e).__name__}:{e}"
            failed.append(name)
        us = (time.perf_counter() - t0) * 1e6
        common.emit(f"{name}.total", us, derived)
    common.flush()
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
