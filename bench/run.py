#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload han_imdb.full --seed 7 --seconds 10 --trace 0

The cell, its configuration, traffic, metrics and limits are read from
``BENCHMARK.json`` and the files it names.  Prints the run's notes
(set-up, window, recompiles after warm-up, generator lateness), then each
compared number beside its limit on standard error, then one JSON line:
``{"correct", "attempted", "failed", "metrics", "device"[, "breakdown"],
"checks"}``.  ``--trace 0`` reports the cell's end-to-end metrics, ``--trace
1`` its per-layer metrics from a profiler trace of the window.  Exits
non-zero with no result line when JAX finds no TPU, fewer chips than the
cell asks for, or no program to run.
"""
from __future__ import annotations

import time

T_PROC0 = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import harness

    return harness.main(args, T_PROC0)


if __name__ == "__main__":
    sys.exit(main())
