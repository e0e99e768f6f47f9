#!/usr/bin/env python3
"""Readings for a cell's limits: on each seed, one short run of the cell
at its own load, then the program's error and the control's against the
float64 reference, all in one process (set-up compiles once).

    python3 bench/control.py --workload han_imdb.full --seeds 1,2,3 --seconds 3

The controls are the reference with lower-precision matrix products (three
bf16 passes, XLA's ``high``; one bf16 pass), on the chip, over the same
compared rows.  Prints one JSON line per seed: ``{"seed", "logit_rel_err",
"control_rel_err": {precision: error}, "correct", ...}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from bench import harness
    from repro.launch.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 3
    enable_compile_cache()
    cell = harness.find_cell(args.workload)
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        out = harness._finite(harness.run_cell(cell, seed, args.seconds,
                                               False, t0, control=True))
        notes = out["_notes"]
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "logit_rel_err": out["checks"]["logit_rel_err"]["value"],
            "control_rel_err": notes["control_rel_err"],
            "correct": out["correct"], "compared": notes["compared"],
            "attempted": out["attempted"], "failed": out["failed"],
            "recompiles": notes["recompiles_after_warmup"],
            "run_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
