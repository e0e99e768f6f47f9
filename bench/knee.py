#!/usr/bin/env python3
"""Sweep the open-loop rate of a serving cell once, to find its knee: the
highest rate whose backlog does not grow over the window.

    python3 bench/knee.py --workload han_imdb.serve_zipf --rates 200,400,800 --seconds 8

One process, one set-up; per rate one window of the cell's own traffic at
that rate.  Prints one JSON line per rate with the latency quartiles of
the first and the last quarter of the window's requests (a growing backlog
shows as a last quarter far slower than the first) and the answered rate.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from bench import harness
    from repro.launch.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("knee: no TPU", file=sys.stderr)
        return 3
    enable_compile_cache()
    cell = harness.find_cell(args.workload)
    st = harness.set_up(cell, args.seed, harness.Spans(False))
    for rate in [float(r) for r in args.rates.split(",")]:
        spec = {**cell["traffic"], "rate_per_s": rate}
        win = harness.window_open(st, spec, args.seconds, args.seed,
                                  harness.Spans(False))
        lat = np.asarray([1e3 * (r.t_done - r.due) for r in win["reqs"]])
        q = max(1, len(lat) // 4)
        t_last = max(r.t_done for r in win["reqs"]) - min(
            r.due for r in win["reqs"])
        print(json.dumps({
            "rate_per_s": rate, "requests": len(lat),
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "first_quarter_p50_ms": float(np.median(lat[:q])),
            "last_quarter_p50_ms": float(np.median(lat[-q:])),
            "answered_per_s": len(win["ok"]) / t_last,
            "steps": len(win["steps"]), "failed": win["failed"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
