#!/usr/bin/env python3
"""Readings that set a cell's limits (``check`` in its configuration
file): the program's gap readings and the float8 control's, seed by seed,
in one process.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 11 12 ... \
        [--control 3]

Each seed runs the cell as a benchmark run does (same window, same sample
of served requests); for the first ``--control`` seeds the float8 control
(``check.py``) is read on the same prompts and tokens. One JSON line per
seed; a TPU is required. The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(CHECKOUT))
    from bench import run as bench_run
    devices = bench_run.start_jax("control")
    if devices is None:
        return 2
    from bench import harness, traffic
    bm = harness.load_benchmark()
    w = harness.workload(bm, args.workload)
    cfg = harness.load_config(w["config"])
    mix = traffic.load_mix(w["traffic"])
    peaks = harness.load_peaks(devices[0].device_kind)
    for i, seed in enumerate(args.seeds):
        out = bench_run.run_cell(w, cfg, mix, bm["end_to_end"], [], seed,
                                 args.seconds, False, peaks,
                                 control=i < args.control)
        c = out["checks"]
        print(json.dumps({
            "seed": seed, "correct": out["correct"],
            "readings": out["readings"], "control": out.get("control"),
            "tokens": c["tokens_compared"]["value"],
            "attempted": out["attempted"], "failed": out["failed"],
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
            "memory_peak_bytes": out["device"]["memory_peak_bytes"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
