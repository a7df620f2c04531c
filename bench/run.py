#!/usr/bin/env python3
"""Run one benchmark cell once on the machine's accelerator.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up in ``BENCHMARK.json``; its configuration, traffic mix
and metric readers are files under ``bench/`` found by name. Set-up (weights
drawn on the device from the seed, the cell's shapes compiled or read from
the compile cache in ``<checkout>/.jax_cache``, long contexts prefilled)
is timed as ``setup_s``; then the serving loop serves the mix for ``--seconds``.
With ``--trace 0`` the line holds the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read from a profiler trace of a steady
stretch of the window and from the serving loop's host spans. After the window,
a sample of the served requests is compared with the plain reference; the
numbers compared, each with its limit, are the last lines on stderr and
the last key of the result.

Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result: it never falls back to the CPU.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Optional  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[1]
CACHE_DIR = CHECKOUT / ".jax_cache"
TRACE_DIR = CHECKOUT / ".bench_trace"


def now() -> float:
    return time.perf_counter() - T_START


def run_cell(w: dict, cfg: dict, mix: dict, e2e: list, per_layer: list,
             seed: int, seconds: float, trace: bool, peaks: dict,
             trace_dir: Path = TRACE_DIR, control: Optional[bool] = None) -> dict:
    """Build, warm, drive and check one cell; returns the result object.
    Takes the cell's data as dicts, so a test can drive a tiny cell on the
    CPU as a function."""
    import jax
    import numpy as np

    from bench import check, harness, traffic
    from bench import trace as trace_lib
    from bench import weights
    from repro.config import ServeConfig
    from repro.core import engine as engine_lib
    from repro.models import model

    clock = harness.CompileClock(jax.monitoring, now)
    tcfg = harness.model_config(cfg)
    dcfg = harness.draft_model_config(cfg, tcfg)
    slots = int(mix["slots"])
    serve_cfg = ServeConfig(
        max_batch=slots, max_new_tokens=int(mix["output"].get("fixed", 0)) or 1,
        temperature=0.0, max_context=int(mix["max_context"]),
        ssv=harness.strategy(cfg), use_planner=False, kv_backend="paged",
        kv_page_size=int(cfg["nsa"]["sel_block"]))
    key0 = jax.random.PRNGKey(0)
    tp = weights.make_params(jax.eval_shape(lambda: model.init(key0, tcfg)),
                             seed, salt=1)
    dp = weights.make_params(jax.eval_shape(lambda: model.init(key0, dcfg)),
                             seed, salt=2)
    eng = engine_lib.BatchedSSVEngine(tp, tcfg, dp, dcfg, serve_cfg)
    eng.start_empty(slots)
    reqs = traffic.generate(mix, seed, tcfg.vocab_size)

    closed = mix["loop"] == "closed"
    if not closed:
        # warm every prompt bucket's admission, then one step of them
        warm = harness.ServeLoop(eng, [
            traffic.BenchRequest(req_id=-1 - i, prompt=np.full(b, 1, np.int32),
                                 max_new_tokens=1, due=0.0)
            for i, b in enumerate(traffic.prompt_buckets(mix))], mix, now)
        warm.serve(until=float("inf"))
        jax.block_until_ready((eng.t_segs, eng.d_segs))
        eng.start_empty(slots)
    drv = harness.ServeLoop(eng, reqs, mix, now)
    if closed:
        # every slot's session is prefilled in set-up; one step compiles
        # (or loads) the fused step and emits each session's first token
        drv.origin = now()
        drv.admit_arrived()
        drv.step(drv.sched.decoding_mask())
        w0 = now()
    else:
        drv.origin = now()
        w0 = drv.origin + float(mix["lead_s"])
    deadline = w0 + seconds
    profile = None
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        start = w0 + 0.25 * seconds
        profile = (start, start + min(4.0, 0.5 * seconds), trace_dir)
    # phase 1: the window
    drv.serve(until=deadline, profile=profile)
    if closed:
        w1 = max([s.t1 for s in drv.steps] + [deadline])
        window = (w0, w1)
    else:
        window = (w0, deadline)
        due = [r for r in reqs if w0 <= drv.origin + r.due < deadline]
        drv.serve(until=deadline + float(mix["drain_s"]),
                  done=lambda: all(r.first_token is not None for r in due),
                  profile=profile)
    compiles = clock.compiles_between(window[0], window[1])
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    # free the program's state before the reference runs
    eng.t_segs = eng.d_segs = None
    del eng, drv.eng
    gc.collect()

    rec = harness.RunRecord(cfg=cfg, mix=mix, peaks=peaks, window=window,
                            steps=drv.steps, requests=reqs,
                            origin=drv.origin, compiles_in_window=compiles)
    verdict = check.judge(reqs, tp, cfg, mix, seed, control=bool(control))
    out = {"correct": verdict["correct"]}
    # attempted: the sessions held in the window (closed loop), or the
    # requests due in it (open loop); failed: those of them that got no
    # token by the end of the window (closed) or of the drain (open)
    if closed:
        held = [r for r in reqs
                if r.admit_start is not None and r.admit_start <= window[1]
                and not (r.done and r.emissions[-1][0] <= window[0])]
        out["failed"] = sum(1 for r in held if not any(
            window[0] < t <= window[1] for t, _ in r.emissions))
    else:
        held = rec.due_in_window()
        out["failed"] = sum(1 for r in held if r.first_token is None)
    out["attempted"] = len(held)
    if trace and drv.profile and len(drv.profile) == 2:
        rec.trace = trace_lib.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if rec.trace:
            device["busy_s"] = rec.trace["busy_s"]
            device["window_s"] = rec.trace["window_s"]
    metrics = {}
    for m in (per_layer if trace else e2e):
        if "workloads" in m and w["name"] not in m["workloads"]:
            continue
        value = harness.load_reader(m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out["metrics"] = metrics
    out["device"] = device
    if trace and rec.trace:
        out["breakdown"] = rec.trace["breakdown"]
    if control is not None:
        out["readings"] = verdict["readings"]
    if control:
        out["control"] = verdict["control"]
    out["checks"] = verdict["checks"]
    return out


def start_jax(who: str):
    """JAX on this machine's TPU, with the program's persistent compile
    cache (``repro.compile_cache``) given the checkout's fixed
    ``.jax_cache``; returns the devices, or None (with the reason on
    stderr) where there is no TPU or the program is not in the checkout.
    The cache directory is set before JAX is imported, which reads it."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"{who}: JAX found no accelerator: {e}", file=sys.stderr)
        return None
    if devices[0].platform != "tpu":
        print(f"{who}: no TPU found (JAX platform {devices[0].platform!r}); "
              "the benchmark never falls back to the CPU", file=sys.stderr)
        return None
    sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]
    try:
        from repro.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"{who}: the program is not in this checkout: {e}",
              file=sys.stderr)
        return None
    enable_compile_cache()
    return devices


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    devices = start_jax("bench")
    if devices is None:
        return 2
    from bench import harness
    bm = harness.load_benchmark()
    w = harness.workload(bm, args.workload)
    if len(devices) < int(w["chips"]):
        print(f"bench: {args.workload} needs {w['chips']} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2
    from bench import traffic
    cfg = harness.load_config(w["config"])
    mix = traffic.load_mix(w["traffic"])
    peaks = harness.load_peaks(devices[0].device_kind)
    out = run_cell(w, cfg, mix, bm["end_to_end"], bm["per_layer"],
                   args.seed, args.seconds, bool(args.trace), peaks)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct: {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
