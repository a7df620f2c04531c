"""Benchmark runner: one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows.

  PYTHONPATH=src python -m benchmarks.run                   # everything
  PYTHONPATH=src python -m benchmarks.run --list            # valid suite names
  PYTHONPATH=src python -m benchmarks.run --only e2e        # one suite
  PYTHONPATH=src python -m benchmarks.run --only e2e,kernel # several suites
  PYTHONPATH=src python -m benchmarks.run --quick           # CPU-sized shapes,
                                                            # seconds not minutes
"""
import argparse
import inspect
import sys
import time
import traceback

SUITES = [
    ("overlap", "benchmarks.overlap_profile"),       # Fig. 2 / Fig. 4
    ("kernel", "benchmarks.kernel_breakdown"),       # Fig. 10
    ("verification", "benchmarks.verification"),     # Fig. 9 / Fig. 7
    ("e2e", "benchmarks.e2e_spec"),                  # Fig. 8
    ("quality", "benchmarks.quality_proxy"),         # Table 1
    ("planner", "benchmarks.planner_eval"),          # Table 3
    ("refinement", "benchmarks.refinement_sweep"),   # Table 4
    ("roofline", "benchmarks.roofline_report"),      # EXPERIMENTS §Roofline
]


def run_suite(modname: str, quick: bool) -> None:
    mod = __import__(modname, fromlist=["main"])
    kwargs = {}
    if quick and "quick" in inspect.signature(mod.main).parameters:
        kwargs["quick"] = True
    mod.main(**kwargs)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names, e.g. --only e2e or "
                         "--only e2e,kernel,quality")
    ap.add_argument("--quick", action="store_true",
                    help="tiny shapes/token counts so every suite finishes in "
                         "seconds — the tier-1 smoke-test mode")
    ap.add_argument("--list", action="store_true",
                    help="print the valid suite names (one per line) and exit")
    args = ap.parse_args(argv)
    if args.list:
        for name, _ in SUITES:
            print(name)
        return
    only = None
    if args.only:
        only = [s.strip() for s in args.only.split(",") if s.strip()]
        valid = {n for n, _ in SUITES}
        unknown = [s for s in only if s not in valid]
        if unknown or not only:
            bad = ", ".join(repr(s) for s in unknown) or repr(args.only)
            ap.error(f"unknown suite {bad}; choose from "
                     f"{', '.join(n for n, _ in SUITES)} "
                     "(comma-separate for several, e.g. --only e2e,kernel)")
    print("name,us_per_call,derived")
    failures = 0
    for name, modname in SUITES:
        if only is not None and name not in only:
            continue
        t0 = time.time()
        try:
            run_suite(modname, args.quick)
            print(f"# {name} done in {time.time() - t0:.0f}s", flush=True)
        except Exception:
            failures += 1
            print(f"# {name} FAILED:", flush=True)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
