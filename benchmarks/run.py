"""Run every benchmark (one per paper table/figure) and print
``name,us_per_call,derived`` CSV.

  python -m benchmarks.run [--scale N] [--only cleaning,sampling,...]
"""
from __future__ import annotations

import argparse
import sys
import time


MODULES = ["cleaning", "sampling", "layouts", "storage", "cooking",
           "access", "recovery", "streaming"]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=200_000,
                    help="rows of TPC-H lineitem-like data per bench")
    ap.add_argument("--only", type=str, default=None)
    args = ap.parse_args()
    only = args.only.split(",") if args.only else MODULES

    import importlib

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    failures = 0
    for mod in MODULES:
        if mod not in only:
            continue
        t0 = time.time()
        try:
            m = importlib.import_module(f"benchmarks.bench_{mod}")
            for name, secs, derived in m.run(args.scale):
                print(f"{name},{secs * 1e6:.1f},{derived}", flush=True)
        except Exception as e:  # keep the harness going; report at the end
            failures += 1
            print(f"{mod}/ERROR,0,{type(e).__name__}: {e}", flush=True)
        print(f"# {mod} done in {time.time() - t0:.1f}s", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
