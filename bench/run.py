#!/usr/bin/env python3
"""Run one benchmark cell once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout that holds ``src/repro`` and
``BENCHMARK.json``, on a machine with as many TPU chips as the cell asks
for.  The last line of standard output is the JSON result; the numbers the
correctness check compared, each beside its limit, are the last lines of
standard error.  See ``bench/harness.py``.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

if __name__ == "__main__":
    from harness import main
    sys.exit(main())
