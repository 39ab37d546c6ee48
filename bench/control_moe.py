#!/usr/bin/env python3
"""Readings of the ``train_moe`` driver's correctness check under sound
runs, planted faults and the control, on the chip at the cell's own size.

    python3 bench/control_moe.py --workload moonlight_train.fed8k \\
        --seeds 1,2,3 --plants half_batch

For each seed: set-up (which fills the store and drives the first steps
of the program), sound and under each of ``bench/plants.py``'s training
plants named; the reference over the same rows from the same initial
weights; and the control -- the reference computed with fp8 (e4m3)
operands in every bf16 matrix product (``reference/decoder.fp8``), in the
program's place.  One JSON line per seed and reading: the numbers
compared and whether they pass the configuration's limits; then each
leaf's norms for the record.  The limits were set from these readings.
"""
import argparse
import gc
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402
import plants  # noqa: E402


def readings(cell, driver, seed, names, devices, device_info):
    from contextlib import nullcontext
    from reference import decoder
    progs, sound = {}, None
    for name in ("sound",) + tuple(names):
        work = tempfile.mkdtemp(prefix="bench_")
        ctx = harness.Context(cell, seed, 0.0, False, work, devices,
                              harness.Spans(), device_info)
        try:
            with nullcontext() if name == "sound" else plants.plant("train", name):
                st = driver.setup(ctx)
        finally:
            shutil.rmtree(work, ignore_errors=True)
        progs[name] = st["prog"]
        if name == "sound":
            sound = (st["fed"], st["start"], st["feed"])
            harness.log(f"[readings] memory_peak_bytes after the sound set-up: "
                        f"{harness.memory_peak_bytes(devices)}")
        del st
        gc.collect()
    raw, start, feed = sound
    rows = driver.fed.reference_rows(feed, seed, int(cell.traffic["fill_shards"]))
    missing, batches = driver.fed.check_fed(rows, raw)
    assert missing == 0, f"{missing} fed rows are not corpus rows"
    want = driver.run_reference(cell.config, start, batches)
    low = driver.run_reference(cell.config, start, batches, quantize=decoder.fp8)
    out = {name: driver.compare(cell.config, p, want) for name, p in progs.items()}
    out["control"] = driver.compare(cell.config, low, want)
    prog = progs["sound"]
    out["leaves"] = {"losses": {"program": prog["losses"], "reference": want["losses"],
                                "control": low["losses"]},
                     "grad_prog": prog["grad_norms"].tolist(),
                     "grad_ref": want["grad_norms"].tolist(),
                     "change_prog": prog["change_norms"].tolist(),
                     "change_ref": want["change_norms"].tolist()}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--plants", default="",
                    help="comma-separated plants, besides sound and the control")
    args = ap.parse_args()
    harness.prepare()
    cell = harness.load_cell(args.workload)
    driver = harness.load_module(harness.bench_file(cell.root, "drivers", "train_moe"),
                                 "driver_train_moe")
    device_info = harness.require_devices(cell.chips)
    harness.enable_compile_cache()
    import jax
    devices = jax.devices()[:cell.chips]
    limits = cell.config["limits"]
    names = [n for n in args.plants.split(",") if n]
    for seed in (int(s) for s in args.seeds.split(",")):
        res = readings(cell, driver, seed, names, devices, device_info)
        leaves = res.pop("leaves")
        for name, r in res.items():
            print(json.dumps({"seed": seed, "reading": name, **r,
                              "passes": all(v <= limits[k] for k, v in r.items())}),
                  flush=True)
        print(json.dumps({"seed": seed, "reading": "leaves", **leaves}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
