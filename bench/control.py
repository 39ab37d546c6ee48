#!/usr/bin/env python3
"""Readings of the correctness check under sound runs, the control and
planted faults, on the chip at a cell's own size, over several seeds.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 \\
        --plants sound,control,half_batch,token_altered,state_unchanged

An ingest cell runs whole (set-up, a window of ``--seconds``, the check)
under each plant.  A training cell needs no window: for each seed it runs
set-up (which drives the first steps) under each plant, and the reference
once; the control is the reference computed in fp8.  One JSON line per
seed and plant: the numbers compared and whether the check passed.  The
limits of ``PERF.md`` were set from these readings.
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


def train_readings(cell, seed, names, devices, device_info):
    driver = harness.load_module(harness.bench_file(cell.root, "drivers", "train"),
                                 "driver_train")
    out = {}
    fed = None
    for name in names:
        if name == "control":
            continue
        work = tempfile.mkdtemp(prefix="bench_")
        ctx = harness.Context(cell, seed, 0.0, False, work, devices,
                              harness.Spans(), device_info)
        try:
            if name == "sound":
                st = driver.setup(ctx)
            else:
                with plants.plant("train", name):
                    st = driver.setup(ctx)
        finally:
            shutil.rmtree(work, ignore_errors=True)
        out[name] = st["prog"]
        fed = fed or (st["feed"], st["fed"], st["words"])
        del st
        gc.collect()
    feed, raw, words = fed
    rows = driver.reference_rows(feed, seed, int(cell.traffic["fill_shards"]))
    missing, batches = driver.check_fed(rows, raw)
    assert missing == 0, f"{missing} fed rows are not corpus rows"
    want = driver.run_reference(cell.config, words, batches)
    res = {n: driver.compare(cell.config, p, want) for n, p in out.items()}
    if "sound" in out:
        # which leaf each gap comes from, for the record
        import jax
        from reference import decoder
        paths = [jax.tree_util.keystr(k) for k, _ in jax.tree_util.tree_flatten_with_path(
            decoder.param_shapes(cell.config),
            is_leaf=lambda x: isinstance(x, tuple) and len(x) == 3)[0]]
        p = out["sound"]
        res["sound_leaves"] = {"losses": list(p["losses"]),
            "leaf": paths,
            "grad_prog": p["grad_norms"].tolist(), "grad_ref": want["grad_norms"].tolist(),
            "change_prog": p["change_norms"].tolist(),
            "change_ref": want["change_norms"].tolist()}
    if "control" in names:
        from reference import decoder
        low = driver.run_reference(cell.config, words, batches, quantize=decoder.fp8)
        res["control"] = driver.compare(cell.config, low, want)
        res["control_leaves"] = {
            "grad": low["grad_norms"].tolist(), "change": low["change_norms"].tolist(),
            "losses": list(low["losses"])}
    res["reference_losses"] = {"losses": want["losses"],
                               "grad": want["grad_norms"].tolist(),
                               "change": want["change_norms"].tolist()}
    return res


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--plants", default="sound,control")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args()
    harness.prepare()
    cell = harness.load_cell(args.workload)
    device_info = harness.require_devices(cell.chips)
    harness.enable_compile_cache()
    import jax
    devices = jax.devices()[:cell.chips]
    names = args.plants.split(",")
    for seed in (int(s) for s in args.seeds.split(",")):
        if cell.traffic["driver"] == "train":
            res = train_readings(cell, seed, names, devices, device_info)
            for name, r in res.items():
                print(json.dumps({"seed": seed, "plant": name, **r}), flush=True)
            continue
        for name in names:
            if name == "sound":
                r = harness.run_cell(cell, seed, args.seconds, False,
                                     device_info, devices)
            else:
                with plants.plant(cell.traffic["driver"], name):
                    r = harness.run_cell(cell, seed, args.seconds, False,
                                         device_info, devices)
            print(json.dumps({"seed": seed, "plant": name,
                              "correct": r["correct"], "checks": r["checks"]}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
