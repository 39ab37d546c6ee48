#!/usr/bin/env python3
"""Readings of the ``train_kda`` driver's correctness check under sound
runs, planted faults and the control, on the chip at the cell's own size.

    python3 bench/control_kda.py --workload kimi_linear_train.fed8k \\
        --seeds 1,2,3 --plants half_batch,no_reset

``bench/control_moe.py``'s readings (sound, each plant named, and the fp8
control: the reference computed with fp8 operands in the program's place),
with one plant more: ``no_reset``, the chunked KDA given rows of one
segment, so that its state carries across the packed documents (the short
convolutions still stop at them).  One JSON line per seed and reading,
then each leaf's norms; and the compiled step's memory analysis once.
"""
import argparse
import json
import os
import sys
from contextlib import contextmanager

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import control_moe  # noqa: E402
import harness  # noqa: E402
import plants  # noqa: E402


@contextmanager
def no_reset():
    """Patch the program's chunked KDA to see one segment a row."""
    import jax.numpy as jnp
    from repro.kernels import ops
    kda = ops.kda

    def carried(q, k, v, log_a, beta, seg, **kw):
        return kda(q, k, v, log_a, beta, jnp.ones_like(seg), **kw)
    with plants.patched(ops, "kda", carried):
        yield


class _Plants:
    """``bench/plants.py`` with ``no_reset`` beside its training plants."""

    @staticmethod
    @contextmanager
    def plant(driver, name):
        if name == "no_reset":
            with no_reset():
                yield
        else:
            with plants.plant(driver, name):
                yield


def memory_analysis(cell, driver, devices, device_info) -> dict:
    """The compiled train step's memory analysis at the cell's size."""
    import shutil
    import tempfile
    work = tempfile.mkdtemp(prefix="bench_")
    ctx = harness.Context(cell, 1, 0.0, False, work, devices, harness.Spans(),
                          device_info)
    try:
        st = driver.setup(ctx)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    loop = st["loop"]
    batch = loop.trainer.put_batch(loop.hosts[-1])
    m = st["trainer"].step.lower(st["params"], st["opt_state"], batch
                                 ).compile().memory_analysis()
    return {k: getattr(m, k) for k in ("argument_size_in_bytes", "output_size_in_bytes",
                                       "alias_size_in_bytes", "temp_size_in_bytes",
                                       "generated_code_size_in_bytes")}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--plants", default="",
                    help="comma-separated plants, besides sound and the control")
    args = ap.parse_args()
    harness.prepare()
    cell = harness.load_cell(args.workload)
    driver = harness.load_module(harness.bench_file(cell.root, "drivers", "train_kda"),
                                 "driver_train_kda")
    device_info = harness.require_devices(cell.chips)
    harness.enable_compile_cache()
    import jax
    devices = jax.devices()[:cell.chips]
    limits = cell.config["limits"]
    names = [n for n in args.plants.split(",") if n]
    control_moe.plants = _Plants
    print(json.dumps({"memory_analysis": memory_analysis(cell, driver, devices,
                                                         device_info)}), flush=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        res = control_moe.readings(cell, driver, seed, names, devices, device_info)
        leaves = res.pop("leaves")
        for name, r in res.items():
            print(json.dumps({"seed": seed, "reading": name, **r,
                              "passes": all(v <= limits[k] for k, v in r.items())}),
                  flush=True)
        print(json.dumps({"seed": seed, "reading": "leaves", **leaves}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
