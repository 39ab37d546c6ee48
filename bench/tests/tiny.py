"""A copy of the benchmark's files at sizes a CPU test can run: every cell
of ``BENCHMARK.json`` that has its CPU sizes, its configuration and
traffic cut down (shorter rows and shards, a two-layer model), the drivers
and metrics unchanged.

The CPU sizes are files named by what they size, under
``bench/tests/sizes/``, each ``{"over": {...}}`` merged into the file it
sizes:

- ``configs/<config>.json`` into the configuration; its ``"beside"`` maps
  a configuration that the cut-down one names to ``{"from": <config>,
  "over": {...}}``, written as ``bench/configs/<name>.json``;
- ``drivers/<driver>.json``, then ``arrivals/<arrivals>.json``, into each
  traffic file that names that driver or arrivals kind.

A cell whose configuration, driver or arrivals kind has no size file is
left out of the copy; ``test_every_cell_has_a_cpu_size`` names the file.

A new cell therefore adds files and entries, and edits none:

- ``bench/configs/<config>.json`` and ``bench/traffic/<traffic>.json``;
- its driver, reference, costs and metric readers (``bench/drivers/``,
  ``bench/reference/``, ``bench/costs/``, ``bench/metrics/``), where no
  existing one serves;
- its two CPU size files, ``sizes/configs/<config>.json`` and
  ``sizes/drivers/<driver>.json`` (and ``sizes/arrivals/`` for a new
  arrivals kind);
- its entries in ``BENCHMARK.json``: the configuration, the workload, and
  the cell's name appended to the ``workloads`` lists of the end-to-end
  metric it reports and of the shared per-layer metrics it should report.
"""
from __future__ import annotations

import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

#: where the CPU sizes live, relative to the root of a benchmark
SIZES = os.path.join("bench", "tests", "sizes")


def _merge(base, over):
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(base[k], v) if isinstance(v, dict) and k in base else v
    return out


def _load(path):
    with open(path) as f:
        return json.load(f)


def _dump(obj, path):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


def _traffic(source, entry):
    return _load(os.path.join(source, "bench", "traffic", entry["traffic"] + ".json"))


def _sizes(source, entry):
    """The size files a cell needs, relative to ``source``, in the order
    they are merged."""
    t = _traffic(source, entry)
    out = [os.path.join(SIZES, "configs", entry["config"] + ".json"),
           os.path.join(SIZES, "drivers", t["driver"] + ".json")]
    if "arrivals" in t:
        out.append(os.path.join(SIZES, "arrivals", t["arrivals"] + ".json"))
    return out


def _workloads(source):
    return _load(os.path.join(source, "BENCHMARK.json"))["workloads"]


def _missing(source, entry):
    return [p for p in _sizes(source, entry)
            if not os.path.exists(os.path.join(source, p))]


def missing_sizes(cell, source=ROOT):
    """The size files that ``cell`` needs and ``source`` lacks."""
    return _missing(source, {w["name"]: w for w in _workloads(source)}[cell])


def make_root(tmp, source=ROOT) -> str:
    """Write the cut-down copy of the benchmark at ``source`` under
    ``tmp``; returns its root.  Its drivers and metrics are this
    benchmark's (``harness.bench_file``); those only ``source`` has are
    copied."""
    root = str(tmp)
    bench = _load(os.path.join(source, "BENCHMARK.json"))
    bench["workloads"] = [w for w in bench["workloads"]
                          if not _missing(source, w)]
    used = {w["config"] for w in bench["workloads"]}
    bench["configs"] = [c for c in bench["configs"] if c["name"] in used]
    for c in bench["configs"]:
        size = _load(os.path.join(source, SIZES, "configs", c["name"] + ".json"))
        _dump(_merge(_load(os.path.join(source, c["file"])), size["over"]),
              os.path.join(root, c["file"]))
        for name, other in size.get("beside", {}).items():
            base = _load(os.path.join(source, "bench", "configs", other["from"] + ".json"))
            _dump(_merge(base, other["over"]),
                  os.path.join(root, "bench", "configs", name + ".json"))
    for w in bench["workloads"]:
        t = _traffic(source, w)
        for path in _sizes(source, w)[1:]:
            t = _merge(t, _load(os.path.join(source, path))["over"])
        _dump(t, os.path.join(root, "bench", "traffic", w["traffic"] + ".json"))
    _dump(bench, os.path.join(root, "BENCHMARK.json"))
    for kind in ("drivers", "metrics"):
        for f in os.listdir(os.path.join(source, "bench", kind)):
            if f.endswith(".py") and not os.path.exists(os.path.join(BENCH, kind, f)):
                os.makedirs(os.path.join(root, "bench", kind), exist_ok=True)
                shutil.copy(os.path.join(source, "bench", kind, f),
                            os.path.join(root, "bench", kind, f))
    return root


def cells(driver=None, sized=True, source=ROOT):
    """The cells of ``source``'s benchmark (of one driver), those its
    cut-down copy holds unless ``sized`` is false."""
    out = []
    for w in _workloads(source):
        if sized and _missing(source, w):
            continue
        if driver is None or _traffic(source, w)["driver"] == driver:
            out.append(w["name"])
    return out


def cpu_device(chips):
    return {"platform": "cpu", "kind": "cpu", "count": chips}


def run(root, cell, seed, seconds=1.0, trace=0, capsys=None):
    """One run of ``cell`` in ``root`` on the CPU; returns the result line."""
    import harness
    rc = harness.main(["--workload", cell, "--seed", str(seed), "--seconds",
                       str(seconds), "--trace", str(trace)],
                      find_devices=cpu_device, root=root)
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])
