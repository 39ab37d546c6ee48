"""One run of one benchmark cell, driven by the files that name it.

``BENCHMARK.json`` lists the cells.  A cell names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); the traffic file names the driver that
generates it (``bench/drivers/<driver>.py``).  Each per-layer metric is a
reader of its own (``bench/metrics/<metric>.py``).  Adding a cell, a
configuration, a traffic mix or a metric therefore adds files and entries,
and edits none.

A run: find the chips (none, or too few, is an error and prints no
result), keep JAX's compilation cache at a fixed place, let the driver set
up, measure for ``--seconds`` and check its answers against the plain
references, then print the checks on standard error and one JSON result
as the last line of standard output.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
#: JAX's persistent compilation cache where the environment names none
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------------ files
def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a file by path (file names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + name.replace(".", "_").replace("-", "_"), path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """A cell with everything its files say."""
    name: str
    entry: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    root: str = ROOT

    @property
    def chips(self) -> int:
        return int(self.entry["chips"])


def _reports(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def bench_file(root: str, kind: str, name: str) -> str:
    """``<root>/bench/<kind>/<name>.py``, or the same file of this
    benchmark where ``root`` has none (a cell in another root may bring
    its own drivers and metrics, or use these)."""
    own = os.path.join(root, "bench", kind, name + ".py")
    return own if os.path.exists(own) else os.path.join(BENCH, kind, name + ".py")


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"known: {sorted(entries)}")
    entry = entries[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(root, configs[entry["config"]]["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     entry["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, name) and m["moves"] in reported]
    return Cell(name, entry, config, traffic, e2e, per_layer, root)


# ------------------------------------------------------------------ spans
class Spans:
    """Host spans the benchmark records around its calls into the program,
    on ``time.perf_counter``; in a traced run each is also written into
    the profiler's trace under the same name."""

    def __init__(self, annotate: bool = False) -> None:
        self.annotate = annotate
        self.records: List[Tuple[str, float, float]] = []

    @contextmanager
    def span(self, name: str) -> Iterator[None]:
        ann = None
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.records.append((name, t0, t1))

    def total(self, names: Sequence[str], lo: float = float("-inf"),
              hi: float = float("inf")) -> float:
        return sum(min(e, hi) - max(s, lo) for n, s, e in self.records
                   if n in names and e > lo and s < hi)


# ----------------------------------------------------------------- device
def require_devices(chips: int) -> Dict[str, Any]:
    """The accelerator JAX reports, or SystemExit: a run never falls back
    to the CPU."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(f"bench: no TPU found (JAX platform is {platform!r}); "
                         f"refusing to run")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX finds "
                         f"{len(devices)}")
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": chips}


def memory_peak_bytes(devices: Sequence[Any]) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def enable_compile_cache() -> str:
    """JAX's persistent cache: the directory the environment names, else a
    fixed directory inside the checkout.  Every program is cached, however
    quickly it compiled, so that only a cell's first run compiles."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir


class CompileCounter:
    """Counts the programs JAX compiles or loads from its persistent cache
    (every new executable passes through the cache) while ``counting``."""

    EVENT = "/jax/compilation_cache/compile_requests_use_cache"

    def __init__(self) -> None:
        self.count = 0
        self._on = False

    def _listen(self, event: str, **kw: Any) -> None:
        if self._on and event == self.EVENT:
            self.count += 1

    @contextmanager
    def counting(self) -> Iterator[None]:
        """Count, and log by name, what compiles in the enclosed window."""
        import jax
        import jax.monitoring
        jax.monitoring.register_event_listener(self._listen)
        logged = jax.config.jax_log_compiles
        jax.config.update("jax_log_compiles", True)
        self._on = True
        try:
            yield
        finally:
            self._on = False
            jax.config.update("jax_log_compiles", logged)
            jax.monitoring.unregister_event_listener(self._listen)


# ---------------------------------------------------------------- context
@dataclass
class Context:
    """What a driver is given for one run."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    work_dir: str
    devices: List[Any]
    spans: Spans
    device_info: Dict[str, Any]


@dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclass
class Outcome:
    """What a driver hands back."""
    setup_s: float
    end_to_end: Dict[str, float]
    attempted: int
    failed: int
    checks: List[Check]
    memory_peak_bytes: Optional[int]
    #: read by the per-layer metrics (see ``bench/metrics``)
    record: Dict[str, Any] = field(default_factory=dict)


@contextmanager
def traced(ctx: Context, record: Dict[str, Any],
           window_span: str = "window") -> Iterator[None]:
    """Profile the enclosed window when the run is traced, and put the
    reduced trace into ``record``: the trace, the window's bounds on the
    trace clock, and the device's busy seconds (averaged over chips)."""
    import devtrace as tr
    if not ctx.trace:
        yield
        return
    log_dir = os.path.join(ctx.work_dir, "trace")
    tr.start(log_dir)
    try:
        yield
    finally:
        tr.stop()
    names = {n for n, _, _ in ctx.spans.records} | {window_span}
    t = tr.load(tr.find_xplane(log_dir), names)
    shutil.rmtree(log_dir, ignore_errors=True)
    bounds = tr.span_bounds(t.host_spans, window_span)
    if bounds is None:
        raise RuntimeError(f"no {window_span!r} span in the trace")
    lo, hi = bounds
    planes = sorted(t.device_ops)[:ctx.cell.chips]
    busy = [tr.busy_seconds(t.device_ops[p], lo, hi) for p in planes]
    record["trace"] = t
    record["trace_window"] = (lo, hi)
    record["trace_planes"] = planes
    record["busy_s"] = sum(busy) / max(1, len(busy))
    record["window_s"] = hi - lo


def trace_breakdown(record: Dict[str, Any]) -> Dict[str, Any]:
    import devtrace as tr
    t = record["trace"]
    lo, hi = record["trace_window"]
    plane = record["trace_planes"][0]
    spans = [s for s in t.host_spans if s.name != "window"]
    return {"device_ops": [list(x) for x in tr.top_ops(t.device_ops[plane], lo, hi)],
            "idle_gaps": [list(x) for x in tr.named_gaps(t.device_ops[plane],
                                                         spans, lo, hi)]}


# ------------------------------------------------------------------- main
def host_state() -> str:
    """Load, dirty page cache and the scratch file system: what a host-bound
    cell's spread may come from."""
    parts = []
    try:
        parts.append("load=%.2f,%.2f,%.2f" % os.getloadavg())
        parts.append(f"cpus={os.cpu_count()}")
        with open("/proc/meminfo") as f:
            mem = dict(line.split(":", 1) for line in f)
        parts += [f"{k}={mem[k].strip()}" for k in ("Dirty", "Writeback")]
        tmp = os.path.realpath(tempfile.gettempdir())
        with open("/proc/mounts") as f:
            mounts = [line.split() for line in f]
        best = max((m for m in mounts if tmp.startswith(m[1])),
                   key=lambda m: len(m[1]))
        parts.append(f"tmp={tmp} on {best[2]}")
    except (OSError, ValueError, KeyError):
        pass
    return " ".join(parts)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             device_info: Dict[str, Any], devices: List[Any]) -> Dict[str, Any]:
    """Run the cell's driver once and build the result line."""
    log(f"[host] before: {host_state()}")
    driver = load_module(bench_file(cell.root, "drivers", cell.traffic["driver"]),
                         "driver_" + cell.traffic["driver"])
    work = tempfile.mkdtemp(prefix="bench_")
    ctx = Context(cell, seed, seconds, trace, work, devices,
                  Spans(annotate=trace), device_info)
    try:
        out: Outcome = driver.run(ctx)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    log(f"[host] after: {host_state()}")
    device = dict(device_info)
    device["memory_peak_bytes"] = out.memory_peak_bytes
    values: Dict[str, float] = {"setup_s": out.setup_s, **out.end_to_end}
    metrics: Dict[str, Dict[str, Any]] = {}
    if trace:
        device["busy_s"] = out.record["busy_s"]
        device["window_s"] = out.record["window_s"]
        out.record["device"] = device_info
        for m in cell.per_layer:
            reader = load_module(bench_file(cell.root, "metrics", m["name"]),
                                 m["name"])
            v = reader.read(out.record)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    checks = out.checks
    result: Dict[str, Any] = {
        "correct": bool(checks) and all(c.ok for c in checks) and out.failed == 0,
        "attempted": out.attempted, "failed": out.failed,
        "metrics": metrics, "device": device}
    if trace and "trace" in out.record:
        result["breakdown"] = trace_breakdown(out.record)
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result


def print_result(result: Dict[str, Any]) -> None:
    for name, c in result["checks"].items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)


def parse_args(argv: Optional[Sequence[str]]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def prepare(root: str = ROOT) -> None:
    """The program under test must be beside the benchmark."""
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"bench: no repro package under {src}")
    if src not in sys.path:
        sys.path.insert(0, src)


def main(argv: Optional[Sequence[str]] = None,
         find_devices: Callable[[int], Dict[str, Any]] = require_devices,
         root: str = ROOT) -> int:
    args = parse_args(argv)
    prepare()
    cell = load_cell(args.workload, root)
    device_info = find_devices(cell.chips)
    enable_compile_cache()
    import jax
    devices = jax.devices()[:cell.chips]
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      device_info, devices)
    print_result(result)
    return 0
