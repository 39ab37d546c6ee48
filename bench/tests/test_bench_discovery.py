"""Cells, configurations, traffic, drivers and metrics are found by name:
a new cell with a metric of its own runs from new files and entries alone,
its CPU sizes among them."""
import json
import os
import shutil
import textwrap

import pytest

import harness
import tiny

DRIVER = '''
import harness

def run(ctx):
    return harness.Outcome(
        setup_s=0.25, end_to_end={"dummy_ops_per_s": 42.0 + ctx.cell.config["size"]},
        attempted=ctx.cell.traffic["ops"], failed=0,
        checks=[harness.Check("dummy_gap", 0.0, 0.5)],
        memory_peak_bytes=None, record={"busy_s": 1.0, "window_s": 4.0})
'''
METRIC = '''
def read(rec):
    return 100.0 * rec["busy_s"] / rec["window_s"]
'''


def _write(path, text):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(textwrap.dedent(text))


def _bench(source, change):
    path = os.path.join(source, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    change(bench)
    with open(path, "w") as f:
        json.dump(bench, f)


def _add_cell(source, cell, config, sized=True):
    """Add ``cell``, on a configuration of its own and the dummy traffic, to
    the benchmark at ``source``; with ``sized``, the configuration's CPU size
    file too (the configuration's size reads 100, its CPU size 1)."""
    def change(bench):
        bench["configs"].append({"name": config, "source": "https://example.org",
                                 "file": f"bench/configs/{config}.json",
                                 "reduced": [], "why": "a dummy"})
        bench["workloads"].append({"name": cell, "config": config,
                                   "traffic": "dummy", "chips": 1, "why": "a dummy"})
    _bench(source, change)
    _write(os.path.join(source, "bench", "configs", config + ".json"), '{"size": 100}')
    if sized:
        _write(os.path.join(source, tiny.SIZES, "configs", config + ".json"),
               '{"over": {"size": 1}}')


@pytest.fixture
def source(tmp_path):
    """A copy of this benchmark with the dummy cell added as files and
    entries alone: configuration, traffic, driver, metric, two CPU sizes."""
    source = str(tmp_path / "source")
    shutil.copytree(harness.BENCH, os.path.join(source, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(tiny.ROOT, "BENCHMARK.json"), source)
    _add_cell(source, "dummy.cell", "dummy_cfg")

    def metrics(bench):
        bench["end_to_end"].append({"name": "dummy_ops_per_s", "unit": "ops/s",
                                    "better": "higher", "bound": 0.05,
                                    "source": "host_clock", "workloads": ["dummy.cell"]})
        bench["per_layer"].append({"name": "dummy_share", "unit": "%",
                                   "better": "higher", "source": "device_trace",
                                   "layer": "dummy", "moves": "dummy_ops_per_s",
                                   "workloads": ["dummy.cell"]})
    _bench(source, metrics)
    _write(os.path.join(source, "bench", "traffic", "dummy.json"),
           '{"driver": "dummy", "ops": 100}')
    _write(os.path.join(source, tiny.SIZES, "drivers", "dummy.json"),
           '{"over": {"ops": 3}}')
    _write(os.path.join(source, "bench", "drivers", "dummy.py"), DRIVER)
    _write(os.path.join(source, "bench", "metrics", "dummy_share.py"), METRIC)
    return source


@pytest.fixture
def root(source, tmp_path):
    return tiny.make_root(tmp_path / "tiny", source)


def test_a_cell_added_as_files_runs(root, capsys):
    out = tiny.run(root, "dummy.cell", 7, capsys=capsys)
    assert out["correct"] is True
    assert out["attempted"] == 3        # the driver's CPU size, not the traffic's 100
    assert out["metrics"] == {"dummy_ops_per_s": {"value": 43.0, "unit": "ops/s"},
                              "setup_s": {"value": 0.25, "unit": "s"}}
    assert list(out)[-1] == "checks"
    assert out["checks"] == {"dummy_gap": {"value": 0.0, "limit": 0.5}}


def test_its_per_layer_metric_is_read_in_a_traced_run(root, capsys):
    out = tiny.run(root, "dummy.cell", 7, trace=1, capsys=capsys)
    assert out["metrics"] == {"dummy_share": {"value": 25.0, "unit": "%"}}
    assert out["device"]["busy_s"] == 1.0 and out["device"]["window_s"] == 4.0
    assert "breakdown" not in out       # no device trace to break down


def test_a_cell_reports_only_its_own_metrics(root):
    cell = harness.load_cell("dummy.cell", root)
    assert {m["name"] for m in cell.end_to_end} == {"dummy_ops_per_s", "setup_s"}
    assert [m["name"] for m in cell.per_layer] == ["dummy_share"]
    for name in tiny.cells():
        other = harness.load_cell(name, root)
        assert "dummy_ops_per_s" not in [m["name"] for m in other.end_to_end]


@pytest.mark.parametrize("name", tiny.cells(sized=False))
def test_every_benchmark_cell_finds_its_files(name):
    cell = harness.load_cell(name)
    assert os.path.exists(harness.bench_file(cell.root, "drivers",
                                             cell.traffic["driver"]))
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert cell.per_layer
    for m in cell.per_layer:
        reader = harness.load_module(harness.bench_file(cell.root, "metrics",
                                                        m["name"]), m["name"])
        assert callable(reader.read)
        assert reader.read({}) is None      # nothing to read: no number


@pytest.mark.parametrize("name", tiny.cells(sized=False))
def test_every_cell_has_a_cpu_size(name):
    missing = tiny.missing_sizes(name)
    assert not missing, f"{name} has no CPU size: add {', '.join(missing)}"


def test_a_cell_without_a_cpu_size_is_left_out(source, tmp_path, capsys):
    _add_cell(source, "dummy.unsized", "dummy_unsized", sized=False)
    assert tiny.missing_sizes("dummy.unsized", source) == [
        os.path.join(tiny.SIZES, "configs", "dummy_unsized.json")]
    root = tiny.make_root(tmp_path / "tiny", source)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [w["name"] for w in bench["workloads"]] == tiny.cells() + ["dummy.cell"]
    assert "dummy_unsized" not in [c["name"] for c in bench["configs"]]
    assert tiny.cells(source=source) == tiny.cells() + ["dummy.cell"]
    assert tiny.cells(sized=False, source=source)[-1] == "dummy.unsized"
    assert tiny.run(root, "dummy.cell", 7, capsys=capsys)["correct"] is True
