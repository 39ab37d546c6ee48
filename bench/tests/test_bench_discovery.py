"""Cells, configurations, traffic, drivers and metrics are found by name:
a new cell with a metric of its own runs from new files and entries alone."""
import json
import os
import textwrap

import pytest

import harness
import tiny

DRIVER = '''
import harness

def run(ctx):
    return harness.Outcome(
        setup_s=0.25, end_to_end={"dummy_ops_per_s": 42.0 + ctx.cell.config["size"]},
        attempted=3, failed=0, checks=[harness.Check("dummy_gap", 0.0, 0.5)],
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


@pytest.fixture
def root(tmp_path):
    root = tiny.make_root(tmp_path)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "dummy_cfg", "source": "https://example.org",
                             "file": "bench/configs/dummy_cfg.json",
                             "reduced": [], "why": "a dummy"})
    bench["workloads"].append({"name": "dummy.cell", "config": "dummy_cfg",
                               "traffic": "dummy", "chips": 1, "why": "a dummy"})
    bench["end_to_end"].append({"name": "dummy_ops_per_s", "unit": "ops/s",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock", "workloads": ["dummy.cell"]})
    bench["per_layer"].append({"name": "dummy_share", "unit": "%",
                               "better": "higher", "source": "device_trace",
                               "layer": "dummy", "moves": "dummy_ops_per_s",
                               "workloads": ["dummy.cell"]})
    with open(path, "w") as f:
        json.dump(bench, f)
    _write(os.path.join(root, "bench", "configs", "dummy_cfg.json"), '{"size": 1}')
    _write(os.path.join(root, "bench", "traffic", "dummy.json"), '{"driver": "dummy"}')
    _write(os.path.join(root, "bench", "drivers", "dummy.py"), DRIVER)
    _write(os.path.join(root, "bench", "metrics", "dummy_share.py"), METRIC)
    return root


def test_a_cell_added_as_files_runs(root, capsys):
    out = tiny.run(root, "dummy.cell", 7, capsys=capsys)
    assert out["correct"] is True
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


@pytest.mark.parametrize("name", tiny.cells())
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
