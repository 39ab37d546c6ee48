"""A copy of the benchmark's files at sizes a CPU test can run: every cell
of ``BENCHMARK.json``, its configuration and traffic cut down (shorter
rows and shards, a two-layer model), the drivers and metrics unchanged."""
from __future__ import annotations

import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

CONFIG = {
    "lm_corpus.rs10-4": {
        "seq_len": 256, "epoch": {"items": 4, "seconds": 1.0},
        "corpus": {"shard_tokens": 4096, "doc_len_median": 60,
                   "doc_len_cap": 3000}},
    "smollm-135m.feed": {
        "hidden_size": 48, "intermediate_size": 96, "num_attention_heads": 3,
        "num_key_value_heads": 1, "head_dim": 16, "num_hidden_layers": 2,
        "vocab_size": 256, "dtype": "float32", "param_dtype": "float32",
        "feed_config": "lm_corpus.tiny"},
}
FEED = {"name": "lm_corpus.tiny", "seq_len": 64, "vocab_size": 256,
        "corpus": {"shard_tokens": 1024, "vocab_size": 256,
                   "doc_len_median": 20, "doc_len_cap": 500}}
TRAFFIC = {
    "ingest": {"queue_capacity": 8, "shape_sample_shards": 8,
               "check_shards": 6, "check_stripes": 4},
    "backlog": {"epoch_items": 4, "epoch_shards": [4, 4]},
    "poisson": {"rate_shards_per_s": 30.0, "epoch_shards": [1, 4]},
    "train": {"fill_shards": 8},
}


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


def make_root(tmp) -> str:
    """Write the cut-down copy under ``tmp``; returns its root."""
    root = str(tmp)
    bench = _load(os.path.join(ROOT, "BENCHMARK.json"))
    for c in bench["configs"]:
        cfg = _merge(_load(os.path.join(ROOT, c["file"])), CONFIG[c["name"]])
        _dump(cfg, os.path.join(root, c["file"]))
    feed = _merge(_load(os.path.join(ROOT, "bench", "configs",
                                     "lm_corpus.rs10-4.json")), FEED)
    _dump(feed, os.path.join(root, "bench", "configs", "lm_corpus.tiny.json"))
    for w in bench["workloads"]:
        t = _load(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
        t = _merge(t, TRAFFIC[t["driver"]])
        if "arrivals" in t:
            t = _merge(t, TRAFFIC[t["arrivals"]])
        _dump(t, os.path.join(root, "bench", "traffic", w["traffic"] + ".json"))
    _dump(bench, os.path.join(root, "BENCHMARK.json"))
    return root


def cells(driver=None):
    bench = _load(os.path.join(ROOT, "BENCHMARK.json"))
    out = []
    for w in bench["workloads"]:
        t = _load(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
        if driver is None or t["driver"] == driver:
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
