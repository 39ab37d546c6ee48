"""The fed-training check at a size the CPU can run: a sound run passes,
and the control -- the reference computed in fp8 in the program's place --
reads over the limits."""
import os

import pytest

import corpus
import harness
import tiny
from reference import packer


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("cell", tiny.cells("train"))
def test_sound_run_is_correct(root, cell, capsys):
    out = tiny.run(root, cell, 3000000021, capsys=capsys)
    assert out["correct"] is True, out["checks"]
    assert out["checks"]["loss_gap"]["value"] < 1e-4


def test_control_reads_over_the_limits(root):
    cell = harness.load_cell(tiny.cells("train")[0], root)
    driver = harness.load_module(os.path.join(harness.BENCH, "drivers", "train.py"),
                                 "driver_train")
    feed = harness.load_json(os.path.join(root, "bench", "configs",
                                          cell.config["feed_config"] + ".json"))
    planes = packer.pack(corpus.shard_docs(5, 0, feed["corpus"]), feed["seq_len"])
    batches = [{p: v[i * 8:(i + 1) * 8] for p, v in planes.items()} for i in range(3)]
    from reference import decoder
    words = decoder.seed_words(5)
    want = driver.run_reference(cell.config, words, batches)
    low = driver.run_reference(cell.config, words, batches, quantize=decoder.fp8)
    readings = driver.compare(cell.config, low, want)
    limits = cell.config["limits"]
    assert any(readings[k] > limits[k] for k in readings), readings
