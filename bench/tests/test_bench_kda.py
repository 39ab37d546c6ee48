"""The Kimi-Linear cell's driver, check, costs and metrics at a size the CPU
can run: a sound run passes with nothing dropped and
every KDA reset made; a planted KDA that carries its state across packed
documents, half the batch's labels left out and the fp8 control read over
the limits; the operation counts match hand counts; the KDA kernel metric
reads a recorded trace."""
import json
import os

import pytest

import devtrace
import harness
import tiny
from costs import kda_mla_moe_step

CELL = "kimi_linear_train.fed8k"
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("tiny"))


def _driver():
    return harness.load_module(os.path.join(BENCH, "drivers", "train_kda.py"),
                               "driver_train_kda")


def test_sound_run_is_correct(root, capsys):
    out = tiny.run(root, CELL, 3000000051, capsys=capsys)
    assert out["correct"] is True, out["checks"]
    assert out["checks"]["tokens_dropped"]["value"] == 0
    assert out["checks"]["kda_resets_missing"]["value"] == 0
    assert out["checks"]["loss_gap"]["value"] < 1e-5


def test_no_reset_half_batch_and_control_read_over_the_limits(root, monkeypatch):
    """``bench/control_kda.py``'s readings at the CPU size: the sound
    set-up passes the limits; the KDA that carries its state across the
    packed documents, half the batch's labels left out and the fp8 control
    do not."""
    import jax
    import control_kda
    import control_moe
    cell = harness.load_cell(CELL, root)
    monkeypatch.setattr(control_moe, "plants", control_kda._Plants)
    res = control_moe.readings(cell, _driver(), 3000000053,
                               ["no_reset", "half_batch"],
                               jax.devices()[:1], tiny.cpu_device(1))
    limits = cell.config["limits"]
    over = {k: [n for n, v in res[k].items() if v > limits[n]]
            for k in ("sound", "no_reset", "half_batch", "control")}
    assert not over["sound"], res["sound"]
    assert over["no_reset"] and over["half_batch"] and over["control"], res


def _config():
    with open(os.path.join(BENCH, "configs", "kimi-linear-48b-a3b.ep32.json")) as f:
        return json.load(f)


def test_step_operations_hand_count():
    cfg = _config()
    D, HK = 2304, 32 * 128
    kda = 4 * D * HK + D * 32 + 2 * (D * 128 + 128 * HK)
    mla = D * 32 * 192 + D * 576 + 512 * 32 * 256 + 32 * 128 * D
    moe = 8 * 8 / 256 * 3 * D * 1024 + 3 * D * 1024 + D * 256
    per_token = 4 * kda + mla + 3 * D * 9216 + 4 * moe + D * 20480
    assert kda_mla_moe_step.matmul_params_per_token(cfg) == pytest.approx(per_token)
    C, d = 64, 128
    chunk = (C * (C - 1) / 2 * d + C * (C + 1) / 2 * d + C ** 3 / 6 + C * C * d
             + 3 * C * d * d + C * C / 2 * d)
    kda_ops = 4 * 3 * 2 * 32 * chunk / C                   # KDA layers, fwd + bwd
    attention = 3 * 8192 * 32 * (192 + 128)                 # one causal layer
    assert kda_mla_moe_step.flops_per_step(cfg, 1, 8192) == pytest.approx(
        (6 * per_token + attention + kda_ops) * 8192, rel=1e-12)
    # a kernel call covers every head of the step's rows: the forward once,
    # the backward at twice the forward
    assert kda_mla_moe_step.kernel_flops(cfg, "kda_fwd", 4, 8192) == pytest.approx(
        2 * 4 * 32 * 128 * chunk, rel=1e-12)
    assert kda_mla_moe_step.kernel_flops(cfg, "kda_bwd", 4, 8192) == pytest.approx(
        2 * 2 * 4 * 32 * 128 * chunk, rel=1e-12)


def test_kda_metric_reads_a_recorded_trace():
    # 2 steps x 4 KDA layers over 4 rows: 2 forward calls (the forward and
    # the mixer's recomputation) and 1 backward each, 10 ms a forward call
    # and 25 ms a backward; one op of another name
    fwd = [("kda_fwd.3", 0.01 * i, 0.01 * i + 0.01) for i in range(16)]
    bwd = [("kda_bwd.4", 1.0 + 0.025 * i, 1.0 + 0.025 * i + 0.025) for i in range(8)]
    t = devtrace.Trace(device_ops={"/device:TPU:0": [
        devtrace.Event(n, s, e) for n, s, e in fwd + bwd + [("fusion.1", 2.0, 3.0)]]})
    rec = {"trace": t, "trace_window": (0.0, 10.0), "trace_planes": ["/device:TPU:0"],
           "device": {"kind": "TPU v5 lite"}, "config": _config(), "batch": 4,
           "seq_len": 8192, "steps": 2}
    metric = harness.load_module(os.path.join(BENCH, "metrics", "kda_roofline.py"),
                                 "kda_roofline")
    C, d = 64, 128
    chunk = (C * (C - 1) / 2 * d + C * (C + 1) / 2 * d + C ** 3 / 6 + C * C * d
             + 3 * C * d * d + C * C / 2 * d)
    per_call = 2 * 4 * 32 * 128 * chunk
    flops = 16 * per_call + 8 * 2 * per_call
    want = 100 * flops / 197e12 / (0.16 + 0.2)
    assert metric.read(rec) == pytest.approx(want, rel=1e-9)
    assert metric.read({}) is None
    assert metric.read(dict(rec, batch=None)) is None
