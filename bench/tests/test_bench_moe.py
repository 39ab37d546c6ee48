"""The Moonlight cell's driver, check, costs and metrics at a size the CPU
can run: a sound run passes with nothing dropped, the control -- the
reference computed in fp8 in the program's place -- reads over the
limits, the operation counts match hand counts and the kernel metrics
read a recorded trace."""
import json
import os

import pytest

import devtrace
import harness
import tiny
from costs import mla_moe_step

CELL = "moonlight_train.fed8k"
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("tiny"))


def _driver():
    return harness.load_module(os.path.join(BENCH, "drivers", "train_moe.py"),
                               "driver_train_moe")


def test_sound_run_is_correct(root, capsys):
    out = tiny.run(root, CELL, 3000000041, capsys=capsys)
    assert out["correct"] is True, out["checks"]
    assert out["checks"]["tokens_dropped"]["value"] == 0
    assert out["checks"]["bias_entries_differing"]["value"] == 0


def test_control_reads_over_the_limits(root):
    cell = harness.load_cell(CELL, root)
    driver = _driver()
    from reference import decoder, packer
    from reference.mla_moe import init_params as ref_init
    import corpus
    feed = cell.config["feed"]
    planes = packer.pack(corpus.shard_docs(5, 0, feed["corpus"]), feed["seq_len"])
    batches = [{p: v[i * 4:(i + 1) * 4] for p, v in planes.items()} for i in range(3)]
    import jax
    start = jax.device_get(jax.jit(lambda w: ref_init(cell.config, w))(
        decoder.seed_words(5)))
    want = driver.run_reference(cell.config, start, batches)
    low = driver.run_reference(cell.config, start, batches, quantize=decoder.fp8)
    readings = driver.compare(cell.config, low, want)
    limits = cell.config["limits"]
    assert any(readings[k] > limits[k] for k in readings), readings



def test_readings_script_tells_sound_from_fault_and_control(root):
    """``bench/control_moe.py``'s readings at the CPU size: the sound
    set-up passes the limits, half the batch's labels left out and the
    fp8 control do not."""
    import jax
    import control_moe
    cell = harness.load_cell(CELL, root)
    res = control_moe.readings(cell, _driver(), 3000000043, ["half_batch"],
                                 jax.devices()[:1], tiny.cpu_device(1))
    limits = cell.config["limits"]
    over = {k: [n for n, v in res[k].items() if v > limits[n]]
            for k in ("sound", "half_batch", "control")}
    assert not over["sound"], res["sound"]
    assert over["half_batch"] and over["control"], res


def _config():
    with open(os.path.join(BENCH, "configs", "moonlight-16b-a3b.ep8.json")) as f:
        return json.load(f)


def test_step_operations_hand_count():
    cfg = _config()
    attn = (2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048)
    moe = 6 * 8 / 64 * 3 * 2048 * 1408 + 3 * 2048 * 2816 + 2048 * 64
    per_token = 5 * attn + 3 * 2048 * 11264 + 4 * moe + 2048 * 20480
    assert mla_moe_step.matmul_params_per_token(cfg) == pytest.approx(per_token)
    attention = 5 * 3 * 8192 * 16 * (192 + 128)      # causal, fwd + bwd
    assert mla_moe_step.flops_per_step(cfg, 4, 8192) == pytest.approx(
        (6 * per_token + attention) * 4 * 8192, rel=1e-12)
    assert mla_moe_step.flops_per_step(cfg, 4, 8192) == pytest.approx(74.81e12, rel=1e-3)


def _record(events):
    t = devtrace.Trace(device_ops={"/device:TPU:0": [
        devtrace.Event(n, s, e) for n, s, e in events]})
    return {"trace": t, "trace_window": (0.0, 10.0),
            "trace_planes": ["/device:TPU:0"], "device": {"kind": "TPU v5 lite"},
            "config": _config(), "batch": 4, "seq_len": 8192, "steps": 2,
            "moe_computed": [100000.0, 96000.0]}


def test_kernel_metrics_read_a_recorded_trace():
    # 2 steps x 4 expert layers x 12 calls (3 products: forward, its
    # recomputation, the input gradient, the weight gradient), 1 ms each;
    # one splash call of each kind, 2 ms each; one op of another name
    events = [("gmm.1", 0.001 * i, 0.001 * i + 0.001) for i in range(72)]
    events += [("tgmm.2", 1.0 + 0.001 * i, 1.0 + 0.001 * i + 0.001) for i in range(24)]
    events += [("splash_mha_fwd_segmented_residuals.4", 2.0, 2.002),
               ("splash_mha_dq_segmented_no_residuals.5", 3.0, 3.002),
               ("splash_mha_dkv_segmented_no_residuals.6", 4.0, 4.002),
               ("fusion.9", 5.0, 6.0)]
    rec = _record(events)
    gmm = harness.load_module(os.path.join(BENCH, "metrics", "moe_gmm_roofline.py"),
                              "moe_gmm_roofline")
    flops = 12 * 2 * 196000 * 2048 * 1408             # calls per layer-step x work
    want = 100 * flops / 197e12 / 0.096
    assert gmm.read(rec) == pytest.approx(want, rel=1e-9)
    attn = harness.load_module(os.path.join(BENCH, "metrics",
                                            "mla_attention_roofline.py"),
                               "mla_attention_roofline")
    pairs = 4 * 16 * 8192 * 8192 / 2
    flops = 2 * pairs * ((1 + 2 + 2) * 192 + (1 + 1 + 2) * 128)
    assert attn.read(rec) == pytest.approx(100 * flops / 197e12 / 0.006, rel=1e-9)
    assert gmm.read({}) is None and attn.read({}) is None
