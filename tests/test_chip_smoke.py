"""``chip_smoke.py``'s phases at a tiny size on the CPU (kernels in
interpret mode), so the script keeps working between runs on the chip."""
import importlib.util
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
SEQ, ROWS, K, M = 128, 8, 4, 2
VOCAB = 512


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ingested(smoke, tmp_path_factory):
    corpus = smoke.make_corpus(3, 1 << 14, vocab=VOCAB, median=60, cap=600)
    store, items, report = smoke.ingest(
        corpus, str(tmp_path_factory.mktemp("smoke") / "store"),
        seq_len=SEQ, rows_per_block=ROWS, shard_tokens=1 << 11,
        epoch_tokens=1 << 12, k=K, m=M)
    return corpus, store, items, report


def test_corpus_is_seeded_and_holds_every_token(smoke):
    a = smoke.make_corpus(1, 5000, vocab=VOCAB, median=60, cap=600)
    b = smoke.make_corpus(1, 5000, vocab=VOCAB, median=60, cap=600)
    docs = a["docs"]["tokens"]
    assert np.array_equal(np.concatenate(list(docs)), a["flat"])
    assert np.array_equal(a["flat"], b["flat"])
    assert a["docs"]["length"].sum() == 5000
    assert max(a["docs"]["length"]) <= 600


def test_ingest_runs_kernels_in_epochs(ingested):
    _, _, items, report = ingested
    assert len(report.epochs) == len(items) // 2
    # both kernels launch once per epoch
    assert report.kernel_calls() == 2 * len(report.epochs)
    assert report.vectorized_rows() > 0


def test_reference_checks_pass(smoke, ingested):
    corpus, store, items, _ = ingested
    rows = smoke.check_packed(store, items, seq_len=SEQ, rows_per_block=ROWS)
    assert smoke.check_parity(store, k=K, m=M) > 0
    fed = smoke.check_feed(store, corpus["flat"], vocab=VOCAB)
    assert fed["rows"] == rows
    sizes = smoke.epoch_kernel_sizes(store, rows_per_block=ROWS, k=K)
    assert sizes["rows"] > 0 and sizes["cols"] > 0


def test_reference_checks_catch_a_corrupt_block(smoke, ingested, tmp_path):
    """A flipped byte in a stored parity block fails the parity check."""
    _, store, _, _ = ingested
    e = next(b for b in store.blocks() if b.is_parity)
    path = os.path.join(store.root, e.path)
    raw = bytearray(open(path, "rb").read())
    try:
        raw[0] ^= 0xFF
        open(path, "wb").write(bytes(raw))
        with pytest.raises(smoke.SmokeFailure, match="parity"):
            smoke.check_parity(store, k=K, m=M)
    finally:
        raw[0] ^= 0xFF
        open(path, "wb").write(bytes(raw))


def test_interpreted_kernels_fail_the_custom_call_check(smoke):
    """On the CPU the kernels are interpreted: no Mosaic kernel is in the
    program, and the check says so instead of passing."""
    with pytest.raises(smoke.SmokeFailure, match="tpu_custom_call"):
        smoke.check_kernels_lowered(4096, 32, 1024, seq_len=SEQ, k=K, m=M)


def test_train_steps_on_fed_batches(smoke, ingested):
    from repro.configs import get_smoke
    _, store, _, _ = ingested
    out = smoke.train(get_smoke("smollm-135m").replace(vocab_size=VOCAB),
                      store, steps=2, batch=8, seq_len=SEQ)
    assert len(out["losses"]) == 2 and np.all(np.isfinite(out["losses"]))
    assert out["compile_s"] > 0


def test_data_parallel_matches_one_device(smoke, ingested):
    import jax
    from repro.configs import get_smoke
    _, store, _, _ = ingested
    out = smoke.compare_data_parallel(
        get_smoke("smollm-135m").replace(vocab_size=VOCAB), store,
        jax.devices()[:1], steps=2, batch=8, seq_len=SEQ)
    assert out["rows_per_device"] == 8
    assert out["max_rel_diff"] == 0.0


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert '"ok"' not in r.stdout
