"""The plain references agree with the program on small inputs, and with
hand-worked values: they are written independently, so agreement here is
evidence for both."""
import numpy as np
import pytest

import corpus
from reference import gf256, packer

CORPUS = {"shard_tokens": 3000, "vocab_size": 1000, "doc_len_median": 40,
          "doc_len_sigma": 1.2, "doc_len_cap": 700}


def test_gf256_field_hand_values():
    # 0x53 * 0xCA = 1 under 0x11B (the AES inverse pair)
    assert gf256.mul(0x53, 0xCA) == 1
    assert gf256.inv(0x53) == 0xCA
    assert gf256.mul(2, 0x80) == 0x1B       # x * x^7 reduces by the polynomial
    c = gf256.cauchy(4, 10)
    assert c.shape == (4, 10)
    assert gf256.mul(int(c[0, 0]), 10 ^ 0) == 1


def test_gf256_parity_matches_the_program():
    from repro.erasure.reed_solomon import ReedSolomon
    rng = np.random.default_rng(0)
    payloads = [rng.integers(0, 256, n, dtype=np.uint8).tobytes()
                for n in (300, 512, 77, 512)]
    want, width = ReedSolomon(10, 4).encode_payloads(payloads)
    got = gf256.stripe_parity(payloads, 10, 4)
    assert got.shape == (4, width)
    np.testing.assert_array_equal(got, want)


def test_packer_hand_example():
    docs = [np.arange(1, 6), np.arange(10, 13), np.arange(20, 30)]
    out = packer.pack(docs, seq_len=8)
    # row 0: doc 0 (5) + doc 1 (3) fill it; doc 2 is cut 8 + 2
    np.testing.assert_array_equal(out["tokens"][0], [1, 2, 3, 4, 5, 10, 11, 12])
    np.testing.assert_array_equal(out["segment_ids"][0], [1, 1, 1, 1, 1, 2, 2, 2])
    np.testing.assert_array_equal(out["positions"][0], [0, 1, 2, 3, 4, 0, 1, 2])
    np.testing.assert_array_equal(out["tokens"][1], np.arange(20, 28))
    np.testing.assert_array_equal(out["tokens"][2], [28, 29, 0, 0, 0, 0, 0, 0])
    np.testing.assert_array_equal(out["loss_mask"][2], [1, 1, 0, 0, 0, 0, 0, 0])
    assert len(packer.blocks(out, 2)) == 2


@pytest.mark.parametrize("shard", [0, 1, 2])
def test_packer_matches_the_programs_scalar_packer(shard):
    from repro.core.items import IngestItem
    from repro.core.ops_format import PackOp
    item = corpus.shard_item(11, shard, CORPUS)
    rows = PackOp(seq_len=256, rows_per_block=8)._pack_rows(
        IngestItem(item.data, item.granularity))
    got = packer.pack(corpus.shard_docs(11, shard, CORPUS), 256)
    for p in packer.PLANES:
        np.testing.assert_array_equal(got[p], np.stack([r[p] for r in rows]))
