"""The benchmark's operation and byte counts against hand counts."""
import json
import os

import pytest

from costs import decoder_step, gf256_matmul, pack_tokens

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_pack_tokens_bytes_hand_count():
    # 100 tokens read (400 B), a row table of 2 rows (16 B), and three
    # (2, 8) int32 planes written (192 B)
    assert pack_tokens.bytes_moved(rows=2, tokens=100, seq_len=8) == 608
    assert pack_tokens.flops(rows=2, tokens=100, seq_len=8) == 0


def test_gf256_matmul_bytes_hand_count():
    # RS(10, 4) over 1000 columns: 10 kB read, 4 kB written
    assert gf256_matmul.bytes_moved(10, 4, 1000) == 14000
    assert gf256_matmul.gf_macs(10, 4, 1000) == 40000


def _smollm():
    with open(os.path.join(BENCH, "configs", "smollm-135m.feed.json")) as f:
        return json.load(f)


def test_decoder_matmul_params_hand_count():
    cfg = _smollm()
    per_layer = (576 * 9 * 64            # q
                 + 2 * 576 * 3 * 64      # k, v
                 + 9 * 64 * 576          # o
                 + 3 * 576 * 1536)       # gate, up, down
    assert decoder_step.matmul_params(cfg) == 30 * per_layer + 49152 * 576


def test_decoder_matmul_params_match_the_model_size():
    """The program's analytic size less its norms is what counts here."""
    from repro.configs import get_config
    cfg = _smollm()
    program = get_config("smollm-135m")
    norms = 30 * 2 * 576 + 576
    assert decoder_step.matmul_params(cfg) == program.param_count() - norms


def test_decoder_flops_per_step_hand_count():
    cfg = _smollm()
    n = decoder_step.matmul_params(cfg)
    attn = 30 * 6 * 2048 * 9 * 64      # causal QK^T and PV, fwd + bwd
    want = (6 * n + attn) * 8 * 2048
    assert decoder_step.flops_per_step(cfg, 8, 2048) == pytest.approx(want, rel=1e-12)
