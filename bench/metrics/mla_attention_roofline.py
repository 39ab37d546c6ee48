"""Latent attention's splash kernels (forward, dq, dkv) in the traced
window: their operations over their device time, against the chip's bf16
peak (%).  Operations per call: the causal half of the kernel's products
at qk head nope + rope and v head ``v_head_dim`` over the step's rows
(``bench/costs/mla_moe_step.py``)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import _train_kernels  # noqa: E402
from costs import mla_moe_step as cost  # noqa: E402


def read(rec):
    if "qk_nope_head_dim" not in rec.get("config", {}):
        return None
    ev = _train_kernels.kernel_events(rec, tuple(cost.SPLASH_PRODUCTS))
    if not ev:
        return None
    flops = sum(n * cost.splash_flops(rec["config"], k, rec["batch"], rec["seq_len"])
                for k, (n, _) in ev.items())
    return _train_kernels.roofline_pct(rec, flops, sum(s for _, s in ev.values()))
