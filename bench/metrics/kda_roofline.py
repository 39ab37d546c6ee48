"""The KDA kernels (``kda_fwd``, ``kda_bwd``) in the traced window: their
operations over their device time, against the chip's bf16 peak (%).
Operations per call: the chunked form's products over the step's rows and
every KDA head (``bench/costs/kda_mla_moe_step.py``).  The kernels
compute in float32 (several bf16 passes a product), so the share of the
bf16 peak stays well below 100%."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import _train_kernels  # noqa: E402
from costs import kda_mla_moe_step as cost  # noqa: E402


def read(rec):
    cfg = rec.get("config", {})
    if "linear_attn_config" not in cfg or not rec.get("batch"):
        return None
    ev = _train_kernels.kernel_events(rec, tuple(cost.KERNEL_MACS))
    if not ev:
        return None
    flops = sum(n * cost.kernel_flops(cfg, k, rec["batch"], rec["seq_len"])
                for k, (n, _) in ev.items())
    return _train_kernels.roofline_pct(rec, flops, sum(s for _, s in ev.values()))
