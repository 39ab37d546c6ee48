"""The dropless expert layer's grouped-matmul kernels (megablox ``gmm``
forward and input-gradient, ``tgmm`` weight-gradient) in the traced
window: their operations over their device time, against the chip's bf16
peak (%).

Operations: each call moves one layer's assignments computed here through
one (D, F) or (F, D) matrix, 2 n D F (``bench/costs/mla_moe_step.py``);
the window's calls per layer and step are counted from the trace (forward,
its recomputation in the backward, and both backward products), and the
assignments from the step's own counter (``moe_computed``, summed over the
expert layers), so that the count follows the routing the step saw."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import _train_kernels  # noqa: E402
from costs import mla_moe_step as cost  # noqa: E402


def read(rec):
    computed = rec.get("moe_computed")
    ev = _train_kernels.kernel_events(rec, ("gmm", "tgmm"))
    if not computed or not ev or not rec.get("steps"):
        return None
    cfg = rec["config"]
    calls = sum(n for n, _ in ev.values())
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    per_layer_step = calls / (rec["steps"] * layers)
    flops = per_layer_step * cost.gmm_flops(cfg, sum(computed))
    return _train_kernels.roofline_pct(rec, flops, sum(s for _, s in ev.values()))
