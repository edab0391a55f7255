"""Whole train step's share of the chips' bf16 peak: the analytic FLOPs
of a fused K-member step (``flops.train_step_flops``) times the steps of
the window, over the window's host-clock seconds, chips and peak, in
percent."""
import flops


def read(rec):
    if rec["traffic"]["loop"] != "train" or rec["peaks"] is None:
        return None
    w = rec["window"]
    total = flops.train_step_flops(rec["cfg"], w["train_batch"]) * w["steps"]
    return 100.0 * total / (w["elapsed"] * rec["chips"]
                            * rec["peaks"]["bf16_flops_per_s"])
