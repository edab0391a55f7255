"""The pair part of a PaiNN fleet step, its share of the chips' bf16 peak:
the analytic FLOPs of the filter layer and the messages over every atom
pair, forward and reverse (``pair_step_flops`` of the configuration's
architecture), times the steps of the window, over the device seconds of
the operations that hold a pair tensor, chips and peak, in percent.

A pair operation is one whose HLO text, which names the trace's
operation and lists its operands' shapes as well as its own, holds an
array with two adjacent axes of the structure's atom count A.  At A = 64
the readout's hidden layer (K, N, A, F/2) matches too; its operations
move under a hundredth of the pair tensors' bytes.  Architectures
without a pair part, and runs without a trace, read nothing."""
import re

import harness
import xplane


def pair_matcher(n_atoms):
    pair = re.compile(r"\[(?:\d+,)*%d,%d(?:,\d+)*\]" % (n_atoms, n_atoms))
    return lambda name: pair.search(name) is not None


def read(rec):
    s = rec["trace"]
    if rec["traffic"]["loop"] != "exchange" or s is None \
            or rec["peaks"] is None:
        return None
    program = harness.arch(rec["cfg"]).program
    if not hasattr(program, "pair_step_flops"):
        return None
    w = rec["window"]
    secs = xplane.op_seconds(s, pair_matcher(rec["cfg"]["n_atoms"]))
    if secs <= 0 or w["steps"] <= 0:
        return None
    flops = program.pair_step_flops(rec["cfg"], w["step_flops_walkers"]) \
        * w["steps"]
    return 100.0 * flops / (secs * rec["chips"]
                            * rec["peaks"]["bf16_flops_per_s"])
