"""The committee-UQ Pallas kernel's share of its roofline: the least
time the chip could take for its bytes (``flops.uq_kernel_bytes`` over
the HBM bandwidth; the kernel does a few FLOPs per byte, so bytes bound
it) over its device time per call in the trace, in percent.  The kernel
is the window's one Mosaic custom call, ``custom_call_target=
"tpu_custom_call"`` in the HLO text that names the trace's operation."""
import flops
import xplane


def is_kernel(name):
    return 'custom_call_target="tpu_custom_call"' in name


def read(rec):
    s = rec["trace"]
    if rec["traffic"]["loop"] != "exchange" or s is None \
            or rec["peaks"] is None:
        return None
    secs = xplane.op_seconds(s, is_kernel)
    w = rec["window"]
    if secs <= 0 or w["steps"] <= 0:
        return None
    rows = w["uq_rows"] // rec["chips"]
    least = flops.uq_kernel_bytes(w["uq_members"], rows, w["uq_dim"]) \
        / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (secs / w["steps"])
