"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports.

``load`` turns the trace into plain lists: per device, its operations as
(name, start_ns, duration_ns), a TPU operation named by its whole HLO
instruction; on the host, the spans the
benchmark wrote with ``jax.profiler.TraceAnnotation``.  ``reduce`` then
computes, inside the traced window (the host span ``window``):

* ``busy_s``: the union of the intervals in which an operation ran, per
  device, averaged over the devices;
* ``ops``: device seconds per operation name, averaged over devices;
* ``idle_gaps``: device idle time attributed to the innermost benchmark
  span the host was in when the gap began, summed per span name.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

# lines of a device plane that carry its operations, in order of
# preference (TPU planes have "XLA Ops"; other names are older profilers)
OP_LINES = ("XLA Ops", "TPU Ops", "Ops")
_HLO = re.compile(r"^(\S+) = (.*?) ([a-z][\w.-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
WINDOW = "window"


def find(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str, span_names: Sequence[str]):
    """(devices, spans): ``devices`` maps a device plane's name to its
    operations [(name, start_ns, dur_ns)]; ``spans`` is
    [(name, start_ns, dur_ns)] for host events named in ``span_names``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Tuple[str, float, float, str]]] = {}
    spans: List[Tuple[str, float, float]] = []
    wanted = set(span_names)
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            line = next((lines[n] for n in OP_LINES if n in lines), None)
            if line is None:
                continue
            devices[plane.name] = [
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name in wanted:
                        spans.append((e.name, float(e.start_ns),
                                      float(e.duration_ns)))
    return devices, spans


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge [start, end) intervals."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce(devices, spans, top: int = 10):
    """Reduce ``load``'s output to busy time, per-op time and idle gaps
    inside the host span ``window``."""
    win = [(s, s + d) for n, s, d in spans if n == WINDOW]
    if not win:
        raise ValueError("trace holds no 'window' span")
    lo, hi = win[0][0], win[-1][1]
    window_s = (hi - lo) * 1e-9
    inner = sorted((s, s + d, n) for n, s, d in spans if n != WINDOW)
    starts = [s for s, _, _ in inner]
    busy, ops = [], {}
    gaps: Dict[str, float] = {}
    ndev = max(len(devices), 1)
    for ops_list in devices.values():
        iv = []
        for name, s, d in ops_list:
            c = clip([(s, s + d)], lo, hi)
            if not c:
                continue
            iv.append(c[0])
            t = (c[0][1] - c[0][0]) * 1e-9 / ndev
            ops[name] = ops.get(name, 0.0) + t
        merged = union(iv)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        edges = [lo] + [x for iv_ in merged for x in iv_] + [hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge <= gs:
                continue
            label = _label(inner, starts, gs) if inner else "outside_spans"
            gaps[label] = gaps.get(label, 0.0) + (ge - gs) * 1e-9 / ndev
    busy_s = sum(busy) / ndev if busy else 0.0
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    top_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": window_s, "busy_s": busy_s, "devices": len(devices),
            "ops": ops,
            "device_ops": [[short_name(n), t] for n, t in top_ops],
            "idle_gaps": [[n, t] for n, t in top_gaps]}


def _label(inner, starts, t: float, depth: int = 8) -> str:
    """The innermost benchmark span (the latest-starting of those that
    hold time ``t``, looking back ``depth`` spans) or ``outside_spans``."""
    i = bisect.bisect_right(starts, t) - 1
    for s, e, n in inner[max(i - depth, -1) + 1:i + 1][::-1]:
        if s <= t < e:
            return n
    return "outside_spans"


def short_name(op: str) -> str:
    """``%fusion.2 fusion f32[1024,64,64,384]`` from an operation's HLO
    text (the trace names a TPU operation by its whole instruction)."""
    m = _HLO.match(op)
    if not m:
        return op[:120]
    lhs, out_type, opcode = m.groups()
    target = _TARGET.search(op)
    out = f"{lhs} {opcode} {out_type.split('{')[0][:60]}"
    return out + (f" {target.group(1)}" if target else "")


def op_seconds(summary, match) -> float:
    """Device seconds of the operations whose name ``match`` accepts."""
    return sum(t for n, t in summary["ops"].items() if match(n))
