#!/usr/bin/env python3
"""Where the device's idle time goes: the program's own spans and device
programs in a profiler trace of one cell's window.

    python3 benchmarks/chip/span_split.py --workload <name> --seed <n> \\
        --seconds <s>

From the root of a checkout.  Runs the cell's set-up and a traced window
as ``run.py --trace 1`` does, without the check after it, and prints one
JSON object.  ``xplane`` reduces a trace with the benchmark's own spans
only; this module reads it with the program's spans as well
(``repro.core.monitor.SPAN_NAMES``) and with each device's program runs,
and splits the idle time:

* ``idle_gaps``: idle seconds by the innermost span, benchmark's or
  program's, that the host was in when each gap began;
* ``span_s``, ``span_self_s``, ``span_n``: seconds, self seconds (less the
  spans opened inside, on the same host thread) and count per span name;
* ``module_runs``, ``module_s``: runs and device seconds per program, by
  its base name (``jit_engine_step_score``);
* ``module_gap_s``: idle seconds between consecutive runs of a program;
  ``module_idle_in_s``: idle seconds inside its runs;
* ``layers``: the four per-layer numbers these give
  (``exchange_host_ms.fleet``, ``fleet_launch_gap_ms.fleet``,
  ``trainer_host_ms.train``, ``train_launch_gap_ms.train``) and the split
  of the idle time between, inside and around runs of the cell's main
  program.

Every quantity is inside the ``window`` span and averaged over devices,
as in ``xplane.reduce``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Sequence, Tuple  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402
import xplane  # noqa: E402

MODULE_LINE = "XLA Modules"
OUTSIDE = "outside_spans"
# the program each loop kind runs once per step
MAIN_PROGRAM = {"exchange": "jit_engine_step_score",
                "train": "jit_committee_train_step"}


def span_name(event_name: str) -> str:
    """A host event's span name: the part before any ``#``, where a
    profiler may encode the span's arguments (``name#step=3#``)."""
    return event_name.split("#", 1)[0]


def program_name(event_name: str) -> str:
    """``jit_engine_step_score(1361...)`` -> ``jit_engine_step_score``."""
    return event_name.split("(", 1)[0]


def load(path: str, span_names: Sequence[str]):
    """(devices, modules, spans): per device plane its operations
    [(name, start_ns, dur_ns)] and its program runs [(base name, start_ns,
    dur_ns)]; host spans named in ``span_names`` as [(name, start_ns,
    dur_ns, host line)]."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Tuple[str, float, float]]] = {}
    modules: Dict[str, List[Tuple[str, float, float]]] = {}
    spans: List[Tuple[str, float, float, str]] = []
    wanted = set(span_names)
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            line = next((lines[n] for n in xplane.OP_LINES if n in lines),
                        None)
            if line is None:
                continue
            devices[plane.name] = [
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events]
            modules[plane.name] = [
                (program_name(e.name), float(e.start_ns),
                 float(e.duration_ns))
                for e in (lines[MODULE_LINE].events
                          if MODULE_LINE in lines else ())]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                key = f"{plane.name}/{ln.name}"
                for e in ln.events:
                    name = span_name(e.name)
                    if name in wanted:
                        spans.append((name, float(e.start_ns),
                                      float(e.duration_ns), key))
    return devices, modules, spans


def label(spans, times: Sequence[float]) -> List[str]:
    """For each of the sorted ``times``, the innermost span holding it:
    of the spans with start <= t < end, the one that started last (the
    shorter of two that started together), or ``outside_spans``.  A sweep
    keeps a stack of the spans opened so far and drops ended ones from its
    top, so any depth of nesting is handled."""
    order = sorted(spans, key=lambda s: (s[1], -s[2]))
    out, stack, i = [], [], 0
    for t in times:
        while i < len(order) and order[i][1] <= t:
            stack.append(order[i])
            i += 1
        while stack and stack[-1][1] + stack[-1][2] <= t:
            stack.pop()
        out.append(stack[-1][0] if stack else OUTSIDE)
    return out


def overlap(a, b) -> float:
    """Total length of the intersection of two sorted lists of disjoint
    [start, end) intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def self_times(spans) -> List[float]:
    """Each span's duration less that of its direct children on the same
    host line, in the order of ``spans``."""
    child = [0.0] * len(spans)
    by_line: Dict[str, List[int]] = {}
    for k, s in enumerate(spans):
        by_line.setdefault(s[3], []).append(k)
    for ks in by_line.values():
        stack: List[int] = []
        for k in sorted(ks, key=lambda k: (spans[k][1], -spans[k][2])):
            start = spans[k][1]
            while stack and spans[stack[-1]][1] + spans[stack[-1]][2] \
                    <= start:
                stack.pop()
            if stack:
                child[stack[-1]] += spans[k][2]
            stack.append(k)
    return [s[2] - c for s, c in zip(spans, child)]


def reduce(devices, modules, spans):
    """The split of the device's idle time inside the host span
    ``window`` (keys in the module docstring)."""
    win = [(s, s + d) for n, s, d, _ in spans if n == xplane.WINDOW]
    if not win:
        raise ValueError("trace holds no 'window' span")
    lo, hi = win[0][0], win[-1][1]
    inner = [s for s in spans if s[0] != xplane.WINDOW]
    ndev = max(len(devices), 1)
    span_s: Dict[str, float] = {}
    span_self_s: Dict[str, float] = {}
    span_n: Dict[str, int] = {}
    for s, own in zip(inner, self_times(inner)):
        if lo <= s[1] < hi:
            span_s[s[0]] = span_s.get(s[0], 0.0) + s[2] * 1e-9
            span_self_s[s[0]] = span_self_s.get(s[0], 0.0) + own * 1e-9
            span_n[s[0]] = span_n.get(s[0], 0) + 1
    gaps: Dict[str, float] = {}
    busy = 0.0
    runs_n: Dict[str, float] = {}
    mod_s: Dict[str, float] = {}
    gap_s: Dict[str, float] = {}
    idle_in: Dict[str, float] = {}
    for dev, ops in devices.items():
        merged = xplane.union(xplane.clip(
            [(s, s + d) for _, s, d in ops], lo, hi))
        busy += sum(e - s for s, e in merged) * 1e-9 / ndev
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        idle = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
        for (s, e), name in zip(idle, label(inner, [s for s, _ in idle])):
            gaps[name] = gaps.get(name, 0.0) + (e - s) * 1e-9 / ndev
        per_prog: Dict[str, List[Tuple[float, float]]] = {}
        for name, s, d in modules.get(dev, ()):
            per_prog.setdefault(name, []).append((s, s + d))
        for name, runs in per_prog.items():
            runs = xplane.clip(sorted(runs), lo, hi)
            between = [(a[1], b[0]) for a, b in zip(runs, runs[1:])
                       if b[0] > a[1]]
            runs_n[name] = runs_n.get(name, 0.0) + len(runs) / ndev
            mod_s[name] = mod_s.get(name, 0.0) \
                + sum(e - s for s, e in runs) * 1e-9 / ndev
            gap_s[name] = gap_s.get(name, 0.0) \
                + overlap(idle, between) * 1e-9 / ndev
            idle_in[name] = idle_in.get(name, 0.0) \
                + overlap(idle, xplane.union(runs)) * 1e-9 / ndev
    return {"window_s": (hi - lo) * 1e-9, "busy_s": busy,
            "idle_s": (hi - lo) * 1e-9 - busy if devices else 0.0,
            "devices": len(devices),
            "idle_gaps": [[n, t] for n, t in sorted(
                gaps.items(), key=lambda kv: -kv[1])],
            "span_s": span_s, "span_self_s": span_self_s, "span_n": span_n,
            "module_runs": runs_n, "module_s": mod_s,
            "module_gap_s": gap_s, "module_idle_in_s": idle_in}


def layers(s, loop: str, steps: int, program_spans: Sequence[str]):
    """The per-layer numbers for one loop kind, ``None`` where the trace
    holds none of their spans or programs; and the idle split around the
    loop's main program."""
    main = MAIN_PROGRAM[loop]
    span_s, gap = s["span_s"], s["module_gap_s"]
    out: Dict[str, object] = {}

    def per_step_ms(x):
        return None if x is None or steps <= 0 else 1e3 * x / steps

    if loop == "exchange":
        host = None
        if "exchange.round" in span_s:
            host = span_s["exchange.round"] - span_s.get("engine.wait", 0.0)
        out["exchange_host_ms.fleet"] = per_step_ms(host)
        out["fleet_launch_gap_ms.fleet"] = per_step_ms(gap.get(main))
    else:
        out["trainer_host_ms.train"] = per_step_ms(
            span_s.get("trainer.dispatch"))
        out["train_launch_gap_ms.train"] = per_step_ms(gap.get(main))
    if s["devices"] and main in gap:
        between, inside = gap[main], s["module_idle_in_s"][main]
        out["idle_split_s"] = {
            "total": s["idle_s"], "between_runs": between,
            "inside_runs": inside,
            "before_first_or_after_last_run":
                s["idle_s"] - between - inside}
    idle = sum(t for _, t in s["idle_gaps"])
    if idle > 0:
        out["idle_share_under_program_spans"] = sum(
            t for n, t in s["idle_gaps"] if n in program_spans) / idle
    return out


def run(spec, seed: int, seconds: float, *, t_start=None,
        impl: str = "pallas", require_tpu: bool = True,
        compile_cache: bool = True, root: str = harness.ROOT):
    """Set-up and one traced window of a cell; returns the split."""
    import jax

    t_start = time.perf_counter() if t_start is None else t_start
    cell, traffic = spec["cell"], spec["traffic"]
    device = harness.device_info(cell["chips"], require_tpu)
    if compile_cache:
        harness.configure_jax(root)
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.core import monitor

    # a program from before Monitor.span has no spans of its own; its
    # split then reads the benchmark's spans and its device programs
    program_spans = getattr(monitor, "SPAN_NAMES", ())
    spans = harness.Spans()
    ctx = harness.Context(cell=cell, cfg=spec["cfg"], traffic=traffic,
                          seed=seed, chips=cell["chips"], impl=impl,
                          spans=spans)
    loop = harness.load_module(
        os.path.join(HERE, "loops", traffic["loop"] + ".py"),
        "loop_" + traffic["loop"].replace("-", "_"))
    st = loop.setup(ctx)
    setup_s = time.perf_counter() - t_start
    trace_dir = os.path.join(root, ".bench_trace",
                             f"span-split-{cell['name']}-{seed}")
    shutil.rmtree(trace_dir, ignore_errors=True)
    spans.annotate = True
    with jax.profiler.trace(trace_dir):
        with spans("window"):
            win = loop.window(ctx, st, seconds)
    spans.annotate = False
    loop.release(st)
    s = reduce(*load(xplane.find(trace_dir),
                     harness.SPAN_NAMES + program_spans))
    shutil.rmtree(trace_dir, ignore_errors=True)
    return {"workload": cell["name"], "seed": seed, "device": device,
            "setup_s": setup_s,
            "window": {k: v for k, v in win.items()
                       if isinstance(v, (int, float))},
            "layers": layers(s, traffic["loop"], win["steps"],
                             program_spans),
            "split": s}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    try:
        out = run(harness.cell_spec(args.workload), args.seed, args.seconds,
                  t_start=T_START)
    except harness.NoDevice as e:
        print(f"span_split.py: {e}", file=sys.stderr)
        sys.exit(2)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
