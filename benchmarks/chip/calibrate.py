#!/usr/bin/env python3
"""Readings that the limits of ``limits/<cell>.json`` are set from.

    python3 benchmarks/chip/calibrate.py --workload <name> \\
        --seeds 11,12,...  [--control 3] [--fault <name>] [--seconds 2]

One process on the chip runs the cell once per seed, each a whole run
of ``harness.run_spec`` (set-up, a window of ``--seconds``, the check),
and records the numbers compared: the program's, which set each limit's
lower reading, and for the first ``--control`` seeds the control's, the
reference computed in bfloat16 and put in the program's place, which
sets the upper reading.  ``--fault`` plants one of ``faults.FAULTS`` in
the program for every seed instead.  One JSON object per seed goes to
standard output, and is appended to ``--out`` where that is given.  The
benchmark's own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--fault", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import faults
    import harness
    import reference as ref

    # the faults patch the program before the first run imports it
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    spec = harness.cell_spec(args.workload)
    loop = harness.load_module(
        os.path.join(harness.HERE, "loops", spec["traffic"]["loop"] + ".py"),
        "loop_calibrate")
    t0 = T_START
    with (open(args.out, "a") if args.out else contextlib.nullcontext()) as f:
        for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
            plant = faults.planted(args.fault, spec["traffic"]["loop"],
                                   spec["cell"]["chips"]) \
                if args.fault else contextlib.nullcontext()
            with plant:
                result, readings, st, ctx = harness.run_spec(
                    spec, seed, args.seconds, False, t_start=t0,
                    keep_state=True)
            row = {"workload": args.workload, "seed": seed,
                   "fault": args.fault or None, "program": readings,
                   "correct": result["correct"],
                   "metrics": result["metrics"],
                   "diagnostics": result["diagnostics"],
                   "device": result["device"]}
            if i < args.control:
                t = time.perf_counter()
                row["control"] = loop.readings(ctx, st, dtype=ref.BF16,
                                               limits=spec["limits"])
                row["control_s"] = time.perf_counter() - t
            line = json.dumps(row, default=float)
            print(line, flush=True)
            if f is not None:
                f.write(line + "\n")
            t0 = time.perf_counter()


if __name__ == "__main__":
    main()
