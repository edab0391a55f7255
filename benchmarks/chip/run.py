#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

From the root of a checkout.  Set-up (imports, TPU start, weights and
data from the seed, compile or cache load, warm-up) is timed from the
start of this script; then the cell's loop runs for ``--seconds``.  With
``--trace 0`` the line reports the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  After the window the reference checks what the timed path
produced.  The last lines on standard error and the ``checks`` key, last
in the result line, give each number compared beside its limit.  The
last line on standard output is the result, one JSON object.

Exits non-zero and prints no result without a TPU, or with fewer chips
than the cell asks for: there is no CPU fallback.  So it does where a
program compiles inside the window.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness

    spec = harness.cell_spec(args.workload)
    try:
        result, _, _, _ = harness.run_spec(
            spec, args.seed, args.seconds, bool(args.trace),
            t_start=T_START)
    except harness.NoDevice as e:
        print(f"run.py: {e}", file=sys.stderr)
        sys.exit(2)
    except harness.WindowCompiled as e:
        print(f"run.py: {e}", file=sys.stderr)
        sys.exit(3)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
