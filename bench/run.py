#!/usr/bin/env python3
"""Run one benchmark cell once, on the TPU this process is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (chip bring-up, the cell's inputs built from ``--seed``, compile
or cache load, and one warm-up study on inputs no timed study uses) is
reported as ``setup_s``.  Then design-space studies run back to back
through ``Experiment.run()`` while fewer than ``--seconds`` have passed,
and ``sim_req_per_s`` is the requests they simulated over the wall time
from the first study's start to the last study's results.  ``--trace 1``
runs the same window under the profiler and reports the cell's
per-layer metrics instead.  A seeded sample of the window's grid points
is then re-computed by the plain reference and compared exactly.

The last line of standard output is one JSON object: ``correct``,
``attempted`` (studies in the window), ``failed``, ``metrics``,
``device`` and, last, ``checks`` (each compared number with its limit).
Without a TPU, or with fewer chips than the cell asks for, the run exits
with code 2 and prints no result.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    import harness
    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), t_start=T0)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    out["checks"] = out.pop("checks")  # the compared numbers come last
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
