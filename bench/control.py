#!/usr/bin/env python3
"""The control of the ``correct`` comparison, and the readings its limit
is set from.

    python3 bench/control.py --workload <cell> --seeds 11 12 13 ...

For each seed it builds one study of the cell at the cell's own size and
runs it twice through ``Experiment.run()``: as the configuration states
it, and as the control — the program's own cheaper refresh model
(``refresh_mode="legacy"``, a closed-form blackout in place of the
stateful per-bank REF schedule), which breaks the configuration's stated
refresh guarantee.  Both are compared with the plain reference on the
run's seeded sample of grid points, and one JSON line per seed gives
both readings of the compared number.  The sound readings are the lower
ones, the control's the upper ones; the benchmark's own runs never run
the control.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def readings(name, seeds, require_tpu=True, cfg_override=None,
             traffic_override=None, log=print):
    """``[(seed, sound mismatches, control mismatches), ...]``."""
    import check
    import harness
    import study

    spec = harness.load_spec()
    cell, cfg, traffic, _ = harness.cell_parts(spec, name)
    cfg = {**cfg, **(cfg_override or {})}
    traffic = {**traffic, **(traffic_override or {})}
    if require_tpu:
        harness.limit_visible_chips(int(cell["chips"]))
        harness.pin_compile_cache()
        harness.module_level_tracing()
    pools = {s: harness.StudyPool(traffic, cfg, s, 0) for s in seeds}
    import jax
    dev = jax.devices()
    if require_tpu and (dev[0].platform != "tpu"
                        or len(dev) < int(cell["chips"])):
        raise harness.NoChip(f"{name} needs {cell['chips']} TPU chip(s)")
    kw = harness.program_experiment_kwargs(cfg, traffic)
    ctl = {**kw, "base": dataclasses.replace(kw["base"],
                                             refresh_mode="legacy")}
    points = study.grid_points(traffic, cfg)
    out = []
    for s in seeds:
        inputs = {1: pools[s].get(1)}
        sample = study.check_sample(traffic, cfg, s, 1)
        row = [s]
        for args in (kw, ctl):
            res = harness.run_study(inputs[1], args)
            v = check.compare([res], inputs, sample, points, cfg, traffic)
            row.append(v["checks"]["mismatches"]["value"])
        log(json.dumps({"seed": s, "sound": row[1], "control": row[2]}))
        out.append(tuple(row))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    import harness
    try:
        rows = readings(args.workload, args.seeds,
                        log=lambda m: print(m, flush=True))
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    sound = [r[1] for r in rows]
    control = [r[2] for r in rows]
    print(json.dumps({"workload": args.workload, "seeds": len(rows),
                      "lower": max(sound), "upper": min(control),
                      "seconds": time.perf_counter() - T0}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
