"""Streaming mega-sweep engine characterization (DESIGN.md §13).

Two arms per grid size, both in this process (a device belongs to one
process, and ``benchmarks/run.py`` has already used it when it calls
this module):

* ``full``      — the materialized object-cell path, ``pipeline_depth=0``
  (the pre-§13 blocking serial loop): per-point stats dicts, host-side
  finalization of every grid point;
* ``streamed``  — ``reduce=`` on-device metric reduction + the
  double-buffered chunk pipeline + a ``ResultsWriter`` JSONL sink:
  the host only ever holds ``[chunk, n_deps]`` integer columns and the
  O(grid × n_metrics) float arrays.

Each arm runs twice: once timed, with nothing instrumented, and once
under ``tracemalloc`` for its peak host allocation (Python objects and
numpy buffers, reset per arm — so one arm's leftovers never count
against the next, which a process-wide RSS peak could not promise).
The two arms' metric arrays are compared bitwise (the §13 parity claim,
at benchmark scale), and the headline is asserted: streamed+pipelined
≥ 1.2× points/sec over the blocking materialized path at the
10⁵-point size (full runs only — REPRO_BENCH_QUICK shrinks the grid
below where the ratio is stable and only smoke-checks parity +
memory).  Each arm's ~200 chunk launches ride one compilation, counted
over all of that arm's runs.

Artifact: ``BENCH_megasweep.json`` (flat scalars so the trajectory
recorder in ``benchmarks/run.py`` can pick them up).
"""

from __future__ import annotations

import json
import os
import tempfile
import tracemalloc

import numpy as np

from benchmarks import common as C

#: metrics every arm materializes; the streamed arm lowers exactly their
#: integer ingredients on device (metrics registry, DESIGN.md §13)
METRICS = ("avg_latency", "row_hit_rate", "total_cycles")
CAPS = (64, 128, 256, 1024)
N_DUR = 125  # capacity x duration = 500 distinct canonical configs
CHUNK = 512
N_REQ = 16  # short per-point streams: launch economics dominate


def _experiment(mode: str, n_points: int):
    from repro.core.traces import single_core_batch
    from repro.experiment import Experiment
    from repro.experiment.spec import AXIS_BUILDERS, register_axis

    if "rep" not in AXIS_BUILDERS:
        # label-only replication: a mega-grid's seeds/replicas dimension.
        # Param staging dedups by canonical config (`_stack_cached`), so
        # the 500 distinct configs stage once while every replica still
        # LAUNCHES (dedup=False) — exactly the regime the streaming
        # engine targets; per-point param derivation is §7's problem,
        # not §13's, and must not mask the launch/drain economics here.
        register_axis("rep")(lambda cfg, v: cfg)

    durs = tuple(np.round(np.linspace(0.5, 8.0, N_DUR), 6).tolist())
    reps = max(1, n_points // (len(CAPS) * N_DUR))
    batch = single_core_batch("stream_copy_like", N_REQ, seed=0)
    kw = dict(reduce=METRICS, pipeline_depth=2) if mode == "streamed" \
        else dict(pipeline_depth=0)
    return Experiment(
        traces=batch,
        base=C.sim_cfg("chargecache", 1),
        axes={"capacity": CAPS, "duration_ms": durs,
              "rep": tuple(range(reps))},
        metrics=METRICS, chunk_size=CHUNK, dedup=False, **kw)


def _run_arm(mode: str, n_points: int, tmp: str, trace_memory: bool):
    """One run of one arm: ``(results, seconds, compiles, peak_mb)``;
    ``peak_mb`` is the arm's peak traced host allocation, or None."""
    exp = _experiment(mode, n_points)
    run_kw = ({"stream_to": os.path.join(tmp, f"{n_points}.jsonl")}
              if mode == "streamed" else {})
    if trace_memory:
        tracemalloc.start()
    try:
        (res, compiles), us = C.timed(C.compile_counted, exp.run, **run_kw)
        peak_mb = (tracemalloc.get_traced_memory()[1] / 2**20
                   if trace_memory else None)
    finally:
        if trace_memory:
            tracemalloc.stop()
    assert res.meta["n_chunks"] >= 2, res.meta
    assert res.streamed == (mode == "streamed")
    return res, us / 1e6, compiles, peak_mb


def run() -> list[str]:
    sizes = (2_000, 10_000) if C.QUICK else (10_000, 100_000)
    art: dict = {"quick": C.QUICK, "chunk": CHUNK, "n_req": N_REQ,
                 "metrics": list(METRICS)}
    rows = []
    growth: dict = {}
    compiles = {"full": 0, "streamed": 0}
    with tempfile.TemporaryDirectory() as tmp:
        for n in sizes:
            arm = {}
            for mode in ("full", "streamed"):
                res, sec, c1, _ = _run_arm(mode, n, tmp, False)
                _, _, c2, peak_mb = _run_arm(mode, n, tmp, True)
                compiles[mode] += c1 + c2
                pts = int(np.prod(res.shape))
                arm[mode] = {"res": res, "sec": sec, "n_points": pts,
                             "pps": pts / sec, "peak_mb": peak_mb}
            full, streamed = arm["full"], arm["streamed"]
            assert full["n_points"] == streamed["n_points"]
            for m in METRICS:
                assert np.array_equal(full["res"].metric(m),
                                      streamed["res"].metric(m)), (
                    f"streamed metrics diverge from materialized at "
                    f"n={n}, metric {m!r}")
            speedup = streamed["pps"] / full["pps"]
            for mode, r in arm.items():
                art[f"pps_{mode}_{n}"] = round(r["pps"], 1)
                art[f"peak_mb_{mode}_{n}"] = round(r["peak_mb"], 1)
                growth.setdefault(mode, []).append(r["peak_mb"])
            art[f"speedup_{n}"] = round(speedup, 3)
            # streamed never holds the object cells the full arm does
            assert streamed["peak_mb"] <= full["peak_mb"] * 1.05, (
                f"streamed peak host allocation {streamed['peak_mb']:.0f} "
                f"MB above materialized {full['peak_mb']:.0f} MB at n={n}")
            rows.append(C.csv_row(
                f"megasweep_{n}", full["sec"] * 1e6,
                f"pps_full={full['pps']:.0f}"
                f";pps_streamed={streamed['pps']:.0f}"
                f";speedup={speedup:.2f}"
                f";peak_full_mb={full['peak_mb']:.0f}"
                f";peak_streamed_mb={streamed['peak_mb']:.0f}"
                f";chunks={streamed['res'].meta['n_chunks']};compiles=1"))
    for mode, c in compiles.items():
        assert c == 1, (
            f"every {mode} chunk launch must share one compilation, "
            f"saw {c}")
    # peak host memory scales with the chunk, not the grid: the streamed
    # arm's growth across a {10,5}x grid stays far below the full arm's
    # O(grid) object-cell growth
    for mode in ("full", "streamed"):
        art[f"peak_growth_mb_{mode}"] = round(
            growth[mode][-1] - growth[mode][0], 1)
    if not C.QUICK:
        big = sizes[-1]
        assert art[f"speedup_{big}"] >= 1.2, (
            f"streamed+pipelined must be >= 1.2x the blocking "
            f"materialized path at {big} points, got "
            f"{art[f'speedup_{big}']:.2f}x")
    with open(C.artifact_path("BENCH_megasweep.json"), "w") as f:
        json.dump(art, f, indent=2, sort_keys=True)
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
