"""Benchmark driver: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (see EXPERIMENTS.md for the
mapping to the thesis's tables/figures) and writes a machine-readable
``BENCH_results.json`` (name -> us_per_call + parsed derived values)
next to the CSV stream.  REPRO_BENCH_QUICK=1 shrinks workloads for CI
and exercises the ``sweep()`` engine end to end (sweep_bench).

**Artifact contract**: every ``BENCH_*.json`` lands at the repo root
(``common.artifact_path``), never the invoking CWD — a module that
declares an artifact and completes without writing it is a driver
*failure*, not a silent skip.  The run ends with one summary line
listing emitted vs skipped artifacts.

``--trajectory`` appends one summary entry (timestamp, git sha, the
flat numbers of every BENCH artifact) to ``BENCH_trajectory.json``
after the run; ``--trajectory-only`` records the artifacts already on
disk without running anything (the CI recorder step).
"""

from __future__ import annotations

import json
import os
import sys
import time
import traceback

from benchmarks import common as C

RESULTS_JSON = C.artifact_path(
    os.environ.get("REPRO_BENCH_JSON", "BENCH_results.json"))


def _parse_derived(derived: str) -> dict:
    """Best-effort ``k=v;k2=v2`` -> dict with numeric values parsed."""
    out = {}
    for item in derived.split(";"):
        if "=" not in item:
            continue
        k, _, v = item.partition("=")
        try:
            out[k] = float(v)
        except ValueError:
            out[k] = v
    return out


def _record(results: dict, row: str) -> None:
    name, _, rest = row.partition(",")
    us, _, derived = rest.partition(",")
    try:
        us_val = float(us)
    except ValueError:
        us_val = None
    results[name] = {"us_per_call": us_val, "derived": derived,
                     "values": _parse_derived(derived)}


def _artifact_summaries() -> dict:
    """Flat numeric top-level values of every ``BENCH_*.json`` artifact
    at the repo root (the trajectory's per-run payload) — nested
    structures are skipped, so artifacts opt in to the trajectory by
    keeping their headline numbers flat (e.g. ``BENCH_megasweep.json``'s
    points/sec, speedup and peak host memory scalars)."""
    out: dict = {}
    for name in sorted(os.listdir(C.REPO_ROOT)):
        if not (name.startswith("BENCH_") and name.endswith(".json")):
            continue
        if name == TRAJECTORY_JSON_NAME:
            continue
        try:
            with open(C.artifact_path(name)) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            out[name] = {"error": "unreadable"}
            continue
        if isinstance(doc, dict):
            out[name] = {k: v for k, v in doc.items()
                         if isinstance(v, (int, float, bool))}
    return out


TRAJECTORY_JSON_NAME = "BENCH_trajectory.json"


def _git_sha() -> str | None:
    import subprocess
    try:
        p = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                           cwd=C.REPO_ROOT, capture_output=True, text=True)
        return p.stdout.strip() or None
    except OSError:
        return None


def append_trajectory() -> str:
    """Append one summary entry (timestamp, git sha, quick flag, the
    flat numbers of every BENCH artifact) to ``BENCH_trajectory.json``
    — the per-PR perf trajectory the repo carries forward.  The file is
    a JSON *array* of entries; appending re-reads and rewrites it (it
    stays small: one entry per recorded run)."""
    path = C.artifact_path(TRAJECTORY_JSON_NAME)
    entries = []
    if os.path.exists(path):
        try:
            with open(path) as f:
                entries = json.load(f)
            assert isinstance(entries, list)
        except (ValueError, AssertionError):
            entries = []
    entries.append({
        "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "git": _git_sha(),
        "quick": C.QUICK,
        "artifacts": _artifact_summaries(),
    })
    with open(path, "w") as f:
        json.dump(entries, f, indent=2, sort_keys=True)
    print(f"# trajectory: appended entry {len(entries)} to {path}",
          flush=True)
    return path


def main() -> None:
    if "--trajectory-only" in sys.argv:
        # record the current artifacts without re-running anything
        append_trajectory()
        return
    from benchmarks import (aldram, capacity, charge_model_bench, duration,
                            energy, frfcfs, geometry, kernels_bench,
                            megasweep, refresh, rltl, serving_loop,
                            serving_trace, simstep_bench, speedup,
                            sweep_bench, workloads)
    # (name, module, declared BENCH_* artifacts the module must emit)
    mods = [
        ("charge_model", charge_model_bench, ()),
        ("rltl", rltl, ()),
        ("sweep", sweep_bench, ()),
        ("speedup", speedup, ()),
        ("energy", energy, ()),
        ("capacity", capacity, ()),
        ("duration", duration, ()),
        ("geometry", geometry, ("BENCH_geometry.json",)),
        ("aldram", aldram, ("BENCH_aldram.json",)),
        ("refresh", refresh, ("BENCH_refresh.json",)),
        ("frfcfs", frfcfs, ("BENCH_frfcfs.json",)),
        ("workloads", workloads, ("BENCH_workloads.json",)),
        ("simstep", simstep_bench, ("BENCH_simstep.json",)),
        ("serving", serving_trace, ()),
        ("serving_loop", serving_loop, ("BENCH_serving.json",)),
        ("kernels", kernels_bench, ()),
        ("megasweep", megasweep, ("BENCH_megasweep.json",)),
    ]
    print("name,us_per_call,derived")
    results: dict = {}
    failed, missing = [], []
    emitted, skipped = [], []
    for name, mod, artifacts in mods:
        t_start = time.time()
        try:
            for row in mod.run():
                print(row, flush=True)
                _record(results, row)
        except Exception as e:
            failed.append(name)
            traceback.print_exc()
            print(f"{name},0,ERROR:{type(e).__name__}", flush=True)
            results[name] = {"us_per_call": None, "derived": None,
                             "error": type(e).__name__}
            skipped.extend(artifacts)
            continue
        for art in artifacts:
            path = C.artifact_path(art)
            # freshness guard: a stale artifact from an earlier run must
            # not mask a module that stopped emitting
            if os.path.exists(path) and os.path.getmtime(path) >= t_start:
                emitted.append(art)
            else:
                # the module "succeeded" without its declared artifact —
                # exactly the silent-miss mode PRs 3-5 shipped with
                missing.append(art)
    with open(RESULTS_JSON, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
    emitted.append(os.path.basename(RESULTS_JSON))
    print(f"# wrote {RESULTS_JSON} ({len(results)} entries)", flush=True)
    print("# artifacts: emitted=[" + ", ".join(emitted) + "]"
          + " skipped=[" + ", ".join(skipped) + "]"
          + " MISSING=[" + ", ".join(missing) + "]", flush=True)
    if missing:
        print(f"# FATAL: {len(missing)} declared artifact(s) silently "
              f"missing: {missing}", flush=True)
    if "--trajectory" in sys.argv:
        append_trajectory()
    if failed or missing:
        sys.exit(1)


if __name__ == "__main__":
    main()
