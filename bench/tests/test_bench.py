"""CPU self-check of the benchmark harness (not part of the repository's
tier-1 suite; run with ``python -m pytest bench/tests``).

* the copied trace generator reproduces the program's bitwise;
* every cell's study builder drives ``Experiment`` end to end at a tiny
  size and agrees exactly with the plain reference;
* without a TPU the command exits non-zero and prints nothing;
* the trace reduction reads a small recorded chip trace;
* the control (the program's legacy refresh model) is caught;
* faults planted under the timed path make ``correct`` false: answers
  altered, either half of the grid lanes or half of the mixes left out.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

import gen  # noqa: E402
import harness  # noqa: E402
import study  # noqa: E402

SPEC = harness.load_spec()
CELLS = [w["name"] for w in SPEC["workloads"]]
#: tiny sizes: 150 requests per core, at most 4 mixes per study
TINY_CFG = {"requests_per_core": 150}
#: a profile of one tiny study (8 cores x 150 requests, 2 mixes) recorded
#: on one TPU v5e the way ``--trace 1`` records it
FIXTURE = os.path.join(BENCH, "tests", "fixtures",
                       "trace_small.xplane.pb.gz")


def tiny_traffic(name):
    _, _, traffic, _ = harness.cell_parts(SPEC, name)
    return {"mixes_per_study": min(4, traffic["mixes_per_study"]),
            "check_points": min(4, traffic.get("check_points", 0))}


def run_tiny(name, seed=2 ** 31 + 99, seconds=0.5):
    return harness.run_cell(name, seed, seconds, False, require_tpu=False,
                            cfg_override=TINY_CFG,
                            traffic_override=tiny_traffic(name), workers=0,
                            log=lambda m: None)


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 3])
def test_generator_copy_matches_program(seed):
    from repro.core import traces
    names = gen.random_mixes(1, 8, seed=seed % 1000)[0]
    ours = gen.multicore_batch(names, 300, seed, 16, 65536)
    theirs = traces.multicore_batch(names, 300, seed=seed)
    for f in ours._fields:
        np.testing.assert_array_equal(getattr(ours, f), getattr(theirs, f))
    assert gen.random_mixes(3, 8) == traces.random_mixes(3, 8)


def test_studies_share_work_but_not_streams():
    _, cfg, traffic, _ = harness.cell_parts(SPEC, "inorder.mix8_rltl")
    cfg = {**cfg, **TINY_CFG}
    a = study.build_study(traffic, cfg, 5, 1)
    b = study.build_study(traffic, cfg, 5, 2)
    c = study.build_study(traffic, cfg, 6, 1)
    n = len(study.grid_points(traffic, cfg))
    assert study.work_of(a, n) == study.work_of(b, n) == study.work_of(c, n)
    assert not np.array_equal(a[0].row, b[0].row)
    assert not np.array_equal(a[0].row, c[0].row)


@pytest.mark.parametrize("name", CELLS)
def test_check_sample_spans_the_modelled_mechanisms(name):
    import reference
    _, cfg, traffic, _ = harness.cell_parts(SPEC, name)
    points = study.grid_points(traffic, cfg)
    sample = study.check_sample(traffic, cfg, 2 ** 31 + 5, 3)
    drawn = {points[p][1]["mechanism"] for _, _, p in sample}
    assert drawn == set(traffic["axes"]["mechanism"]) & set(
        reference.MECHANISMS)
    with pytest.raises(ValueError):
        reference.run(None, cfg, {"mechanism": "nuat"})


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_and_agrees_with_reference(name):
    out = run_tiny(name)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] >= 1
    assert out["metrics"]["sim_req_per_s"]["value"] > 0
    assert out["checks"]["mismatches"] == {"value": 0, "limit": 0}


def test_refuses_without_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                        "--workload", CELLS[0], "--seed", "1", "--seconds",
                        "1", "--trace", "0"], env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_trace_reduction_on_recorded_chip_trace():
    import gzip

    from jax.profiler import ProfileData

    import trace_reduce
    with gzip.open(FIXTURE) as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    red = trace_reduce.reduce_profile(pd, [0])
    assert 0 < red["busy_s"] <= red["window_s"]
    assert "jit__run_grid" in red["module_s"]
    assert red["module_s"]["jit__run_grid"] <= red["busy_s"]
    ops = dict(red["breakdown"]["device_ops"])
    assert max(ops.values()) == red["module_s"][max(ops, key=ops.get)]
    ctx = {"trace": red, "steps": 8 * 150, "window_compiles": 0}
    for m in ("engine_step_us.trace", "device_idle_share", "rltl_share"):
        v = harness.load_reader(m)(ctx)
        assert v is not None and v > 0
    assert harness.load_reader("engine_step_us.window")(ctx) is None


def test_control_is_caught():
    import control
    rows = control.readings("frfcfs16.mix8", [3, 4], require_tpu=False,
                            cfg_override=TINY_CFG,
                            traffic_override=tiny_traffic("frfcfs16.mix8"),
                            log=lambda m: None)
    assert all(sound == 0 for _, sound, _ in rows)
    assert all(ctl > 0 for _, _, ctl in rows)


_FAULTS = r'''
import json, sys
sys.path[:0] = [{bench!r}, {src!r}]
import numpy as np
import harness
from repro.core import simulator as sim
fault = {fault!r}
drain, launch = sim._drain_grid, sim._launch_grid

def altered(out, grid, batches, n_batch, reduce_keys=None):
    rows = drain(out, grid, batches, n_batch, reduce_keys)
    if reduce_keys is not None:   # streamed: the [batch, grid, deps] ints
        return rows + 1
    for row in rows:
        for cell in row:
            cell["lat_sum"] = cell["lat_sum"] + 1
    return rows

def half(shape, stacked, *a, **k):
    # the second half of the grid lanes runs the first half's points
    import jax
    n = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    pick = np.arange(n) % ((n + 1) // 2)
    stacked = jax.tree_util.tree_map(lambda x: np.asarray(x)[pick], stacked)
    return launch(shape, stacked, *a, **k)

def half_first(shape, stacked, *a, **k):
    # the first half of the grid lanes runs the second half's points
    import jax
    n = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    pick = np.arange(n)
    pick[:n // 2] = pick[n - n // 2:]
    stacked = jax.tree_util.tree_map(lambda x: np.asarray(x)[pick], stacked)
    return launch(shape, stacked, *a, **k)

def half_batch(shape, stacked, traces, warmups, *a, **k):
    # the second half of the mixes runs the first half's streams
    import jax
    n = len(warmups)
    pick = np.arange(n) % ((n + 1) // 2)
    traces = jax.tree_util.tree_map(lambda x: np.asarray(x)[pick], traces)
    return launch(shape, stacked, traces, np.asarray(warmups)[pick], *a, **k)

if fault == "altered":
    sim._drain_grid = altered
elif fault == "half":
    sim._launch_grid = half
elif fault == "half_first":
    sim._launch_grid = half_first
else:
    sim._launch_grid = half_batch
out = harness.run_cell({name!r}, 1234567, 0.5, False, require_tpu=False,
                       cfg_override={cfg!r}, traffic_override={traffic!r},
                       workers=0, log=lambda m: None)
print(json.dumps({{"correct": out["correct"], "checks": out["checks"]}}))
'''


@pytest.mark.parametrize("name,fault", [
    (c, f) for c in CELLS
    for f in ("altered", "half", "half_first", "half_batch")])
def test_fault_makes_run_incorrect(name, fault):
    traffic = tiny_traffic(name)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    code = _FAULTS.format(bench=BENCH, src=os.path.join(ROOT, "src"),
                          fault=fault, name=name, cfg=TINY_CFG,
                          traffic=traffic)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is False, res
    assert res["checks"]["mismatches"]["value"] > 0
