"""CPU self-check of the DDR4 cell ``ddr4_frfcfs16.mix8`` (run with
``python -m pytest bench/tests``):

* its configuration, traffic and reference parse, and the reference
  imports nothing of the program;
* the metric ``engine_step_us.window_bg`` is wired to the cell and reads
  only a run whose window-engine steps all took the bank-group path;
* at a tiny size the program equals ``reference_ddr4`` on every compared
  number;
* planted faults in the program turn ``correct`` false: tCCD_L ignored
  (tCCD_L = tCCD_S), the bank-group mapping shifted by one bit, and the
  DDR3 clock used for the caching duration.
"""

from __future__ import annotations

import ast
import json
import os
import subprocess
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

import check  # noqa: E402
import harness  # noqa: E402
import study  # noqa: E402

SPEC = harness.load_spec()
CELL = "ddr4_frfcfs16.mix8"
METRIC = "engine_step_us.window_bg"
TINY_CFG = {"requests_per_core": 150}


def test_cell_files_parse_and_name_their_reference():
    cell, cfg, traffic, layer = harness.cell_parts(SPEC, CELL)
    assert cell["chips"] == 1
    assert layer == [(METRIC, "us")]
    ref = check.load_reference(cfg)
    assert ref.__name__ == "bench_reference_reference_ddr4"
    assert set(traffic["axes"]["mechanism"]) == set(ref.MECHANISMS)
    assert {"ccd_wait_cycles", "rrd_l_wait_cycles"} <= set(ref.STAT_KEYS)
    assert cfg["timing"]["cycle_ns"] == cfg["timing"]["tCK_ns"]
    assert cfg["geometry"]["n_bank_groups"] == 4
    conf = {c["name"]: c for c in SPEC["configs"]}[cell["config"]]
    assert conf["reduced"] == ["requests_per_core"]
    # every point of the grid is compared: 12 points, one per mix
    assert len(study.check_sample(traffic, cfg, 2 ** 31 + 1, 2)) == 12
    # the program builds the cell's base point as written
    kw = harness.program_experiment_kwargs(cfg, traffic)
    assert kw["base"].dram.n_bank_groups == 4
    assert kw["base"].mech.hcrac.caching_cycles == 1_200_000


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference_ddr4.py")) as f:
        tree = ast.parse(f.read())
    mods = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            mods |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            mods.add((node.module or "").split(".")[0])
    assert mods <= {"__future__", "bisect", "numpy"}, mods


def _ctx(module_s):
    return {"trace": {"module_s_by_device": {0: module_s}},
            "steps": 1000, "window_compiles": 0}


def test_metric_reads_only_bank_grouped_window_steps(monkeypatch):
    from repro import obs
    read = harness.load_reader(METRIC)
    ctx = _ctx({"jit__run_window_grid": 0.5})
    steps = {("_run_window_grid", obs.BANK_GROUPS): 4000}
    monkeypatch.setattr(obs, "scan_steps", lambda: dict(steps))
    assert read(ctx) == pytest.approx(500.0)
    steps[("_run_window_grid", obs.NO_BANK_GROUPS)] = 10
    assert read(ctx) is None
    steps.pop(("_run_window_grid", obs.NO_BANK_GROUPS))
    steps[("_run_grid", obs.NO_BANK_GROUPS)] = 10   # another engine
    assert read(ctx) == pytest.approx(500.0)
    monkeypatch.setattr(obs, "scan_steps", dict)     # nothing launched
    assert read(ctx) is None
    monkeypatch.delattr(obs, "scan_steps")          # an older program
    assert read(ctx) is None
    assert harness.load_reader("engine_step_us.window")(ctx) \
        == pytest.approx(500.0)


def test_tiny_cell_agrees_with_reference():
    out = harness.run_cell(CELL, 2 ** 31 + 99, 0.5, False,
                           require_tpu=False, cfg_override=TINY_CFG,
                           workers=0, log=lambda m: None)
    assert out["correct"] is True, out["checks"]
    assert out["checks"]["mismatches"] == {"value": 0, "limit": 0}


_FAULTS = r'''
import dataclasses, json, sys
sys.path[:0] = [{bench!r}, {src!r}]
import jax.numpy as jnp
import harness
from repro.core import dram, simulator as sim
from repro.core.timing import ms_to_cycles
from repro.experiment import spec
fault = {fault!r}
if fault == "tccd_l_ignored":
    params = sim.mech_params
    def mech_params(cfg, *a, **k):
        t = dataclasses.replace(cfg.timing, tCCD_L=cfg.timing.tCCD_S)
        return params(dataclasses.replace(cfg, timing=t), *a, **k)
    sim.mech_params = mech_params
elif fault == "group_shifted":
    def bank_group_of(geom, bank):
        return jnp.mod(jnp.mod(bank, geom.n_banks) // 2, geom.n_bank_groups)
    dram.bank_group_of = bank_group_of
else:
    def duration(cfg, ms):
        cfg = spec._axis_duration(cfg, ms)
        h = dataclasses.replace(cfg.mech.hcrac,
                                caching_cycles=ms_to_cycles(ms))
        return dataclasses.replace(
            cfg, mech=dataclasses.replace(cfg.mech, hcrac=h))
    spec.AXIS_BUILDERS["duration_ms"] = duration
out = harness.run_cell({cell!r}, 7654321, 0.5, False, require_tpu=False,
                       cfg_override={cfg!r}, workers=0, log=lambda m: None)
print(json.dumps({{"correct": out["correct"], "checks": out["checks"]}}))
'''


@pytest.mark.parametrize("fault", ["tccd_l_ignored", "group_shifted",
                                   "ddr3_clock_duration"])
def test_fault_makes_run_incorrect(fault):
    code = _FAULTS.format(bench=BENCH, src=os.path.join(ROOT, "src"),
                          fault=fault, cell=CELL, cfg=TINY_CFG)
    p = subprocess.run([sys.executable, "-c", code],
                       env={**os.environ, "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is False, res
    assert res["checks"]["mismatches"]["value"] > 0
