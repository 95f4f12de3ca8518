"""CPU self-check of what a cell may bring as data alone (run with
``python -m pytest bench/tests``):

* the streams follow the configuration's geometry, and those of the
  existing cells are pinned bit for bit;
* a configuration's ``"reference"`` names the module that decides
  ``correct``;
* a traffic file's ``"reduce"`` streams metrics that are compared
  exactly, on four virtual devices, with planted faults caught;
* ``shard_busy_spread`` and the four-chip visibility.
"""

from __future__ import annotations

import hashlib
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

import check  # noqa: E402
import harness  # noqa: E402
import study  # noqa: E402

SPEC = harness.load_spec()
TINY_CFG = {"requests_per_core": 150}
SWEEP = "inorder.sweep8_4chip"
STUB = "tests.fixtures.stub_reference"

#: sha256 of studies 0 and 1 of seeds 0 and 1 of the first two cells,
#: taken from the generator before it read the geometry from the
#: configuration (16 banks and 65,536 rows per bank fixed in code)
PINNED = {
    ("inorder.mix8_rltl", 0, 0):
        "8cf28b14768a2a694859e34cbdcc7b87d232b693d1ab8f028de06036c27517a2",
    ("inorder.mix8_rltl", 0, 1):
        "40a90f6a9811afb0049f4b24f8d480d0d6978c8d545423f0f8eb33974f1738d0",
    ("inorder.mix8_rltl", 1, 0):
        "8968e2de47524907a1ebcbf9f9ac0198b755413ea37e3e6f40d94b2b2794c78e",
    ("inorder.mix8_rltl", 1, 1):
        "7c442c7b3a05129dc7ef4054b6265af62e7f584553cb21df703660c0ec841e56",
    ("frfcfs16.mix8", 0, 0):
        "6f4a2fe6fda7d4b8c4cbbdb7717910d7632dfdbad94adb72af1b9549b42eaa7a",
    ("frfcfs16.mix8", 0, 1):
        "0a8be768e2b46fc60f489151eb16d54741f9d75c31e9f45891b24b7ca89fc8d0",
    ("frfcfs16.mix8", 1, 0):
        "6273e431ebc1b7d51181faa1df8e431c470fb680eeb3de1907eb4154396ec1c2",
    ("frfcfs16.mix8", 1, 1):
        "d7dc45993e4777ac471878e146f90901eada72048ca3b20c0b09a9549d178f43",
}


def digest(batches) -> str:
    h = hashlib.sha256()
    for b in batches:
        for f in b._fields:
            a = getattr(b, f)
            h.update(f.encode())
            h.update(str(a.dtype).encode())
            h.update(str(a.shape).encode())
            h.update(a.tobytes())
    return h.hexdigest()


def tiny_run(name, cfg=None, traffic=None, seed=2 ** 31 + 99):
    _, _, full, _ = harness.cell_parts(SPEC, name)
    traffic = {"mixes_per_study": min(4, full["mixes_per_study"]),
               "check_points": min(4, full.get("check_points", 0)),
               **(traffic or {})}
    return harness.run_cell(name, seed, 0.5, False, require_tpu=False,
                            cfg_override={**TINY_CFG, **(cfg or {})},
                            traffic_override=traffic, workers=0,
                            log=lambda m: None)


@pytest.mark.parametrize("name,seed,k", sorted(PINNED))
def test_existing_streams_are_pinned(name, seed, k):
    _, cfg, traffic, _ = harness.cell_parts(SPEC, name)
    assert digest(study.build_study(traffic, cfg, seed, k)) \
        == PINNED[(name, seed, k)]


@pytest.mark.parametrize("n_banks", [8, 16])
def test_streams_span_the_configured_banks(n_banks):
    """2 channels x 1 rank x ``n_banks`` per rank: the bank ids of a
    study's streams cover every bank and no other."""
    _, cfg, traffic, _ = harness.cell_parts(SPEC, SWEEP)
    cfg = {**cfg, **TINY_CFG,
           "geometry": {**cfg["geometry"], "n_banks": n_banks,
                        "n_rows": 32768}}
    assert study.stream_geometry(cfg) == (2 * n_banks, 32768)
    banks, rows = set(), 0
    for k in range(3):
        for b in study.build_study(traffic, cfg, 11, k):
            live = np.arange(b.bank.shape[1]) < b.length[:, None]
            banks |= set(np.unique(b.bank[live]).tolist())
            rows = max(rows, int(b.row[live].max()))
    assert banks == set(range(2 * n_banks))
    assert rows < 32768


def test_reference_defaults_to_the_plain_one():
    _, cfg, _, _ = harness.cell_parts(SPEC, SWEEP)
    assert "reference" not in cfg
    mod = check.load_reference(cfg)
    assert os.path.samefile(mod.__file__, os.path.join(BENCH, "reference.py"))
    assert {"run", "STAT_KEYS", "MECHANISMS"} <= set(vars(mod))
    with pytest.raises(ValueError):
        check.load_reference({"reference": "../reference"})


@pytest.mark.parametrize("name,n_checked", [("inorder.mix8_rltl", 4),
                                             (SWEEP, 6)])
def test_configured_reference_decides_correct(name, n_checked):
    """The stub models ``base`` alone and reads one cycle more of latency
    than the program: every sampled point is ``base`` (``check_points``
    of them, or all six of the sweep's), checked by the stub, and off by
    exactly one number."""
    stub = check.load_reference({"reference": STUB})
    stub.RAN.clear()
    out = tiny_run(name, cfg={"reference": STUB})
    assert out["correct"] is False
    assert set(stub.RAN) == {"base"}
    assert out["checks"]["mismatches"]["value"] == len(stub.RAN) == n_checked


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 11])
def test_every_point_sample_covers_the_compared_grid(seed):
    """``"check_every_point"``: each grid point of a modelled mechanism
    once, and every mix of the study (so every chip's lanes)."""
    _, cfg, traffic, _ = harness.cell_parts(SPEC, SWEEP)
    assert traffic["check_every_point"] is True
    pts = study.grid_points(traffic, cfg)
    sample = study.check_sample(traffic, cfg, seed, 3)
    modelled = check.load_reference(cfg).MECHANISMS
    compared = [j for j, (p, _) in enumerate(pts)
                if p["mechanism"] in modelled]
    assert sorted(p for _, _, p in sample) == compared
    assert len(compared) == len(pts) == 12
    assert {m for _, m, _ in sample} == set(range(traffic["mixes_per_study"]))
    assert {s for s, _, _ in sample} <= {0, 1, 2}
    assert sample != study.check_sample(traffic, cfg, seed + 1, 3)


def test_reduce_reaches_the_program():
    _, cfg, traffic, _ = harness.cell_parts(SPEC, SWEEP)
    kw = harness.program_experiment_kwargs(cfg, traffic)
    assert kw["reduce"] == tuple(traffic["reduce"])
    assert kw["rltl"] is False
    _, cfg, traffic, _ = harness.cell_parts(SPEC, "inorder.mix8_rltl")
    assert "reduce" not in harness.program_experiment_kwargs(cfg, traffic)


def test_formulas_match_the_program_bit_for_bit():
    """Integer counters near the top of their range: the benchmark's
    formulas and the program's registered ones give the same bits."""
    import formulas
    from repro.core import metrics
    rng = np.random.default_rng(5)
    for _ in range(200):
        s = {k: int(rng.integers(0, 2 ** 31 - 1)) for k in
             ("lat_sum", "n_req", "row_hits", "hcrac_hits", "hcrac_lookups",
              "acts_lowered", "acts", "total_cycles", "ref_blocked_cycles")}
        s["n_req"] = s["n_req"] % 3      # 0 and 1 denominators too
        for name in formulas.FORMULAS:
            m = metrics._METRICS[name]
            cols = [np.asarray([s[d]], np.int32) for d in m.deps]
            prog = float(np.asarray(m.fn(*cols), np.float64)[0])
            assert formulas.value(name, s) == prog, name


_REDUCE = r'''
import dataclasses, json, sys
sys.path[:0] = [{bench!r}, {src!r}]
import jax
import numpy as np
import harness
from repro.core import metrics, simulator as sim
fault = {fault!r}
if fault == "ulp":
    # one simulated lane of each mix: its avg_latency one ulp higher
    m = metrics._METRICS["avg_latency"]
    def nudged(*cols, fn=m.fn):
        v = np.array(fn(*cols), np.float64).reshape(-1)
        v[:1] = np.nextafter(v[:1], np.inf)
        return v
    metrics._METRICS["avg_latency"] = dataclasses.replace(m, fn=nudged)
elif fault == "shard":
    # the last device's lanes never run: it scans the first device's
    shard = sim._shard_grid
    def last_left_out(tree, n):
        k = -(-n // len(jax.devices()))
        def f(x):
            x = np.array(x)
            x[n - k:n] = x[:k]
            return x
        return shard(jax.tree_util.tree_map(f, tree), n)
    sim._shard_grid = last_left_out
out = harness.run_cell({name!r}, 1234567, 0.5, False, require_tpu=False,
                       cfg_override={cfg!r}, traffic_override={traffic!r},
                       workers=0, log=lambda m: None)
print(json.dumps({{"devices": len(jax.devices()), "correct": out["correct"],
                  "checks": out["checks"]}}))
'''


@pytest.mark.parametrize("fault", ["none", "ulp", "shard"])
def test_reduce_check_on_four_devices(fault):
    """The four-chip cell at a tiny size on four virtual CPU devices, one
    mix to a device: a sound run has no mismatch; a streamed metric one
    ulp off, or one shard's lanes left out, makes ``correct`` false."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    code = _REDUCE.format(bench=BENCH, src=os.path.join(ROOT, "src"),
                          fault=fault, name=SWEEP, cfg=TINY_CFG,
                          traffic={"mixes_per_study": 4, "check_points": 4})
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["devices"] == 4
    if fault == "none":
        assert res["correct"] is True, res
        assert res["checks"]["mismatches"] == {"value": 0, "limit": 0}
    else:
        assert res["correct"] is False, res
        assert res["checks"]["mismatches"]["value"] > 0


def test_shard_busy_spread():
    read = harness.load_reader("shard_busy_spread")

    def ctx(busy):
        return {"trace": {"busy_by_device": dict(enumerate(busy))},
                "steps": 1, "window_compiles": 0}
    assert read(ctx([1.0, 0.9, 1.1, 1.0])) == pytest.approx(0.2)
    assert read(ctx([2.0, 2.0, 2.0, 2.0])) == 0.0
    assert read(ctx([1.0])) is None
    assert read(ctx([0.0, 0.0])) is None


@pytest.mark.parametrize("chips,visible,bounds", [
    (1, "0", "1,1,1"), (4, "0,1,2,3", "2,2,1")])
def test_visible_chips_on_a_larger_host(monkeypatch, chips, visible, bounds):
    for k in ("TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_PROCESS_BOUNDS",
              "TPU_PROCESS_BOUNDS"):
        monkeypatch.delenv(k, raising=False)
    monkeypatch.setattr(harness.glob, "glob",
                        lambda pat: [f"/dev/accel{i}" for i in range(8)])
    harness.limit_visible_chips(chips)
    assert os.environ["TPU_VISIBLE_CHIPS"] == visible
    assert os.environ["TPU_CHIPS_PER_PROCESS_BOUNDS"] == bounds
    assert os.environ["TPU_PROCESS_BOUNDS"] == "1,1,1"
