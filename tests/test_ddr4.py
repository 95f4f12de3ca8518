"""DDR4 bank groups on the normal launch path (DESIGN.md §16).

* ``Experiment`` grids of a small bank-grouped geometry under the
  ``DDR4_2400`` timing set equal the host oracle (``controller/oracle.py``)
  exactly on both controller tiers, ``ccd_wait_cycles`` and
  ``rrd_l_wait_cycles`` included.
* Two-request cases show tCCD_L against tCCD_S and tRRD_L against
  tRRD_S to the cycle.
* The duration axis quantises at the point's own clock, on its own base.
* A DDR3 envelope's scan step carries no bank-group state and traces to
  the same equations and carry as before bank groups existed.
* Grids that mix clocks or bank-group counts run point by point; what
  the bank-group path would silently drop is refused.
"""

import dataclasses
import hashlib

import jax
import numpy as np
import pytest

from _parity import BITWISE_KEYS
from repro import obs
from repro.controller import engine as ctrl_engine
from repro.controller import oracle
from repro.core import energy, simulator as sim_mod
from repro.core.dram import DRAMConfig
from repro.core.simulator import MechanismConfig, SimConfig, simulate
from repro.core.timing import DDR3_1600, DDR4_2400
from repro.core.traces import TraceBatch, WorkloadSpec
from repro.experiment import Experiment
from repro.experiment.spec import AXIS_BUILDERS, GEOMETRY_PRESETS
from repro.workloads.generator import materialize

#: small bank-grouped geometries: 2 groups x 2 banks per rank, on two
#: channels, and on one channel with two ranks (so (rank, group)
#: registers of different ranks must stay apart)
DDR4_2CH = DRAMConfig(n_channels=2, n_ranks=1, n_banks=4, n_bank_groups=2,
                      n_rows=4096)
DDR4_2RK = DRAMConfig(n_channels=1, n_ranks=2, n_banks=4, n_bank_groups=2,
                      n_rows=4096)
MECHS = ["base", "chargecache", "lldram"]


def _ddr4_base(dram, **kw):
    base = SimConfig(dram=dram, timing=DDR4_2400, policy="closed",
                     mech=MechanismConfig(kind="base"), **kw)
    return AXIS_BUILDERS["duration_ms"](base, 1.0)


def _assert_oracle(batch, cfg, got):
    h = oracle.run_host(batch, cfg)
    for k in BITWISE_KEYS:
        assert int(np.asarray(got[k])) == int(h[k]), (
            f"{k}: engine={int(np.asarray(got[k]))} oracle={int(h[k])}")
    assert np.array_equal(np.asarray(got["core_end"]), h["core_end"])
    return h


@pytest.mark.parametrize("dram", [DDR4_2CH, DDR4_2RK], ids=["2ch", "2rk"])
@pytest.mark.parametrize("ctrl,window", [("inorder", 1), ("frfcfs", 8),
                                         ("frfcfs", 16), ("mixed", 16)])
def test_experiment_matches_oracle_on_ddr4(dram, ctrl, window):
    """``mixed``: in-order points ride the W=16 frfcfs points' launch
    (``win_cap=1``), so lanes whose admission loops end after different
    numbers of attempts run in lockstep."""
    batch = materialize(WorkloadSpec(
        names=("stream_copy_like", "mcf_like", "lbm_like", "gcc_like"),
        n_req=120, seed=11), dram)
    axes = {"mechanism": MECHS, "duration_ms": [1.0, 4.0]}
    tier = {"controller": ctrl}
    if ctrl == "mixed":
        axes["controller"] = ["inorder", "frfcfs"]
        tier = {}
    exp = Experiment(traces={"mix": batch}, axes=axes,
                     base=_ddr4_base(dram, window=window, **tier))
    res = exp.run()
    assert res.meta["n_launches"] == 1
    _, _, cfgs = exp.expand()
    waits = {c: {"ccd": 0, "rrd_l": 0} for c in ("inorder", "frfcfs")}
    for cfg, got in zip(cfgs, res.cells[0].flat):
        h = _assert_oracle(batch, cfg, got)
        waits[cfg.controller]["ccd"] += h["ccd_wait_cycles"]
        waits[cfg.controller]["rrd_l"] += h["rrd_l_wait_cycles"]
    # the mechanism binds: column spacing on both tiers, tRRD_L only
    # where the controller reorders
    for c in {cfg.controller for cfg in cfgs}:
        assert waits[c]["ccd"] > 0
        assert (waits[c]["rrd_l"] > 0) == (c == "frfcfs")


def _two_reads(banks, timing=DDR4_2400, **kw):
    """Two cores each issue one read at cycle 0 to ``banks``; returns
    the simulator's stats (checked against the oracle)."""
    z = np.zeros((2, 1), np.int32)
    batch = TraceBatch(gap=z, bank=np.asarray(banks, np.int32)[:, None],
                       row=z + 5, is_write=z.astype(bool),
                       dep=z.astype(bool), next_same=z.astype(bool),
                       length=np.ones(2, np.int32))
    cfg = SimConfig(dram=DDR4_2CH, timing=timing, policy="open",
                    warmup_frac=0.0, mech=MechanismConfig(kind="base"), **kw)
    s = simulate(batch, cfg)
    _assert_oracle(batch, cfg, s)
    return s


def test_tccd_long_against_short_to_the_cycle():
    """Bank 0 then bank 2 (same group) or bank 1 (the other group): the
    second RD waits tCCD_L, not tCCD_S (= the BL8 burst, which the data
    bus already imposes), after the first; both banks open after the
    REF due at cycle 0."""
    T = DDR4_2400
    same = _two_reads([0, 2])
    other = _two_reads([0, 1])
    first_rd = T.tRFC + T.tRCD
    assert int(same["core_end"][1]) == first_rd + T.tCCD_L + T.tCL + T.tBL
    assert int(other["core_end"][1]) == first_rd + T.tCCD_S + T.tCL + T.tBL
    assert int(same["ccd_wait_cycles"]) == T.tCCD_L - T.tCCD_S
    assert int(other["ccd_wait_cycles"]) == 0


def test_trrd_long_against_short_to_the_cycle():
    """On the FR-FCFS tier the second ACT waits tRRD_L in the same bank
    group and tRRD (tRRD_S) in the other; in-order has no ACT window."""
    T = DDR4_2400
    same = _two_reads([0, 2], controller="frfcfs", window=4)
    other = _two_reads([0, 1], controller="frfcfs", window=4)
    first_act = T.tRFC
    assert int(same["core_end"][1]) == (first_act + T.tRRD_L + T.tRCD
                                        + T.tCL + T.tBL)
    assert int(other["core_end"][1]) == (first_act + T.tRRD + T.tRCD
                                         + T.tCL + T.tBL)
    assert int(same["rrd_l_wait_cycles"]) == T.tRRD_L - T.tRRD
    assert int(other["rrd_l_wait_cycles"]) == 0
    assert int(_two_reads([0, 2])["rrd_l_wait_cycles"]) == 0


def test_duration_axis_uses_the_points_clock():
    ddr4 = AXIS_BUILDERS["duration_ms"](
        SimConfig(dram=GEOMETRY_PRESETS["ddr4_2ch"], timing=DDR4_2400,
                  mech=MechanismConfig(kind="base")), 1.0)
    assert ddr4.mech.hcrac.caching_cycles == 1_200_000
    assert (ddr4.mech.lowered.tRCD, ddr4.mech.lowered.tRAS) == (10, 27)
    assert ddr4.mech.lowered.tCK_ns == DDR4_2400.tCK_ns
    four = AXIS_BUILDERS["duration_ms"](ddr4, 4.0)
    assert (four.mech.lowered.tRCD, four.mech.lowered.tRAS) == (11, 29)
    ddr3 = AXIS_BUILDERS["duration_ms"](SimConfig(), 1.0)
    assert ddr3.mech.hcrac.caching_cycles == 800_000
    assert (ddr3.mech.lowered.tRCD, ddr3.mech.lowered.tRAS) == (7, 18)
    assert DDR4_2400.cycles_8ms == 9_600_000
    assert DDR3_1600.cycles_8ms == 6_400_000


# --- the DDR3 step is untouched ---------------------------------------------

def _eqn_lines(jaxpr, depth=0):
    """One line per equation (recursively): primitive, its non-jaxpr
    params and its operand/result shapes — operand *kinds* (literal or
    variable) and names are left out."""
    from jax.extend import core as jcore
    subs = (jcore.ClosedJaxpr, jcore.Jaxpr)
    for e in jaxpr.eqns:
        params, inner = [], []
        for k in sorted(e.params):
            v = e.params[k]
            vs = v if isinstance(v, (tuple, list)) else (v,)
            if vs and all(isinstance(x, subs) for x in vs):
                inner += [getattr(x, "jaxpr", x) for x in vs]
                continue
            if callable(v):
                v = getattr(v, "__name__", type(v).__name__)
            params.append(f"{k}={v}")
        ins = " ".join(v.aval.str_short() for v in e.invars)
        outs = " ".join(v.aval.str_short() for v in e.outvars)
        yield f"{'  ' * depth}{e.primitive.name}[{','.join(params)}] " \
              f"{ins} -> {outs}"
        for j in inner:
            yield from _eqn_lines(j, depth + 1)


def _step_digest(fn, *args) -> str:
    """sha256 of the request scan's body: its carry shapes and every
    equation (``_eqn_lines``)."""
    from jax.extend import core as jcore
    scans = []

    def find(jx):
        for e in jx.eqns:
            if e.primitive.name == "scan" and e.params["length"] > 100:
                scans.append(e)
            for v in e.params.values():
                for x in (v if isinstance(v, (tuple, list)) else (v,)):
                    if isinstance(x, (jcore.ClosedJaxpr, jcore.Jaxpr)):
                        find(getattr(x, "jaxpr", x))
    find(jax.make_jaxpr(fn)(*args).jaxpr)
    (scan,) = scans
    body = scan.params["jaxpr"].jaxpr
    nc, ncar = scan.params["num_consts"], scan.params["num_carry"]
    carry = [v.aval.str_short() for v in body.invars[nc:nc + ncar]]
    text = "carry " + " ".join(carry) + "\n" + "\n".join(_eqn_lines(body))
    return hashlib.sha256(text.encode()).hexdigest()


#: the two engines' DDR3 step digests as taken before bank groups existed
#: (closed rows, 2 cores x 100 requests, window 4); the window step's was
#: taken again when its admission loop came to stop at the first failed
#: attempt, a change that adds no bank-group state
DDR3_STEP_DIGESTS = {
    "inorder":
        "a6fe4b327d7fdcf731978a6460004c47463c7325b698474fe733bc2082b6acd5",
    "frfcfs":
        "e7d2cfecd1a0935fbe2bfd8d5b4251e112e4ea9953d589bd0bb9f8729f375ca9",
}


@pytest.mark.parametrize("ctrl", ["inorder", "frfcfs"])
def test_ddr3_step_is_the_step_before_bank_groups(ctrl):
    batch = materialize(WorkloadSpec(names=("mcf_like", "gcc_like"),
                                     n_req=100, seed=3))
    trace = sim_mod._device_trace(batch)
    n = int(batch.length.sum())
    cfg = SimConfig(policy="closed", controller=ctrl, window=4)
    shape = sim_mod.sim_shape(cfg)
    assert shape.envelope.max_bank_groups == 1
    st = sim_mod._init_state(shape, 2, n)
    assert st.last_cas is None and st.last_cas_bg is None
    assert not set(sim_mod.BG_STAT_KEYS) & set(st.stats)
    assert ctrl_engine._init_window(shape, 2, n, 4).bg_last_act is None
    if ctrl == "inorder":
        fn = lambda p, t: sim_mod._run_impl(shape, p, t, 5, n, True)
    else:
        fn = lambda p, t: ctrl_engine._run_window_impl(shape, 4, p, t, 5,
                                                       n, True)
    assert _step_digest(fn, sim_mod.mech_params(cfg), trace) \
        == DDR3_STEP_DIGESTS[ctrl]


# --- mixed grids, refusals, the launch counter ------------------------------

def test_mixed_clock_and_group_grid_runs_point_by_point(monkeypatch):
    """DDR3 and DDR4 points in one launch (one bank-grouped envelope, two
    clocks): each equals its own run, RLTL histogram included — the
    buckets are in each point's own clock."""
    systems = {"ddr3": (DDR3_1600,
                        DRAMConfig(n_channels=2, n_banks=4, n_rows=4096)),
               "ddr4": (DDR4_2400, DDR4_2CH)}
    monkeypatch.setitem(AXIS_BUILDERS, "system", lambda cfg, v: (
        dataclasses.replace(cfg, timing=v[0], dram=v[1])))
    batch = materialize(WorkloadSpec(names=("mcf_like", "lbm_like"),
                                     n_req=120, seed=4), DDR4_2CH)
    base = SimConfig(policy="closed", mech=MechanismConfig(kind="base"),
                     controller="frfcfs", window=4)
    exp = Experiment(traces=batch, rltl=True, base=base,
                     axes={"system": systems,
                           "mechanism": ["base", "chargecache"],
                           "duration_ms": [1.0]})
    res = exp.run()
    assert res.meta["n_launches"] == 1
    steps_before = obs.scan_steps()
    _, _, cfgs = exp.expand()
    for cfg, got in zip(cfgs, res.cells.flat):
        alone = simulate(batch, cfg)
        for k in BITWISE_KEYS:
            assert int(got[k]) == int(alone[k]), (cfg.timing.tCK_ns, k)
        assert np.array_equal(got["rltl_hist"], alone["rltl_hist"])
    # alone, the DDR3 points took the path without bank groups
    steps = obs.scan_steps()
    grown = {k for k in steps if steps[k] != steps_before.get(k, 0)}
    assert grown == {("_run_window", obs.NO_BANK_GROUPS),
                     ("_run_window", obs.BANK_GROUPS)}


def test_what_the_path_would_drop_is_refused():
    """Checked on each launched point (``mech_params``), not on the
    configs an ``Experiment``'s axes pass through on the way."""
    refused = [
        ("bank-grouped geometry",
         SimConfig(timing=DDR4_2400, mech=MechanismConfig(kind="base"))),
        ("another clock", SimConfig(dram=DDR4_2CH, timing=DDR4_2400,
                                    mech=MechanismConfig(kind="lldram"))),
        ("NUAT bins", SimConfig(dram=DDR4_2CH, timing=DDR4_2400,
                                mech=MechanismConfig(kind="nuat"))),
    ]
    for match, cfg in refused:
        with pytest.raises(AssertionError, match=match):
            sim_mod.mech_params(cfg)
    with pytest.raises(AssertionError, match="split evenly"):
        DRAMConfig(n_banks=8, n_bank_groups=3)
    with pytest.raises(ValueError, match="IDD"):
        energy.energy_nj({"act_ras_sum": 0, "acts": 0, "reads": 0,
                          "writes": 0, "total_cycles": 1}, DDR4_2400)
