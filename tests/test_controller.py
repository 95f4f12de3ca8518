"""FR-FCFS controller-tier contract tests (DESIGN.md §15, satellite 3).

* ``win_cap=1`` riders are bitwise-identical to the in-order engine
  (the mixed-grid guarantee: in-order points riding a window-engine
  launch lose nothing).
* Cross-tier agreement for every registered mechanism on two
  geometries: same request/read/write counts, bounded cycle delta.
* FR-FCFS never reports fewer row hits than in-order on a
  locality-heavy stream (the reordering exists to harvest hits).
* The ChargeCache speedup direction is preserved on both tiers, with a
  bounded tier delta.
* Per-rank ACT streams respect tRRD and the 4-ACT tFAW window.
"""

import numpy as np
import pytest

from _parity import BITWISE_KEYS, assert_cell_matches
from repro.controller import engine as ctrl_engine
from repro.core import simulator as sim_mod
from repro.core.dram import DRAMConfig
from repro.core.simulator import (MechanismConfig, SimConfig, mech_params,
                                  sim_shape, simulate)
from repro.core import mechanisms as registry
from repro.core.traces import WorkloadSpec
from repro.experiment import Experiment
from repro.workloads.generator import materialize

DRAM_2CH = DRAMConfig(n_channels=2, n_ranks=2, n_banks=8)

#: a locality-heavy multi-core mix: streaming cores with high row-buffer
#: locality interleaving in the same banks — the workload class FR-FCFS
#: reordering exists for
LOCALITY_SPEC = WorkloadSpec(
    names=("stream_copy_like", "stream_triad_like", "lbm_like",
           "libquantum_like"), n_req=400, seed=5)


def test_win_cap1_rider_bitwise_equals_inorder():
    """An in-order point riding the window engine (traced win_cap=1, any
    static window depth) reproduces the in-order engine bitwise —
    stats, core_end AND the RLTL event digest."""
    batch = materialize(WorkloadSpec(names=("mcf_like", "gcc_like"),
                                     n_req=200, seed=3))
    cfg = SimConfig(mech=MechanismConfig(kind="rltl"))
    trace = sim_mod._device_trace(batch)
    n_steps = int(batch.length.sum())
    warmup = int(cfg.warmup_frac * n_steps)
    p = mech_params(cfg)  # controller="inorder": win_cap=1, frfcfs=False
    ref = sim_mod._run(sim_shape(cfg), p, trace, warmup, n_steps)
    for W in (1, 4):
        got = ctrl_engine._run_window(sim_shape(cfg), W, p, trace,
                                      warmup, n_steps)
        for k in sim_mod.STAT_KEYS:
            assert int(ref[0][k]) == int(got[0][k]), (W, k)
        assert np.array_equal(np.asarray(ref[1]), np.asarray(got[1]))
        rl_ref = sim_mod._rltl_np(ref[2])
        rl_got = sim_mod._rltl_np(got[2])
        assert np.array_equal(rl_ref[0], rl_got[0])
        assert int(rl_ref[1]) == int(rl_got[1])


@pytest.mark.parametrize("dram", [None, DRAM_2CH],
                         ids=["1ch", "2ch2rk"])
@pytest.mark.parametrize("mech", registry.names())
def test_cross_tier_agreement(mech, dram):
    """Both tiers simulate the same stream: identical request mix, and
    the frfcfs cycle count stays within a bounded delta of in-order
    (the tiers disagree on scheduling, not on the workload)."""
    kw = {} if dram is None else {"dram": dram}
    batch = materialize(WorkloadSpec(names=("mcf_like", "omnetpp_like"),
                                     n_req=200, seed=9),
                        *(() if dram is None else (dram,)))
    s_in = simulate(batch, SimConfig(mech=MechanismConfig(kind=mech),
                                     **kw))
    s_fr = simulate(batch, SimConfig(mech=MechanismConfig(kind=mech),
                                     controller="frfcfs", window=8, **kw))
    for k in ("n_req", "reads", "writes"):
        assert int(s_in[k]) == int(s_fr[k]), k
    ratio = s_fr["total_cycles"] / s_in["total_cycles"]
    assert 0.6 <= ratio <= 1.5, ratio


def test_frfcfs_row_hits_ge_inorder_on_locality_heavy_stream():
    batch = materialize(LOCALITY_SPEC)
    hits = {}
    for ctrl, win in (("inorder", 1), ("frfcfs", 16)):
        s = simulate(batch, SimConfig(controller=ctrl, window=win))
        hits[ctrl] = int(s["row_hits"])
    assert hits["frfcfs"] >= hits["inorder"], hits


def test_cc_speedup_direction_preserved_both_tiers():
    """ChargeCache speeds up the hot-row workload on BOTH tiers, and the
    two tiers agree on the magnitude within a documented bound (the
    §15 controller-sensitivity claim)."""
    batch = materialize(WorkloadSpec(names=("mcf_like", "mcf_like"),
                                     n_req=400, seed=17))
    sp = {}
    for ctrl, win in (("inorder", 1), ("frfcfs", 8)):
        lat = {}
        for mech in ("base", "chargecache"):
            s = simulate(batch, SimConfig(
                mech=MechanismConfig(kind=mech), controller=ctrl,
                window=win))
            lat[mech] = s["lat_sum"] / s["n_req"]
        sp[ctrl] = lat["base"] / lat["chargecache"]
    assert sp["inorder"] >= 1.0
    assert sp["frfcfs"] >= 1.0
    assert abs(sp["frfcfs"] - sp["inorder"]) < 0.15, sp


def test_trace_experiment_frfcfs_matches_simulate_one_compile():
    """A trace-driven controller × mechanism grid through ``Experiment``
    (the ``sweep()`` route: ``_run_window_batched`` with a static step
    count and traced geometry tables) runs in ONE compile and matches
    per-point ``simulate()`` bitwise, RLTL included."""
    batch = materialize(WorkloadSpec(names=("mcf_like", "omnetpp_like"),
                                     n_req=150, seed=4))
    exp = Experiment(traces=batch, rltl=True,
                     axes={"controller": ["inorder", "frfcfs"],
                           "mechanism": ["base", "chargecache", "rltl"]})
    before = ctrl_engine._run_window_batched._cache_size()
    res = exp.run()
    assert ctrl_engine._run_window_batched._cache_size() - before == 1
    _, _, cfgs = exp.expand()
    for cfg, got in zip(cfgs, res.cells.flat):
        assert_cell_matches(simulate(batch, cfg), got, rltl=True)


def test_rank_act_spacing_trrd_tfaw():
    """Every pair of ACTs to one rank is >= tRRD apart, and any five
    consecutive ACTs span >= tFAW (the per-rank sliding window)."""
    dram = DRAMConfig(n_channels=1, n_ranks=1, n_banks=8)
    batch = materialize(WorkloadSpec(
        names=("mcf_like", "stream_copy_like", "gcc_like", "lbm_like"),
        n_req=200, seed=21), dram)
    cfg = SimConfig(dram=dram, controller="frfcfs", window=8,
                    warmup_frac=0.0)
    trace = sim_mod._device_trace(batch)
    n_steps = int(batch.length.sum())
    p = mech_params(cfg)
    _, _, events, _ = ctrl_engine._run_window(
        sim_shape(cfg), cfg.window, p, trace, 0, n_steps)
    gid = np.asarray(events.act_gid)
    t = np.asarray(events.act_t)[gid >= 0]
    bank = gid[gid >= 0] // dram.n_rows
    rank = bank // dram.n_banks
    T = cfg.timing
    assert len(t) > 50  # the stream actually activates
    for r in np.unique(rank):
        ts = np.sort(t[rank == r])
        assert (np.diff(ts) >= T.tRRD).all()
        if len(ts) > ctrl_engine.FAW_DEPTH:
            span = ts[ctrl_engine.FAW_DEPTH:] - ts[:-ctrl_engine.FAW_DEPTH]
            assert (span >= T.tFAW).all()
