"""Traced-engine vs host-oracle cross-validation (DESIGN.md §15).

The FR-FCFS window engine (and its ``win_cap=1`` in-order parity mode)
must match the pure-numpy host oracle (``repro.controller.oracle``)
EXACTLY — every scalar stat counter, ``total_cycles`` and the per-core
end times — on pinned request streams.  The fast tier pins a few
mechanism/tier/geometry combinations on short streams; the ``-m slow``
tier sweeps every registered mechanism on ~2k-request streams.  On the
``frfcfs`` tier the engine's admission attempts
(``repro.obs.admit_attempts``) equal the oracle's tries, and stay out
of the stats.
"""

import numpy as np
import pytest

from _parity import BITWISE_KEYS
from repro import obs
from repro.controller import oracle
from repro.core import aldram as aldram_lib
from repro.core import mechanisms as registry
from repro.core.dram import DRAMConfig
from repro.core.simulator import MechanismConfig, SimConfig, simulate
from repro.core.traces import TraceBatch, WorkloadSpec
from repro.workloads.generator import materialize

DRAM_2CH = DRAMConfig(n_channels=2, n_ranks=2, n_banks=8)


def assert_oracle_matches(batch, cfg):
    before = obs.admit_attempts()
    s = simulate(batch, cfg)
    after = obs.admit_attempts()
    h = oracle.run_host(batch, cfg)
    for k in BITWISE_KEYS:
        assert int(np.asarray(s[k])) == int(h[k]), (
            f"{k}: engine={int(np.asarray(s[k]))} oracle={int(h[k])}")
    assert np.array_equal(np.asarray(s["core_end"]),
                          np.asarray(h["core_end"]))
    # the engine's admission attempts are the oracle's tries, one lane
    # over every request's step, and no stat carries them
    zero = (0, 0)
    grown = {k: (a - before.get(k, zero)[0], n - before.get(k, zero)[1])
             for k, (a, n) in after.items() if (a, n) != before.get(k)}
    if cfg.controller == "frfcfs":
        assert grown == {("_run_window", obs.NO_BANK_GROUPS):
                         (h["admit_attempts"], int(batch.length.sum()))}
    else:  # the in-order engine has no admission loop
        assert grown == {}
    assert not {"admit_attempts", "attempts"} & set(s)


def _pinned_batch(n_req=160, seed=7, dram=None):
    spec = WorkloadSpec(names=("mcf_like", "omnetpp_like"), n_req=n_req,
                        seed=seed)
    return materialize(spec) if dram is None else materialize(spec, dram)


def _burst_batch(n_cores=4, n_req=96, seed=5):
    """Every core issues bursts of 8 back-to-back requests (gap 0, one
    MSHR's worth, no dependencies) at the same cycles, after pauses long
    enough for the window to drain: each burst offers 32 requests at
    once, so one step's admission loop refills a W=16 window from empty
    and all 16 attempts admit."""
    rng = np.random.default_rng(seed)
    shape = (n_cores, n_req)
    gap = np.where(np.arange(n_req) % 8 == 0, 3000, 0)
    return TraceBatch(
        gap=np.broadcast_to(gap, shape).astype(np.int32),
        bank=rng.integers(0, 8, shape, dtype=np.int32),
        row=rng.integers(0, 24, shape, dtype=np.int32),
        is_write=rng.random(shape) < 0.2,
        dep=np.zeros(shape, bool),
        next_same=np.zeros(shape, bool),
        length=np.full(n_cores, n_req, np.int32))


@pytest.mark.parametrize("mech", ["base", "chargecache", "rltl",
                                  "cc_aldram"])
@pytest.mark.parametrize("ctrl,window,stream", [
    pytest.param("inorder", 1, _pinned_batch, id="inorder-1"),
    pytest.param("frfcfs", 8, _pinned_batch, id="frfcfs-8"),
    pytest.param("frfcfs", 16, _burst_batch, id="frfcfs-16-burst")])
def test_oracle_matches_engine_exactly(mech, ctrl, window, stream):
    batch = stream()
    cfg = SimConfig(mech=MechanismConfig(kind=mech), controller=ctrl,
                    window=window)
    assert_oracle_matches(batch, cfg)


def test_oracle_legacy_refresh_closed_policy_multichannel():
    batch = _pinned_batch(dram=DRAM_2CH)
    cfg = SimConfig(mech=MechanismConfig(kind="cc_nuat"), dram=DRAM_2CH,
                    policy="closed", refresh_mode="legacy",
                    controller="frfcfs", window=4)
    assert_oracle_matches(batch, cfg)


def test_oracle_thermal_drift():
    th = aldram_lib.ThermalConfig(points=((0.0, 55.0), (0.4, 85.0),
                                          (0.8, 70.0)))
    batch = _pinned_batch(dram=DRAM_2CH)
    cfg = SimConfig(
        mech=MechanismConfig(
            kind="cc_aldram", thermal=th,
            aldram=aldram_lib.ALDRAMConfig(temperature_c=55.0)),
        dram=DRAM_2CH, controller="frfcfs", window=8)
    assert_oracle_matches(batch, cfg)


@pytest.mark.slow
@pytest.mark.parametrize("mech", registry.names())
@pytest.mark.parametrize("ctrl,window", [("inorder", 1), ("frfcfs", 8)])
def test_oracle_all_mechanisms_long_stream(mech, ctrl, window):
    """ISSUE acceptance: traced frfcfs (and the cap=1 in-order mode)
    matches the numpy oracle EXACTLY on pinned ~2k-request streams for
    every registered mechanism."""
    spec = WorkloadSpec(
        names=("mcf_like", "libquantum_like", "stream_copy_like",
               "gcc_like"), n_req=500, seed=13)
    batch = materialize(spec, DRAM_2CH)
    cfg = SimConfig(mech=MechanismConfig(kind=mech), dram=DRAM_2CH,
                    controller=ctrl, window=window)
    assert_oracle_matches(batch, cfg)
