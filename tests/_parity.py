"""Shared bitwise-parity helpers for the engine test suites.

Every launch mode of the engine — per-config ``simulate()``, vmapped
``sweep()``/``sweep_traces()``, chunked ``Experiment.run()``, padded
geometry envelopes, and the streamed synthetic path (``sweep_synth``) —
must produce *bitwise identical* stats.  The exact-int key list lives
here ONCE: when the simulator grows a new scan accumulator, add it to
``BITWISE_KEYS`` and every parity suite (test_sweep / test_experiment /
test_geometry / test_aldram / test_workloads) checks it in lockstep.
"""

import numpy as np

#: every exact-int stat the scan accumulates, shared by all parity tests
BITWISE_KEYS = ("n_req", "lat_sum", "acts", "acts_lowered", "hcrac_hits",
                "hcrac_lookups", "row_hits", "row_closed", "row_conflicts",
                "reads", "writes", "pres", "act_ras_sum", "refresh8ms_acts",
                "refs_issued", "ref_blocked_cycles", "ccd_wait_cycles",
                "rrd_l_wait_cycles", "total_cycles")


def assert_cell_matches(ref: dict, got: dict, rltl: bool = False):
    """Bitwise equality of two stats dicts; ``rltl=True`` also compares
    the RLTL post-pass outputs (only meaningful when events were
    collected on both sides)."""
    for k in BITWISE_KEYS:
        assert int(ref[k]) == int(got[k]), k
    assert np.array_equal(ref["core_end"], got["core_end"])
    if rltl:
        assert int(ref["rltl_total"]) == int(got["rltl_total"])
        assert np.array_equal(ref["rltl_hist"], got["rltl_hist"])
