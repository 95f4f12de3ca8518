"""The streamed metrics of a ``reduce=`` cell, worked out from the plain
reference's integer counters.

A traffic file's ``"reduce"`` names metrics that the program computes
from integer counters and streams as floats (DESIGN.md §13).  They are
written here again from those definitions, not imported from the
program.  Each is a ratio of integer counters below 2**53, evaluated in
float64 in the same order of operations as the definition, so equal
counters give the same bits on both sides and the comparison is exact.
"""

from __future__ import annotations


def _ratio(num: int, den: int) -> float:
    return float(num) / max(den, 1)


FORMULAS = {
    "avg_latency": lambda s: _ratio(s["lat_sum"], s["n_req"]),
    "row_hit_rate": lambda s: _ratio(s["row_hits"], s["n_req"]),
    "hcrac_hit_rate": lambda s: _ratio(s["hcrac_hits"], s["hcrac_lookups"]),
    "acts_lowered_frac": lambda s: _ratio(s["acts_lowered"], s["acts"]),
    "rmpkc": lambda s: 1000.0 * s["acts"] / max(s["total_cycles"], 1),
    "ref_blocked_frac": lambda s: _ratio(s["ref_blocked_cycles"],
                                         s["total_cycles"]),
}


def value(name: str, stats: dict) -> float:
    """Metric ``name`` of one grid point from its reference counters."""
    return FORMULAS[name](stats)
