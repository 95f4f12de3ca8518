"""DRAM timing parameters and derived (lowered) parameter sets.

All timings are expressed in DRAM *bus cycles* of the timing set's own
clock ``tCK_ns``: DDR3-1600 (800 MHz bus, 1.25 ns per cycle) matches
Table 5.1 of the thesis (tRCD/tRAS = 11/28 cycles); DDR4-2400 (1,200 MHz,
5/6 ns) adds the bank-group timings tCCD_S/tCCD_L and tRRD_L (JESD79-4,
DESIGN.md §16).  The ChargeCache-lowered set (hit in the HCRAC within the
caching duration) reduces tRCD/tRAS by 4/8 cycles at a 1 ms caching
duration (Table 5.1); other caching durations take Table 6.1's ns values
quantised at the timing set's clock (``lowered_for_duration``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple

import jax.numpy as jnp


CYCLE_NS = 1.25  # DDR3-1600: 800 MHz bus clock (the default clock)


@dataclasses.dataclass(frozen=True)
class TimingParams:
    """DRAM timing parameters in bus cycles."""

    tRCD: int = 11   # ACT -> READ/WRITE
    tRAS: int = 28   # ACT -> PRE
    tRP: int = 11    # PRE -> ACT
    tCL: int = 11    # READ -> first data
    tCWL: int = 8    # WRITE -> first data
    tBL: int = 4     # burst length on the data bus (BL8 @ DDR)
    tRTP: int = 6    # READ -> PRE
    tWR: int = 12    # end of write burst -> PRE
    #: rank-level ACT spacing (DDR3-1600 speed bin): consumed by the
    #: FR-FCFS controller tier (DESIGN.md §15) — the in-order tier keeps
    #: its documented approximation and never reads them
    tRRD: int = 6    # ACT -> ACT, same rank (7.5 ns)
    tFAW: int = 32   # four-ACT window per rank (40 ns)
    tREFI: int = 6240   # refresh interval (7.8 us)
    tRFC: int = 208     # refresh cycle time (260 ns, 4 Gb device)
    n_refresh_groups: int = 8192  # rows refreshed per retention window
    #: the bus clock every cycle count above is in (ns per cycle); every
    #: ms/ns -> cycle conversion of a point uses it (DESIGN.md §16)
    tCK_ns: float = CYCLE_NS
    #: bank-group timings (DDR4, DESIGN.md §16): column command to column
    #: command on the channel (tCCD_S) and within one bank group of a
    #: rank (tCCD_L); ACT to ACT within one bank group (tRRD_L, FR-FCFS
    #: tier only; ``tRRD`` is the any-group value).  0 = no such rule,
    #: the DDR3 model; a nonzero value needs ``DRAMConfig.n_bank_groups``
    #: > 1, the only geometry that compiles the bank-group path
    tCCD_S: int = 0
    tCCD_L: int = 0
    tRRD_L: int = 0

    @property
    def tRC(self) -> int:
        return self.tRAS + self.tRP

    @property
    def retention_cycles(self) -> int:
        """Full retention / refresh window (64 ms)."""
        return self.tREFI * self.n_refresh_groups

    @property
    def cycles_8ms(self) -> int:
        """8 ms at this clock: the ``refresh8ms_acts`` window."""
        return ms_to_cycles(8.0, self.tCK_ns)

    @property
    def bank_grouped(self) -> bool:
        """Whether any bank-group timing rule is set."""
        return bool(self.tCCD_S or self.tCCD_L or self.tRRD_L)

    def with_reduction(self, d_rcd: int, d_ras: int) -> "TimingParams":
        return dataclasses.replace(
            self, tRCD=max(1, self.tRCD - d_rcd), tRAS=max(1, self.tRAS - d_ras)
        )


class TimingVec(NamedTuple):
    """Traced (vmappable) view of ``TimingParams``: same field names, each
    an int32 scalar array, so the simulator's arithmetic is identical but
    the values are data — a whole timing sweep stacks into one ``TimingVec``
    of ``[grid]`` arrays and compiles once (DESIGN.md §4)."""
    tRCD: jnp.ndarray
    tRAS: jnp.ndarray
    tRP: jnp.ndarray
    tCL: jnp.ndarray
    tCWL: jnp.ndarray
    tBL: jnp.ndarray
    tRTP: jnp.ndarray
    tWR: jnp.ndarray
    tRRD: jnp.ndarray
    tFAW: jnp.ndarray
    tREFI: jnp.ndarray
    tRFC: jnp.ndarray
    n_refresh_groups: jnp.ndarray
    retention_cycles: jnp.ndarray
    cycles_8ms: jnp.ndarray
    tCCD_S: jnp.ndarray
    tCCD_L: jnp.ndarray
    tRRD_L: jnp.ndarray


def traced(tp: TimingParams) -> TimingVec:
    """The traced-params view of a concrete ``TimingParams``."""
    return TimingVec(*(jnp.int32(getattr(tp, f)) for f in TimingVec._fields))


def with_refresh_pressure(tp: TimingParams, factor: float) -> TimingParams:
    """Timings with the refresh interval scaled by ``1/factor`` — factor
    2/4 mirrors the DDR4 high-temperature 2x/4x refresh modes.

    ``n_refresh_groups`` is unchanged, so the retention window shrinks
    with ``tREFI``: rows are younger on average and both the REF
    blackout share (``tRFC/tREFI``) and the charge-headroom mechanisms'
    opportunity grow — the refresh-pressure axis of
    ``benchmarks/refresh.py`` (DESIGN.md §14).
    """
    assert factor >= 1.0, "refresh pressure only shortens tREFI"
    return dataclasses.replace(
        tp, tREFI=max(tp.tRFC + 1, int(round(tp.tREFI / factor))))


#: Baseline DDR3-1600 timings (Table 5.1).
DDR3_1600 = TimingParams()

#: ChargeCache-lowered timings at the default 1 ms caching duration
#: (Table 5.1: tRCD/tRAS reduction of 4/8 cycles).
DDR3_1600_CC_1MS = DDR3_1600.with_reduction(4, 8)

#: DDR4-2400R (16-16-16), 8 Gb x8 (1 KB page), four bank groups — JEDEC
#: JESD79-4's speed bin and timing tables, in clocks of tCK = 5/6 ns
#: (each with the ns figure it comes from; tCWL is the first CWL of the
#: 2400 bin, an assumption).  Pair it with a ``DRAMConfig`` that has
#: ``n_bank_groups=4`` (``spec.GEOMETRY_PRESETS["ddr4_2ch"]``).
DDR4_2400 = TimingParams(
    tCK_ns=5.0 / 6.0,
    tRCD=16, tRP=16, tCL=16,   # 13.32 ns
    tRAS=39,                   # 32 ns
    tCWL=12,                   # CWL 12 (assumed)
    tBL=4,                     # BL8
    tRTP=9,                    # max(4 nCK, 7.5 ns)
    tWR=18,                    # 15 ns
    tRRD=4,                    # tRRD_S: max(4 nCK, 3.3 ns)
    tRRD_L=6,                  # max(4 nCK, 4.9 ns)
    tCCD_S=4,                  # 4 nCK
    tCCD_L=6,                  # max(5 nCK, 5 ns)
    tFAW=26,                   # 21 ns, 1 KB page
    tREFI=9360,                # 7.8 us
    tRFC=420,                  # tRFC1 350 ns, 8 Gb
    n_refresh_groups=8192,     # 64 ms / tREFI
)


def ns_to_cycles(ns: float, tck_ns: float = CYCLE_NS) -> int:
    """Quantize a nanosecond timing to (ceil) bus cycles of ``tck_ns``."""
    return int(math.ceil(ns / tck_ns - 1e-9))


def ms_to_cycles(ms: float, tck_ns: float = CYCLE_NS) -> int:
    return int(round(ms * 1e6 / tck_ns))


def cycles_to_ms(cycles: float, tck_ns: float = CYCLE_NS) -> float:
    return cycles * tck_ns / 1e6


# --- Table 6.1 of the thesis (SPICE-derived ns values) -----------------
#: caching duration (ms) -> (tRCD ns, tRAS ns).  The baseline row is the
#: DDR3 spec (13.75 ns / 35 ns).  These are the published values; the
#: charge model reproduces them (see tests/test_charge_model.py).
TABLE_6_1 = {
    None: (13.75, 35.0),
    1.0: (8.0, 22.0),
    4.0: (9.0, 24.0),
    16.0: (11.0, 28.0),
}


def lowered_for_duration(duration_ms: float,
                         base: TimingParams = DDR3_1600) -> TimingParams:
    """Lowered TimingParams for a caching duration, per Table 6.1: the
    published ns values quantised at ``base``'s clock and applied to
    ``base`` (never above its own tRCD/tRAS).

    Durations between published points use the next-larger published
    duration (conservative).  Durations > 16 ms fall back to baseline.
    Table 6.1 comes from the thesis's DDR3 cell model; for another
    standard it is an assumption (DESIGN.md §16).
    """
    for d in (1.0, 4.0, 16.0):
        if duration_ms <= d + 1e-9:
            rcd_ns, ras_ns = TABLE_6_1[d]
            return dataclasses.replace(
                base, tRCD=min(base.tRCD, ns_to_cycles(rcd_ns, base.tCK_ns)),
                tRAS=min(base.tRAS, ns_to_cycles(ras_ns, base.tCK_ns)))
    return base
