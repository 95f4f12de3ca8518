"""Bitline / sense-amplifier charge model (the SPICE stand-in).

The thesis derives lowered tRCD/tRAS values from 55 nm SPICE simulations of
the DRAM sense amplifier (Fig 4.2, Table 6.1).  SPICE is not available in
this environment, so we model the same observables with a calibrated
dynamical model:

1. **Cell leakage** after PRE: a stretched exponential toward Vdd/2
   (DRAM retention is famously sub-exponential [Liu+ ISCA'13]):

       V_cell(d) = Vdd/2 + (Vdd/2) * exp(-(d / TAU_LEAK)^BETA)

2. **Charge sharing** on ACT: the bitline (precharged to Vdd/2) moves by

       delta(d) = COUPLING * (V_cell(d) - Vdd/2),   COUPLING = Cc/(Cc+Cb)

3. **Sense amplification**: positive-feedback latch, exponential growth of
   the bitline deviation until the ready-to-access margin V_RM is reached:

       t_ready(d) = T0 + TAU_SA * ln(V_RM / delta(d))

4. **Restoration** (tRAS): ready time plus a first-order restore tail
   proportional to the charge deficit:

       t_restore(d) = t_ready(d) + RAS_A + RAS_B * (Vdd - V_cell(d))

Constants are least-squares calibrated so the model reproduces the
thesis's published Table 6.1 (tRCD rmse 0.07 ns, tRAS rmse 0.39 ns over the
1/4/16/64 ms points).  The same waveform is also integrated numerically
with ``jax.lax.scan`` (``bitline_waveform``) and cross-checked against the
closed form in tests.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import timing as timing_lib

VDD = 1.2
VHALF = VDD / 2.0
COUPLING = 0.125          # Cc / (Cc + Cb)
V_READY_MARGIN = 0.25 * VDD  # bitline deviation treated as "ready to access"

# Calibrated to Table 6.1 (see module docstring).
TAU_LEAK_MS = 2603.7
BETA = 0.324
T0_NS = -30.2915          # affine offset absorbing wordline rise / overdrive
TAU_SA_NS = 26.1119
RAS_A_NS = 10.6195
RAS_B_NS_PER_V = 66.0217

#: Restore threshold used by the scan integrator for the tRAS point.
RESTORE_FRAC = 0.975


def cell_voltage(idle_ms):
    """Cell voltage after ``idle_ms`` ms of leakage following a PRE."""
    idle_ms = jnp.asarray(idle_ms, jnp.float32)
    decay = jnp.exp(-jnp.power(jnp.maximum(idle_ms, 0.0) / TAU_LEAK_MS, BETA))
    return jnp.where(idle_ms <= 0.0, VDD, VHALF + VHALF * decay)


def charge_sharing_delta(v_cell):
    return COUPLING * (jnp.asarray(v_cell) - VHALF)


def t_ready_ns(idle_ms):
    """ACT -> ready-to-access time (the tRCD requirement) in ns."""
    delta = charge_sharing_delta(cell_voltage(idle_ms))
    return T0_NS + TAU_SA_NS * jnp.log(V_READY_MARGIN / delta)


def t_restore_ns(idle_ms):
    """ACT -> full-restore time (the tRAS requirement) in ns."""
    v = cell_voltage(idle_ms)
    return t_ready_ns(idle_ms) + RAS_A_NS + RAS_B_NS_PER_V * (VDD - v)


def bitline_waveform(idle_ms: float, t_max_ns: float = 60.0, dt_ns: float = 0.01):
    """Numerically integrate the bitline voltage after an ACT (Fig 4.2).

    Uses a fixed-step exponential-growth integrator under ``lax.scan`` and
    returns ``(times_ns, v_bitline)``.  The closed-form ``t_ready_ns`` must
    agree with the first crossing of ``VHALF + V_READY_MARGIN`` (tested).
    """
    delta0 = charge_sharing_delta(cell_voltage(idle_ms))
    n = int(t_max_ns / dt_ns)

    def step(v_dev, _):
        # dV/dt = V_dev / tau  (positive feedback), saturating at the rail.
        v_new = jnp.minimum(v_dev * (1.0 + dt_ns / TAU_SA_NS), VHALF)
        return v_new, v_new

    _, devs = jax.lax.scan(step, jnp.asarray(delta0, jnp.float32), None, length=n)
    times = (jnp.arange(n, dtype=jnp.float32) + 1.0) * dt_ns
    return times, VHALF + devs


def t_ready_ns_numeric(idle_ms: float) -> float:
    """Ready time from the scan integrator; cross-check for the closed form.

    The integrator starts at the charge-sharing point, so the affine offset
    ``T0_NS`` (wordline rise etc.) is added on top, as in the closed form.
    """
    times, v = bitline_waveform(idle_ms)
    crossed = v >= VHALF + V_READY_MARGIN
    if not bool(crossed.any()):
        # argmax of an all-False mask is 0 — returning times[0] + T0_NS
        # would report a *minimal* ready time for a waveform that never
        # crossed the margin inside the integration window
        return float("inf")
    idx = jnp.argmax(crossed)
    return float(times[idx]) + T0_NS


@dataclasses.dataclass(frozen=True)
class DerivedTimings:
    duration_ms: float
    tRCD_ns: float
    tRAS_ns: float
    tRCD_cycles: int
    tRAS_cycles: int


def derive_timings(duration_ms: float,
                   tck_ns: float = timing_lib.CYCLE_NS) -> DerivedTimings:
    """Model-derived lowered timings for a caching duration (Table 6.1),
    in cycles of ``tck_ns``."""
    rcd = float(t_ready_ns(duration_ms))
    ras = float(t_restore_ns(duration_ms))
    return DerivedTimings(
        duration_ms=duration_ms,
        tRCD_ns=rcd,
        tRAS_ns=ras,
        tRCD_cycles=timing_lib.ns_to_cycles(rcd, tck_ns),
        tRAS_cycles=timing_lib.ns_to_cycles(ras, tck_ns),
    )


def derived_table(durations_ms=(1.0, 4.0, 16.0, 64.0)):
    """Reproduce Table 6.1 from the model."""
    return [derive_timings(d) for d in durations_ms]


def lowered_params(duration_ms: float,
                   base: timing_lib.TimingParams = timing_lib.DDR3_1600
                   ) -> timing_lib.TimingParams:
    """``base`` with model-derived tRCD/tRAS for ChargeCache hits, at
    ``base``'s clock and never above its own values."""
    d = derive_timings(duration_ms, base.tCK_ns)
    return dataclasses.replace(
        base,
        tRCD=min(d.tRCD_cycles, base.tRCD),
        tRAS=min(d.tRAS_cycles, base.tRAS),
    )
