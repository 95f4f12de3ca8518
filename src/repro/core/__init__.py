"""ChargeCache core: DRAM timing simulator, HCRAC, charge model, traces.

The faithful reproduction of the thesis's mechanism (see DESIGN.md §2.1).
"""

# first, so the jit-cache listener hears every compile of the program
from repro import obs  # noqa: F401

from repro.core.timing import (TimingParams, TimingVec, DDR3_1600,
                               DDR3_1600_CC_1MS, DDR4_2400,
                               lowered_for_duration,
                               ms_to_cycles, ns_to_cycles, CYCLE_NS)
from repro.core.dram import (DRAMConfig, DDR3_SYSTEM, DRAMEnvelope,
                             GeomParams, INTERLEAVE_KINDS, InterleaveConfig,
                             InterleaveParams, NO_ROW, compose_address,
                             envelope_of, geom_params, interleave_params)
from repro.core.aldram import ALDRAMConfig, TEMPERATURE_BINS_C
from repro.core.hcrac import HCRACConfig, HCRACParams, HCRACState
from repro.core.simulator import (MechanismConfig, MechParams, SimConfig,
                                  SimShape, mech_params, sim_shape, simulate,
                                  simulate_synth, sweep, sweep_synth,
                                  sweep_traces, weighted_speedup,
                                  default_nuat_bins, RLTL_EDGES_MS)
from repro.core.traces import WorkloadSpec
from repro.core import aldram, charge_model, energy, rltl, traces

__all__ = [
    "ALDRAMConfig", "TEMPERATURE_BINS_C", "aldram",
    "TimingParams", "TimingVec", "DDR3_1600", "DDR3_1600_CC_1MS",
    "DDR4_2400",
    "lowered_for_duration", "ms_to_cycles", "ns_to_cycles", "CYCLE_NS",
    "DRAMConfig", "DDR3_SYSTEM", "DRAMEnvelope", "GeomParams",
    "INTERLEAVE_KINDS", "InterleaveConfig", "InterleaveParams",
    "compose_address", "interleave_params", "WorkloadSpec",
    "envelope_of", "geom_params", "NO_ROW", "HCRACConfig", "HCRACParams",
    "HCRACState", "MechanismConfig", "MechParams", "SimConfig", "SimShape",
    "mech_params", "sim_shape", "simulate", "simulate_synth", "sweep",
    "sweep_synth", "sweep_traces", "weighted_speedup",
    "default_nuat_bins", "RLTL_EDGES_MS", "charge_model", "energy", "rltl",
    "traces",
]
