"""Pallas kernel tier for the simulator hot loop (DESIGN.md §11).

``kernel.py`` owns the grid-parallel ``pallas_call`` wrapper (one sweep
point per grid step, full per-point state resident in VMEM/scratch),
``ref.py`` re-exports the authoritative ``lax.scan`` engines, and
``ops.py`` is the dispatch layer the engine entry points call
(interpret mode on the CPU; a plain refusal elsewhere, since Mosaic
cannot lower the scan body).
"""

from repro.kernels.sim_step.ops import run_sweep, run_synth  # noqa: F401
