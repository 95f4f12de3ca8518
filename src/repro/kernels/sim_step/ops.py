"""Dispatch layer of the sim-step kernel tier.

``run_sweep`` / ``run_synth`` mirror the calling conventions of the ref
engines (``simulator._run_batched`` / ``_run_synth_batched``) and are
what the ``sweep()`` / ``sweep_synth()`` entry points call when a grid
selects ``backend="pallas"``.  On CPU the kernels run in Pallas
interpret mode (same jnp semantics as the ref scan — the bitwise-parity
contract).  On any other platform they raise ``NotImplementedError``:
Mosaic refuses the scan body (``MOSAIC_REFUSAL``), and nothing falls
back to another engine behind the caller's back.

The scan body itself is *shared* with the ref tier: the kernel body
calls ``simulator._run_impl`` (and, on the synthetic path, the
``repro.workloads`` generator — fused, so streams are produced
in-register and never round-trip through HBM).  There is deliberately
no second implementation of the step to drift.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import simulator
from repro.kernels.sim_step.kernel import grid_step_call

__all__ = ["run_sweep", "run_synth"]


#: why the kernel cannot run compiled: Mosaic (the TPU Pallas compiler,
#: JAX 0.9.0) has no lowering for the scan body's per-bank gathers and
#: scatters — compiling ``_sweep_pallas`` for a v5e chip fails with
#: "Unimplemented primitive in Pallas TPU lowering ...: dynamic_slice"
#: (tests/test_tpu_compile.py)
MOSAIC_REFUSAL = (
    'backend="pallas" runs only in Pallas interpret mode on the CPU: '
    "Mosaic refuses the sim_step scan body on {platform} (unimplemented "
    "Pallas TPU lowering for dynamic_slice, the per-bank gathers); use "
    'backend="ref"')


def _require_cpu() -> None:
    """The kernel runs in interpret mode on the CPU; refuse elsewhere."""
    platform = jax.default_backend()
    if platform != "cpu":
        raise NotImplementedError(MOSAIC_REFUSAL.format(platform=platform))


@functools.partial(jax.jit, static_argnums=(0, 4, 5, 6))
def _sweep_pallas(shape, stacked, trace, warmup, n_steps: int,
                  collect_events: bool, interpret: bool,
                  ns_geoms=None, ns_idx=None):
    """Trace-driven sweep on the Pallas grid: stacked params (and each
    point's distinct-geometry index) are per-grid-step blocks; the trace
    and the hoisted ``next_same`` tables are shared broadcast blocks."""
    hoisted = ns_geoms is not None
    shared = {"trace": dict(trace),
              "warmup": jnp.reshape(jnp.asarray(warmup, jnp.int32), (1,))}
    if hoisted:
        shared["ns"] = simulator._ns_tables(shape, trace, ns_geoms)
        point = (stacked, jnp.asarray(ns_idx, jnp.int32))
    else:
        point = (stacked,)

    def body(pt, sh):
        if hoisted:
            p, gi = pt
            tr = {**sh["trace"], "next_same": sh["ns"][gi]}
        else:
            (p,) = pt
            tr = sh["trace"]
        return simulator._run_impl(shape, p, tr, sh["warmup"][0],
                                   n_steps, collect_events)

    return grid_step_call(point, shared, body, interpret=interpret)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 7, 8, 9))
def _synth_pallas(shape, n_cores: int, max_len: int, stacked, wstack,
                  ilstack, warmups, n_steps: int, collect_events: bool,
                  interpret: bool):
    """Fused synthesis + scan on the Pallas grid: every input is a
    per-point block (there is no shared trace — each grid step generates
    its own stream in-register from its workload counters)."""
    def body(pt, _sh):
        p, w, il, wu = pt
        return simulator._run_synth_impl(shape, n_cores, max_len, p, w,
                                         il, wu, n_steps, collect_events)

    return grid_step_call((stacked, wstack, ilstack, warmups), {}, body,
                          interpret=interpret)


def run_sweep(shape, stacked, trace, warmup, n_steps: int,
              collect_events: bool = True, ns_geoms=None, ns_idx=None):
    """Kernel-tier analogue of ``simulator._run_batched``."""
    _require_cpu()
    return _sweep_pallas(shape, stacked, trace, warmup, n_steps,
                         collect_events, True, ns_geoms, ns_idx)


def run_synth(shape, n_cores: int, max_len: int, stacked, wstack,
              ilstack, warmups, n_steps: int,
              collect_events: bool = True):
    """Kernel-tier analogue of ``simulator._run_synth_batched``."""
    _require_cpu()
    return _synth_pallas(shape, n_cores, max_len, stacked, wstack,
                         ilstack, warmups, n_steps, collect_events, True)
