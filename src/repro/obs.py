"""The program's own observability: host spans and jit-cache counters.

``span(name, **args)`` marks a host phase of a study (``repro/<name>``)
in the JAX profiler's trace, on the same clock as the device's XLA
module events, so a profile of ``Experiment.run()`` says what the host
was doing while the device was idle.  With no trace running a span
costs about a microsecond.  Spans sit at host boundaries only: never
inside a jitted function, never per grid point or per scan step.

``jit_cache()`` returns what JAX's compile path has cost this process
so far, per phase and per function: tracing to a jaxpr, lowering to an
MLIR module, and the backend compile, which also wraps a load from the
persistent compilation cache (timed apart as ``cache_load``).  The
listener that feeds it is registered once, when this module is first
imported (``repro.core`` imports it before anything can compile).

``scan_steps()`` returns the scan steps the engine launches ran, by
engine jit and by whether the bank-group path was compiled in: the
launch functions record each launch's scan length on the host when
they dispatch it, never per step.  ``admit_attempts()`` returns, under
the same keys, the admission attempts the window engine's lanes ran and
their lane-steps: each lane counts its own attempts on the device, and
the drain of a launch with full stats adds them up when it copies the
stats back.

This module is the only one in the program that touches
``jax.profiler`` or ``jax.monitoring``.
"""

from __future__ import annotations

import threading

import jax

#: ``jax.monitoring`` duration events -> the phase ``jit_cache`` reports
PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}

#: the two values of ``scan_steps()``'s path key
BANK_GROUPS, NO_BANK_GROUPS = "bank_groups", "no_bank_groups"

_lock = threading.Lock()
_counts: dict[tuple[str, str], list] = {}
_steps: dict[tuple[str, str], int] = {}
_admits: dict[tuple[str, str], list] = {}


def span(name: str, **args):
    """A profiler span ``repro/<name>`` with ``args`` as its stats."""
    return jax.profiler.TraceAnnotation("repro/" + name, **args)


def _on_duration(event: str, duration: float, **kwargs) -> None:
    phase = PHASES.get(event)
    if phase is None:
        return
    key = (phase, str(kwargs.get("fun_name", "")))
    with _lock:
        entry = _counts.setdefault(key, [0, 0.0])
        entry[0] += 1
        entry[1] += duration


def jit_cache() -> dict[str, dict[str, tuple[int, float]]]:
    """``{phase: {function: (events, seconds)}}`` since the process
    started, for the phases ``trace``, ``lower``, ``compile`` and
    ``cache_load`` (a phase with no event yet is absent).  ``compile``
    counts one event per executable built or loaded; ``cache_load`` is
    the part of ``compile`` spent reading the persistent cache, and
    JAX does not name its function (key ``""``)."""
    out: dict[str, dict[str, tuple[int, float]]] = {}
    with _lock:
        for (phase, fun), (n, s) in _counts.items():
            out.setdefault(phase, {})[fun] = (n, s)
    return out


def _path_key(engine: str, bank_groups: bool) -> tuple[str, str]:
    return engine, BANK_GROUPS if bank_groups else NO_BANK_GROUPS


def record_launch(engine: str, bank_groups: bool, steps: int) -> None:
    """Count one dispatched launch of ``engine`` (its jit's name) that
    scans ``steps`` steps, on the bank-group path or not."""
    key = _path_key(engine, bank_groups)
    with _lock:
        _steps[key] = _steps.get(key, 0) + int(steps)


def record_admits(engine: str, bank_groups: bool, attempts: int,
                  lane_steps: int) -> None:
    """Count ``attempts`` admission attempts over ``lane_steps`` scan
    steps of the lanes of one drained window-engine launch."""
    key = _path_key(engine, bank_groups)
    with _lock:
        entry = _admits.setdefault(key, [0, 0])
        entry[0] += int(attempts)
        entry[1] += int(lane_steps)


def scan_steps() -> dict[tuple[str, str], int]:
    """``{(engine, path): steps}`` since the process started: the scan
    steps every launch of each engine jit ran, with ``path`` either
    ``"bank_groups"`` (the bank-group path compiled in, DESIGN.md §16)
    or ``"no_bank_groups"``."""
    with _lock:
        return dict(_steps)


def admit_attempts() -> dict[tuple[str, str], tuple[int, int]]:
    """``{(engine, path): (attempts, lane_steps)}`` since the process
    started, keyed as ``scan_steps()``: the window engine's admission
    attempts, the failing one that ends a step's loop included, and the
    scan steps of every lane that ran them (padding lanes of a launch
    count like the points they repeat).  ``attempts / lane_steps`` is
    the mean trips of a lane's admission loop, at most the window depth
    ``W``.  Launches that reduce on the device (``reduce=``) are not
    counted."""
    with _lock:
        return {k: (a, n) for k, (a, n) in _admits.items()}


jax.monitoring.register_event_duration_secs_listener(_on_duration)
