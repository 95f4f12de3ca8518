"""Shared benchmark harness utilities.

Workload sizes follow the thesis's methodology scaled to this container
(the mechanism's statistics converge well before 1 B instructions); set
``REPRO_BENCH_QUICK=1`` for CI-sized runs.
"""

from __future__ import annotations

import functools
import multiprocessing
import os
import sys
import time

# Expose one XLA host device per CPU core (before jax's first import) so
# sweep()/sweep_traces() shard their vmapped grid/batch axis across cores
# — near-linear scaling of the batched engine (DESIGN.md §4).  Opt out or
# resize with REPRO_BENCH_DEVICES; a no-op once jax is already loaded.
if "jax" not in sys.modules:
    _ndev = int(os.environ.get("REPRO_BENCH_DEVICES",
                               min(8, multiprocessing.cpu_count())))
    if _ndev > 1 and "host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={_ndev}").strip()

import numpy as np

from repro import compile_cache
from repro.core import (HCRACConfig, MechanismConfig, SimConfig, simulate,
                        weighted_speedup)
from repro.core.traces import (WORKLOADS, multicore_batch, random_mixes,
                               single_core_batch)
from repro.experiment import Experiment
from repro.experiment.results import Results

QUICK = os.environ.get("REPRO_BENCH_QUICK", "0") == "1"

# persistent compile cache for every benchmark process (JAX's own
# JAX_COMPILATION_CACHE_DIR when set, else the in-checkout default)
compile_cache.enable()

#: the repo root — every ``BENCH_*.json`` artifact lands here regardless
#: of the CWD the driver was invoked from (the artifacts are part of the
#: repo's delivered trajectory; a relative default silently scattered
#: them before PR 6)
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def artifact_path(name: str) -> str:
    """Resolve a ``BENCH_*.json`` artifact name to the repo root (env
    overrides that are already absolute are respected verbatim)."""
    return name if os.path.isabs(name) else os.path.join(REPO_ROOT, name)

N_REQ_1C = 20_000 if QUICK else 150_000
N_REQ_8C = 5_000 if QUICK else 40_000
N_MIXES = 2 if QUICK else 20

SINGLE_NAMES = [w.name for w in WORKLOADS]


def mech_config(kind: str, n_cores: int = 1, n_entries: int = 128,
                caching_ms: float = 1.0) -> MechanismConfig:
    """Thesis configuration: 128 entries *per core* (Table 5.1); the
    simulator models the aggregate table."""
    from repro.core import lowered_for_duration, ms_to_cycles
    low = lowered_for_duration(caching_ms)
    return MechanismConfig(
        kind=kind,
        hcrac=HCRACConfig(n_entries=n_entries * n_cores,
                          caching_cycles=ms_to_cycles(caching_ms)),
        lowered=low,
    )


def sim_cfg(kind: str, n_cores: int = 1, policy: str | None = None,
            **mech_kw) -> SimConfig:
    """One grid point: a full SimConfig for sweep()/simulate()."""
    if policy is None:
        policy = "open" if n_cores == 1 else "closed"
    return SimConfig(mech=mech_config(kind, n_cores, **mech_kw),
                     policy=policy)


@functools.lru_cache(maxsize=None)
def _single_batch(name: str, n_req: int, seed: int):
    return single_core_batch(name, n_req, seed=seed)


@functools.lru_cache(maxsize=None)
def _mix_batch(names: tuple, n_req: int, seed: int):
    return multicore_batch(list(names), n_req, seed=seed)


def sim_single(name: str, kind: str, seed: int = 3, **mech_kw) -> dict:
    batch = _single_batch(name, N_REQ_1C, seed)
    return simulate(batch, sim_cfg(kind, 1, **mech_kw))


def sim_mix(names: list[str], kind: str, seed: int = 3, **mech_kw) -> dict:
    batch = _mix_batch(tuple(names), N_REQ_8C, seed)
    return simulate(batch, sim_cfg(kind, len(names), **mech_kw))


def compile_counted(fn, *args, **kw):
    """Run ``fn`` and count the fresh XLA compilations it triggered
    across every grid engine (trace-driven batched/grid and the
    synthetic streamed engine).  The shared harness behind every
    benchmark's "this whole study rides ONE compilation" assertion."""
    from repro.core import simulator as sim_mod
    from repro.kernels.sim_step import ops as sim_step_ops
    from repro.serving.loop import engine as serve_eng
    from repro.controller import engine as ctrl_eng
    engines = (sim_mod._run_grid, sim_mod._run_batched,
               sim_mod._run_synth_batched,
               sim_step_ops._sweep_pallas, sim_step_ops._synth_pallas,
               serve_eng._run_serving_batched, serve_eng._run_serving_pinned,
               ctrl_eng._run_window, ctrl_eng._run_window_batched,
               ctrl_eng._run_window_grid, ctrl_eng._run_window_synth_batched)
    before = [e._cache_size() for e in engines]
    out = fn(*args, **kw)
    compiles = sum(e._cache_size() - b
                   for e, b in zip(engines, before))
    return out, compiles


def mech_speedups(res: Results, base: str = "base") -> dict:
    """Mean weighted speedup per mechanism label against ``base``,
    averaged over every other dim of ``res`` (the per-benchmark
    ``pairwise`` boilerplate, shared)."""
    sp = res.pairwise(
        "mechanism", base,
        lambda b, s: weighted_speedup(b["core_end"], s["core_end"]))
    return {m: float(np.mean(v)) for m, v in sp.items()}


def experiment_synth(axes: dict, n_cores: int = 8, n_req: int | None = None,
                     seed: int = 3, **kw) -> Results:
    """A synthetic (on-device generated) evaluation matrix through the
    Experiment API: ``Experiment(traces=None)`` over a workload axis —
    no host trace is materialized or transferred (DESIGN.md §10).  The
    base config sizes the streams (``n_req`` defaults to the bench's
    multicore sizing) and sets the matching row policy."""
    from repro.core import WorkloadSpec
    import dataclasses
    spec = WorkloadSpec(names=("milc_like",) * n_cores,
                        n_req=n_req if n_req is not None else N_REQ_8C,
                        seed=seed)
    base = dataclasses.replace(sim_cfg("base", n_cores), workload=spec)
    return Experiment(traces=None, axes=axes, base=base, **kw).run()


def experiment_singles(names: list[str], axes: dict, seed: int = 3,
                       **kw) -> Results:
    """The whole (workload × axes) evaluation matrix through the
    Experiment API: one nested-vmap compile per trace shape and chunk,
    labeled Results with a leading ``workload`` dim."""
    traces = {n: _single_batch(n, N_REQ_1C, seed) for n in names}
    return Experiment(traces=traces, axes=axes, base=sim_cfg("base", 1),
                      trace_dim="workload", **kw).run()


def experiment_mixes(mixes: list[list[str]], axes: dict, seed: int = 3,
                     **kw) -> Results:
    """The whole (8-core mix × axes) evaluation matrix through the
    Experiment API; Results carry a leading ``mix`` dim (mix00, ...)."""
    traces = {f"mix{i:02d}": _mix_batch(tuple(m), N_REQ_8C, seed)
              for i, m in enumerate(mixes)}
    return Experiment(traces=traces, axes=axes, base=sim_cfg("base", 8),
                      trace_dim="mix", **kw).run()


def timed(fn, *args, **kw):
    t0 = time.time()
    out = fn(*args, **kw)
    return out, (time.time() - t0) * 1e6


def eight_core_mixes() -> list[list[str]]:
    return random_mixes(N_MIXES, 8)


def csv_row(name: str, us: float, derived: str) -> str:
    return f"{name},{us:.0f},{derived}"
