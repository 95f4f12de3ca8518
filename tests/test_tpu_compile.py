"""Compile rehearsals of the engine jits for a described TPU v5e chip.

Nothing here runs on a chip: each test lowers and compiles one device
program of the main path for ``v5e:2x2``'s first device, at small but
real widths (8 cores, the 1024-entry thesis HCRAC, a few thousand scan
steps), so a change the TPU compiler would refuse fails here first.
The topology is described inside a module fixture — never at import —
and the persistent compilation cache is off for these compiles.
"""

import dataclasses
import re

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.controller import engine as ctrl_engine
from repro.core import simulator as sim_mod
from repro.core.hcrac import HCRACConfig
from repro.core.simulator import MechanismConfig, SimConfig
from repro.core.timing import lowered_for_duration, ms_to_cycles
from repro.core.traces import WorkloadSpec, multicore_batch, random_mixes

MECHS = ("base", "chargecache", "nuat", "cc_nuat", "rltl", "lldram")
N_REQ = 400  # per core: 8 x 400 = 3,200 scan steps


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler or topology support here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _specs(tree, sharding):
    """Shapes (with the chip's sharding) of a pytree of arrays."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype
                                       if not hasattr(x, "dtype")
                                       else x.dtype, sharding=sharding),
        tree)


def _thesis_cfg(kind="base", **kw) -> SimConfig:
    """Table 5.1's 8-core point: 1024-entry HCRAC, 1 ms, closed rows."""
    mech = MechanismConfig(
        kind=kind, hcrac=HCRACConfig(n_entries=1024,
                                     caching_cycles=ms_to_cycles(1.0)),
        lowered=lowered_for_duration(1.0))
    return SimConfig(mech=mech, policy="closed", **kw)


def _compile(fn, *args):
    """Lower and compile for the described chip; returns the executable
    (raises whatever the TPU compiler raises)."""
    return fn.lower(*args).compile()


@pytest.fixture(scope="module")
def trace_launch():
    grid = [_thesis_cfg(k) for k in MECHS]
    shape, stacked = sim_mod._grid_shape_and_params(grid, grid)
    ns_geoms, ns_idx = sim_mod._hoist_geoms(grid, grid)
    batch = multicore_batch(random_mixes(1, 8)[0], N_REQ, seed=3)
    trace = sim_mod._device_trace(batch)
    n_steps = int(batch.length.sum())
    return shape, stacked, trace, n_steps, ns_geoms, ns_idx


def test_run_batched_compiles(one_chip, trace_launch):
    shape, stacked, trace, n_steps, ns_geoms, ns_idx = trace_launch
    dyn = _specs((stacked, trace, np.int32(0)), one_chip)
    ns = _specs((ns_geoms, ns_idx), one_chip)
    exe = _compile(
        jax.jit(lambda p, tr, wu, g, gi: sim_mod._run_batched(
            shape, p, tr, wu, n_steps, True, g, gi)), *dyn, *ns)
    assert exe.memory_analysis() is not None


def _while_bodies(hlo: str) -> dict[str, list[str]]:
    """Instruction lines of each while-loop body computation in an HLO
    module's text."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        m = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif cur is not None:
            cur.append(line)
    return {b: comps[b]
            for b in set(re.findall(r"body=%?([\w.\-]+)", hlo))}


def _tiled_bytes(dims, layout: str) -> int:
    """Bytes of an s32 array under a TPU layout ``{minor..major:T(a,b)}``:
    the tile pads the minor dimensions up to its shape."""
    m2m = [int(d) for d in layout.split(":")[0].split(",")]
    tile = re.search(r"T\(([\d,]+)\)", layout)
    padded = list(dims)
    if tile:
        t = [int(x) for x in tile.group(1).split(",")]
        for dim, size in zip(m2m, reversed(t)):
            padded[dim] = -(-padded[dim] // size) * size
    return 4 * int(np.prod(padded))


def test_run_grid_hcrac_tables_stay_in_one_unpadded_layout(one_chip):
    """The ``inorder.mix8_rltl`` benchmark cell's launch shape (4 eight-core
    traces x 15 points, a 4,096-entry HCRAC envelope, 200 requests per
    core): the scan body must neither copy an HCRAC table nor
    hold one in a layout whose tiles pad it beyond 2x.  Set-major
    ``[sets, ways]`` tables put the 2 ways on the 128-lane axis (64x) and
    the compiler copied each table into that form for every row read."""
    caps = (512, 1024, 2048, 4096)
    grid = ([_thesis_cfg("base")]
            + [dataclasses.replace(c, mech=dataclasses.replace(
                c.mech, hcrac=dataclasses.replace(c.mech.hcrac, n_entries=n)))
               for k in ("chargecache", "cc_nuat", "rltl")
               for c in [_thesis_cfg(k)] for n in caps]
            + [_thesis_cfg("nuat"), _thesis_cfg("lldram")])
    shape, stacked = sim_mod._grid_shape_and_params(grid, grid)
    ns_geoms, ns_idx = sim_mod._hoist_geoms(grid, grid)
    batches = [multicore_batch(mix, 200, seed=5 + i)
               for i, mix in enumerate(random_mixes(4, 8))]
    traces = jax.tree_util.tree_map(
        lambda *xs: np.stack(xs), *[sim_mod._device_trace(b) for b in batches])
    n_steps = int(batches[0].gap.size)
    S, W = shape.hcrac.n_sets, shape.hcrac.n_ways
    assert (len(grid), S, W) == (15, 2048, 2)
    dyn = _specs((stacked, traces, np.zeros(4, np.int32), ns_geoms, ns_idx),
                 one_chip)
    hlo = _compile(jax.jit(lambda p, tr, wu, g, gi: sim_mod._run_grid(
        shape, p, tr, wu, n_steps, True, g, gi)), *dyn).as_text()
    table = sorted((4, 15, S, W))
    seen = 0
    for body, lines in _while_bodies(hlo).items():
        for line in lines:
            m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = s32\[([\d,]+)\]"
                         r"\{([^}]*)\} ([\w-]+)\(", line)
            if not m:
                continue
            name, dims, layout, op = m.groups()
            dims = [int(d) for d in dims.split(",")]
            if sorted(dims) != table:
                continue
            seen += 1
            assert op != "copy", f"{body}: HCRAC table copy {name}"
            assert _tiled_bytes(dims, layout) <= 2 * 4 * np.prod(dims), (
                f"{body}: {name} s32{dims}{{{layout}}} pads the table")
    assert seen, "no HCRAC-table-shaped buffer found in any scan body"


def test_rltl_hist_device_compiles(one_chip, trace_launch):
    """The device sort compiles in ~7 s at 512 events per point and
    ~30 s at 3,200, so this one runs on a short event stream."""
    shape, stacked, trace, n_steps, ns_geoms, ns_idx = trace_launch
    events = jax.eval_shape(
        lambda p, tr, wu, g, gi: sim_mod._run_batched(
            shape, p, tr, wu, n_steps, True, g, gi)[2],
        *_specs((stacked, trace, np.int32(0), ns_geoms, ns_idx), None))
    short = jax.tree_util.tree_map(
        lambda e: jax.ShapeDtypeStruct((len(MECHS), 512), e.dtype,
                                       sharding=one_chip), events)
    _compile(sim_mod._rltl_hist_device, short)


@pytest.mark.parametrize("window", [8, 16])
def test_run_window_batched_compiles(one_chip, trace_launch, window):
    shape, stacked, trace, n_steps, ns_geoms, ns_idx = trace_launch
    dyn = _specs((stacked, trace, np.int32(0)), one_chip)
    ns = _specs((ns_geoms, ns_idx), one_chip)
    _compile(
        jax.jit(lambda p, tr, wu, g, gi: ctrl_engine._run_window_batched(
            shape, window, p, tr, wu, n_steps, True, g, gi)), *dyn, *ns)


def test_window_admission_loop_copies_no_carry_array(one_chip):
    """A [batch, grid] window launch at W=16 (2 eight-core traces x 2
    points): the admission loop nested in the scan step must copy no
    window-slot ``[2, 2, 16]`` or per-core ``[2, 2, 8]`` array on any
    trip.  Its lanes loop while any one still admits and a finished lane
    re-runs a failed attempt, a no-op; looping on each lane's own
    predicate instead makes vmap select every carry leaf per lane, and
    the compiler then copied them into another layout each trip (12
    here, 19 in the ``frfcfs16.mix8`` launch)."""
    grid = [_thesis_cfg(k, controller="frfcfs", window=16)
            for k in ("base", "chargecache")]
    shape, stacked = sim_mod._grid_shape_and_params(grid, grid)
    ns_geoms, ns_idx = sim_mod._hoist_geoms(grid, grid)
    batches = [multicore_batch(mix, 100, seed=7 + i)
               for i, mix in enumerate(random_mixes(2, 8))]
    traces = jax.tree_util.tree_map(
        lambda *xs: np.stack(xs), *[sim_mod._device_trace(b) for b in batches])
    n_steps = int(batches[0].gap.size)
    dyn = _specs((stacked, traces, np.zeros(2, np.int32), ns_geoms, ns_idx),
                 one_chip)
    hlo = _compile(jax.jit(lambda p, tr, wu, g, gi: ctrl_engine._run_window_grid(
        shape, 16, p, tr, wu, n_steps, False, g, gi)), *dyn).as_text()
    bodies = _while_bodies(hlo)
    nested = {inner for lines in bodies.values()
              for inner in re.findall(r"body=%?([\w.\-]+)", "\n".join(lines))}
    assert len(nested) == 1, f"expected one loop inside the scan: {nested}"
    (admit,) = nested
    copies = re.findall(r"= (?:s32|pred)\[([\d,]+)\]\S* copy\(",
                        "\n".join(bodies[admit]))
    carry = [c for c in copies if c in ("2,2,16", "2,2,8")]
    assert not carry, f"{admit} copies carry arrays every trip: {carry}"


def test_run_synth_batched_compiles(one_chip):
    spec = WorkloadSpec(names=tuple(random_mixes(1, 8)[0]), n_req=N_REQ,
                        seed=3)
    grid = [dataclasses.replace(_thesis_cfg(k), workload=spec)
            for k in MECHS]
    (shape, n_cores, max_len, n_steps, stacked, wstack, ilstack,
     warmups) = sim_mod._stage_synth(grid)
    dyn = _specs((stacked, wstack, ilstack, warmups), one_chip)
    _compile(
        jax.jit(lambda p, w, il, wu: sim_mod._run_synth_batched(
            shape, n_cores, max_len, p, w, il, wu, n_steps, True)), *dyn)


def test_run_serving_batched_compiles(one_chip):
    from repro.serving.loop import ServingSpec, engine
    from repro.workloads.arrivals import ArrivalConfig
    spec = ServingSpec(
        arrival=ArrivalConfig(rate=2.0, burstiness=2.0, prompt_pages_min=1,
                              prompt_pages_max=2, decode_min=4,
                              decode_max=8, seed=11),
        n_reqs=256, max_batch=8, queue_cap=32, arrivals_max=8,
        hot_entries=1024, hot_ways=2, hot_caching_ms=0.05, hot_exact=True)
    grid = [_thesis_cfg(k, serving=dataclasses.replace(spec, policy=pol))
            for pol in ("fifo", "charge_aware") for k in ("base",
                                                          "chargecache")]
    shape, params, warmups = engine.stage_serving(grid)
    _compile(jax.jit(lambda p, wu: engine._run_serving_batched(
        shape, p, wu)), *_specs((params, warmups), one_chip))


@pytest.mark.parametrize("exact", [False, True], ids=["sweep", "exact"])
def test_hcrac_lookup_kernel_compiles(one_chip, exact):
    """The serving scheduler's batched probe kernel is a real Mosaic
    kernel on the chip (``tpu_custom_call``), at the 8-core table size."""
    from repro.kernels.hcrac.kernel import hcrac_lookup_kernel
    cfg = HCRACConfig(n_entries=1024, exact_expiry=exact)
    table = jax.ShapeDtypeStruct((cfg.n_ways, cfg.n_sets), np.int32,
                                 sharding=one_chip)
    probes = jax.ShapeDtypeStruct((512,), np.int32, sharding=one_chip)
    exe = _compile(jax.jit(lambda t, it, g, ts: hcrac_lookup_kernel(
        cfg, t, it, g, ts)), table, table, probes, probes)
    assert "tpu_custom_call" in exe.as_text()


def test_sim_step_kernel_refused_by_mosaic(one_chip, trace_launch):
    """Mosaic has no lowering for the sim_step scan body's per-bank
    gathers; the refusal ``ops.MOSAIC_REFUSAL`` names is this one.  If a
    JAX upgrade makes it compile, the pallas tier can run on the chip and
    ROADMAP's design debt 3 must be re-decided."""
    from repro.kernels.sim_step import ops as sim_step_ops
    shape, stacked, trace, n_steps, ns_geoms, ns_idx = trace_launch
    dyn = _specs((stacked, trace, np.int32(0)), one_chip)
    ns = _specs((ns_geoms, ns_idx), one_chip)
    with pytest.raises(Exception, match="dynamic_slice"):
        _compile(
            jax.jit(lambda p, tr, wu, g, gi: sim_step_ops._sweep_pallas(
                shape, p, tr, wu, n_steps, True, False, g, gi)), *dyn, *ns)


def test_pallas_backend_refuses_off_cpu(monkeypatch):
    """``backend="pallas"`` raises plainly on an accelerator instead of
    falling back to another engine (the platform is steered here)."""
    from repro.core.traces import single_core_batch
    batch = single_core_batch("mcf_like", 64, seed=1)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(NotImplementedError, match="Mosaic refuses"):
        sim_mod.sweep(batch, [SimConfig(backend="pallas")])
    spec = WorkloadSpec(names=("mcf_like",), n_req=64, seed=1)
    with pytest.raises(NotImplementedError, match="Mosaic refuses"):
        sim_mod.sweep_synth([SimConfig(workload=spec, backend="pallas")])
