"""Experiment runner: dedup → chunk → pipelined launches → Results.

Data flow (DESIGN.md §7.1, §13):

1. ``Experiment.expand()`` turns the named axes into a flat ``SimConfig``
   grid (C order over the axis coords).
2. **Dedup**: grid points whose *canonical* configs coincide (knobs no
   active mechanism policy consumes are stripped — a ``base`` point is
   the same run at any HCRAC capacity) launch once and fan back out.
3. **Chunking**: the unique grid splits into fixed-size chunks sized by
   ``chunk_size`` or a per-device memory-budget estimate (divided by the
   pipeline depth — every in-flight launch holds its own buffers); every
   chunk is padded to the same point count and every launch passes the
   *full* grid as ``shape_grid``, so all chunks share one ``SimShape`` /
   one stacked-params structure — and therefore exactly one XLA
   compilation.
4. **Staging**: the traced params of the whole unique grid are staged
   ONCE per run as numpy leaves (``_grid_shape_and_params`` /
   ``_stage_synth`` / ``stage_serving`` — all lru-cached per distinct
   config), and each chunk launch slices row views out of them; per-chunk
   host prep is an ``np.take``, not a re-staging.
5. **Pipelined launch**: chunks go through the mode's ``_launch_*``
   (async JAX dispatch; returns unblocked device arrays) / ``_drain_*``
   (blocks) pair, scheduled by ``ChunkScheduler`` against the device
   list: up to ``pipeline_depth × n_devices`` launches stay in flight
   and the host only blocks on the *oldest* — chunk k+1's dispatch and
   host-side assembly of chunk k-1 overlap chunk k's device compute.
   ``pipeline_depth=0`` is the fully blocking serial loop.
6. **Assembly**: full-stats mode fans per-point stats dicts into the
   dense labeled object-cell ``Results`` (the §7.3 layout and the parity
   oracle).  ``reduce=`` mode receives only ``[chunk, n_deps]`` integer
   ingredient columns per launch, applies the registered metric formulas
   vectorized, and assembles the *streamed* layout (``Results.data``) —
   O(grid × n_metrics) floats, never per-point pytrees.  Either mode can
   additionally append every drained chunk to a ``ResultsWriter`` JSONL
   stream (``stream_to=``).

**Progress contract**: ``progress(done, total)`` is invoked once after
every drained launch with ``total = n_trace_rows × n_unique_configs``
and ``done`` strictly increasing to exactly ``total`` at the last call;
a trace-mode launch drains ``len(batches) × n_valid`` points at once
(the whole trace-group row block of that chunk), a serving/synthetic
launch drains ``n_valid``.  Drains happen in launch order, so ``done``
is monotone regardless of pipeline depth (tests/test_streaming.py).

**Spans** (``repro.obs``): ``repro/run`` holds the phases above as
``repro/expand`` (1–3), ``repro/stage`` (4), one ``repro/launch`` and
one ``repro/drain`` per launch (5, with ``chunk=``; a drain holds the
engine's ``repro/d2h``, ``repro/rltl`` and ``repro/finalize`` and the
runner's ``repro/fan_out``) and ``repro/assemble`` (6).

Every cell is bitwise-identical to a direct ``sweep()`` /
``sweep_traces()`` of the same expanded grid (tests/test_experiment.py),
chunked, pipelined, reduced or not.
"""

from __future__ import annotations

import dataclasses
import os
from collections import deque
from typing import Callable, Iterable, Sequence

import jax
import numpy as np

from repro.core import metrics as metrics_lib
from repro.core import simulator as sim_mod
from repro.core.dram import InterleaveConfig
from repro.core.simulator import SimConfig
from repro.core.traces import pad_batch_to
from repro.experiment import registry
from repro.experiment.results import Results, ResultsWriter
from repro.experiment.spec import Experiment
from repro.obs import span

#: default per-device memory budget for auto-chunking (MiB)
DEFAULT_BUDGET_MB = 1024.0


def _canonical(cfg: SimConfig, mode: str) -> SimConfig:
    cfg = dataclasses.replace(cfg, mech=registry.canonical_mech(cfg.mech))
    if cfg.controller == "inorder":
        # only the frfcfs tier reads the window depth: in-order points
        # across a window axis are one run (DESIGN.md §15)
        cfg = dataclasses.replace(
            cfg, window=SimConfig.__dataclass_fields__["window"].default)
    if mode == "synth":
        if cfg.dram.n_channels == 1:
            # with one active channel every interleave policy degenerates
            # to the identity (dram.compose_address) — dedup the axis
            cfg = dataclasses.replace(cfg, interleave=InterleaveConfig())
        return cfg
    # trace-driven and serving launches never consume the workload spec
    # or interleave policy — points differing only there dedup
    cfg = dataclasses.replace(cfg, workload=None,
                              interleave=InterleaveConfig())
    if mode == "serving":
        # knobs only read by disabled serving policies dedup too
        cfg = dataclasses.replace(cfg, serving=cfg.serving.canonical())
    return cfg


def _dedup(configs: list[SimConfig], enable: bool, mode: str):
    """Unique canonical configs + flat-index → unique-index map."""
    if not enable:
        return list(configs), list(range(len(configs)))
    unique: list[SimConfig] = []
    where: dict = {}
    index_map = []
    for cfg in configs:
        key = _canonical(cfg, mode)
        if key not in where:
            where[key] = len(unique)
            unique.append(key)
        index_map.append(where[key])
    return unique, index_map


def bytes_per_point(n_steps: int, n_sets_max: int, n_ways: int,
                    n_cores: int, mshr: int, n_traces: int,
                    rltl: bool, n_banks_total: int = 16,
                    n_channels: int = 2, synth: bool = False,
                    window: int = 0) -> int:
    """Rough per-grid-point device-memory estimate for one launch.

    Dominant terms: the per-point HCRAC state (three int32 arrays, double
    counted for the scan's in/out carry), the per-bank/per-channel carry
    sized by the padded geometry *envelope* (eight int32 bank arrays —
    open-row, three ready times, the two last-PRE registers, the two
    per-bank stat accumulators — plus two bus arrays; a 1024-bank
    envelope point carries ~66 KB where the old constant assumed Table
    5.1's 16 banks), the per-point *folded* address copies + recomputed
    ``next_same`` lookahead (two int32 + one bool stream per point —
    the post-fold recompute, DESIGN.md §8), and — when events are
    collected for RLTL — the per-step event stream (9 int32 scan
    outputs).  ``window > 0`` is the frfcfs controller tier (DESIGN.md
    §15): its carry adds the request window (9 W-length arrays), the
    per-rank ACT registers (6 envelope-bank-sized int32 words) and the
    per-core admission gates.  The shared host trace itself is excluded;
    a *synthetic*
    point (``synth=True``, DESIGN.md §10) instead owns its whole
    generated stream (no host trace exists), adding the request arrays
    and generation temporaries.  With ``sweep_traces`` the whole thing
    multiplies by the batch axis.
    """
    per = 4096  # carry scalars, stats, issue-model state, slack
    per += n_sets_max * n_ways * 3 * 4 * 2
    per += (8 * n_banks_total + 2 * n_channels) * 4 * 2
    per += n_cores * (mshr + 8) * 4
    if window > 0:
        # frfcfs window carry (engine.WindowState): the W-slot request
        # window, per-rank tRRD/tFAW registers (envelope-bank bound) and
        # the per-core admission gates — all double counted (in/out)
        per += (9 * window + 6 * n_banks_total
                + n_cores * (mshr + 3)) * 4 * 2
    if synth:
        # generated stream + the scan's materialized candidate-draw xs
        # (three f32 + five int32 per step) + masked output copies,
        # plus the per-point folded (bank, row) copies + recomputed
        # next_same lookahead (each point generates for its own
        # geometry, so there is nothing to hoist)
        per += (56 + 9) * n_steps
    else:
        # trace-driven launches hoist the fold + next_same recompute to
        # one table per *distinct* geometry (simulator._hoist_geoms);
        # each point only materializes its gathered bool view
        per += n_steps
    if rltl:
        per += 9 * 4 * n_steps
    return per * max(1, n_traces)


def _auto_chunk(unique: list[SimConfig], groups, rltl: bool,
                budget_mb: float | None, mode: str = "trace",
                pipeline_depth: int = 0) -> int:
    """Largest device-aligned chunk fitting the per-device budget.

    ``groups`` holds the trace batches (trace-driven mode); when it is
    empty the grid is synthetic and the stream dimensions come from the
    configs' ``WorkloadSpec``s instead (``bytes_per_point(synth=True)``
    — each point owns its generated stream).  A *serving* grid
    (``mode="serving"``) is estimated from its own carry: the hot-page
    table, the queue/slot arrays, and the drawn per-step arrival
    counts.  With a launch pipeline, every in-flight chunk holds its
    own device buffers, so the budget divides by the depth."""
    budget_mb = (budget_mb if budget_mb is not None else
                 float(os.environ.get("REPRO_EXP_BUDGET_MB",
                                      DEFAULT_BUDGET_MB)))
    budget_mb /= max(1, pipeline_depth)
    n_sets_max = max(c.mech.hcrac.n_sets for c in unique)
    n_ways = unique[0].mech.hcrac.n_ways
    # the carry is sized by the padded geometry envelope of the grid
    n_banks_max = max(c.dram.banks_total for c in unique)
    n_ch_max = max(c.dram.n_channels for c in unique)
    ctrl, win = sim_mod._launch_controller(unique)
    win = win if ctrl == "frfcfs" else 0
    worst = 1
    for batches in groups.values():
        n_cores, max_len = batches[0][1].gap.shape[0], max(
            b.gap.shape[1] for _, b in batches)
        worst = max(worst, bytes_per_point(
            n_steps=n_cores * max_len, n_sets_max=n_sets_max,
            n_ways=n_ways, n_cores=n_cores, mshr=unique[0].mshr,
            n_traces=len(batches), rltl=rltl,
            n_banks_total=n_banks_max, n_channels=n_ch_max,
            window=win))
    if mode == "serving":  # fused serving scan: its own carry model
        sp = [c.serving for c in unique]
        per = 4096
        per += n_sets_max * n_ways * 3 * 4 * 2            # controller HCRAC
        per += max(s.hot_cfg().n_sets for s in sp) \
            * sp[0].hot_ways * 3 * 4 * 2                  # hot-page table
        per += (8 * n_banks_max + 2 * n_ch_max) * 4 * 2   # bank/bus carry
        per += (6 * sp[0].queue_cap + 4 * sp[0].max_batch) * 4 * 2
        per += 4 * max(s.steps() for s in sp)             # drawn counts xs
        worst = per
    elif not groups:  # synthetic grid: no host traces, per-point streams
        from repro.workloads.profiles import max_len_of
        n_cores = unique[0].workload.n_cores
        max_len = max_len_of([c.workload for c in unique])
        worst = bytes_per_point(
            n_steps=n_cores * max_len, n_sets_max=n_sets_max,
            n_ways=n_ways, n_cores=n_cores, mshr=unique[0].mshr,
            n_traces=1, rltl=rltl, n_banks_total=n_banks_max,
            n_channels=n_ch_max, synth=True, window=win)
    ndev = max(1, len(jax.devices()))
    budget = budget_mb * 2**20 * ndev
    chunk = int(max(1, budget // worst))
    if chunk >= ndev:
        chunk = (chunk // ndev) * ndev  # keep launches device-aligned
    return min(chunk, len(unique))


class ChunkScheduler:
    """Bounded-in-flight launch pipeline over a device list.

    ``run(work)`` consumes ``(launch, finish)`` pairs: ``launch()``
    dispatches one chunk (returning *unblocked* device output — JAX
    async dispatch) and ``finish(out)`` blocks on it and assembles.
    At most ``depth × len(devices)`` launches are in flight before the
    scheduler blocks on the oldest, so drains (and therefore progress
    callbacks and stream writes) happen strictly in launch order while
    later chunks' dispatch overlaps earlier chunks' device compute.
    ``depth=0`` degenerates to launch-then-drain serial blocking.
    Each launch and each drain is a span (``repro/launch``,
    ``repro/drain``) carrying the launch's index as ``chunk``.

    The device list is an abstraction seam: ``jax.devices()`` today; a
    mesh's device axis tomorrow (the cross-host mega-sweep, ROADMAP).
    """

    def __init__(self, devices: Sequence | None = None, depth: int = 2):
        self.devices = tuple(devices if devices is not None
                             else jax.devices())
        self.depth = max(0, int(depth))
        self.max_inflight = self.depth * max(1, len(self.devices))

    def run(self, work: Iterable[tuple[Callable, Callable]]) -> None:
        pending: deque = deque()
        for chunk, (launch, finish) in enumerate(work):
            with span("launch", chunk=chunk):
                pending.append((chunk, launch(), finish))
            while len(pending) > self.max_inflight:
                self._drain(*pending.popleft())
        while pending:
            self._drain(*pending.popleft())

    @staticmethod
    def _drain(chunk: int, out, finish: Callable) -> None:
        with span("drain", chunk=chunk):
            finish(out)


def run_experiment(exp: Experiment, progress=None,
                   stream_to: str | None = None) -> Results:
    with span("run"):
        return _run(exp, progress, stream_to)


def _run(exp: Experiment, progress, stream_to: str | None) -> Results:
    with span("expand"):
        labeled, trace_items = exp.trace_items()
        cfg_dims, cfg_coords, configs = exp.expand()
        if not configs:
            configs = [exp.base]
        serving = exp.traces is None and configs[0].serving is not None
        synth = exp.traces is None and not serving
        mode = "serving" if serving else ("synth" if synth else "trace")
        unique, index_map = _dedup(configs, exp.dedup, mode)

        if serving:
            for cfg in unique:
                assert cfg.serving is not None, (
                    "a serving experiment (base.serving set) must set "
                    "cfg.serving on every grid point")
            # one pseudo trace row so chunk fan-out/assembly is shared below
            trace_items = [(None, None)]
        if synth:
            for cfg in unique:
                assert cfg.workload is not None and cfg.workload.names, (
                    "Experiment(traces=None) is the synthetic mode: every "
                    "grid point needs a WorkloadSpec (add a 'workload' axis "
                    "or set base.workload)")
            # fail up front (not mid-launch) on mixed core counts: the
            # streamed engine shares one [C, L] stream shape per grid —
            # unlike the trace-driven path, which groups batches by C
            cores = {cfg.workload.n_cores for cfg in unique}
            assert len(cores) == 1, (
                f"a synthetic grid must share one core count, got "
                f"{sorted(cores)}: split the experiment per core count (the "
                f"workload axis mixes single-core names with multi-core "
                f"mixes)")
            # one pseudo trace row so chunk fan-out/assembly is shared below
            trace_items = [(None, None)]

        # ---- the §13 reduce contract ------------------------------------
        reduced = exp.reduce is not None
        if reduced:
            assert not exp.rltl, (
                "reduce= lowers scalar ingredients only; RLTL histograms "
                "need the full-stats path (reduce=None)")
            assert not exp.trace_metrics, (
                "reduce= streams device-computed metrics only; trace_metrics "
                "extras need the full-stats path")
            if serving:
                from repro.serving.loop.engine import SERVE_REDUCE_KEYS
                available = SERVE_REDUCE_KEYS
            else:
                available = sim_mod.REDUCE_KEYS
            resolved = metrics_lib.resolve(exp.reduce_metrics(), available)
            reduce_keys = metrics_lib.deps_for(resolved)
            out_metrics = tuple(m.name for m in resolved)
        else:
            reduce_keys = None
            out_metrics = tuple(exp.metrics)

        # group traces by core count; pad within a group to the longest trace
        groups: dict[int, list] = {}
        if exp.traces is not None:
            for pos, (label, batch) in enumerate(trace_items):
                groups.setdefault(batch.gap.shape[0], []).append((pos, batch))

        depth = max(0, int(exp.pipeline_depth))
        chunk = exp.chunk_size or _auto_chunk(unique, groups, exp.rltl,
                                              exp.memory_budget_mb, mode,
                                              pipeline_depth=depth)
        chunk = max(1, min(chunk, len(unique)))
        n_unique = len(unique)
        n_chunks = -(-n_unique // chunk)
        # per-chunk row indices into the staged unique grid; the tail chunk
        # pads by repeating its last point so every launch shares one
        # stacked-params shape (same avals -> the one compilation)
        chunk_idx = [np.minimum(np.arange(ci * chunk, (ci + 1) * chunk),
                                n_unique - 1) for ci in range(n_chunks)]
        chunk_cfgs = [[unique[i] for i in idx] for idx in chunk_idx]
        n_valid = [min(chunk, n_unique - ci * chunk) for ci in range(n_chunks)]

        def rows_of(tree, idx):
            """Per-chunk view of once-staged [n_unique, ...] numpy leaves."""
            return jax.tree_util.tree_map(lambda a: np.asarray(a)[idx], tree)

        # ---- dense labeled frame + streaming sinks ----------------------
        dims = ((exp.trace_dim,) + cfg_dims) if labeled else cfg_dims
        coords = dict(cfg_coords)
        if labeled:
            coords[exp.trace_dim] = tuple(label for label, _ in trace_items)
        shape = tuple(len(coords[d]) for d in dims)
        cfg_shape = tuple(len(cfg_coords[d]) for d in cfg_dims)
        n_flat = int(np.prod(cfg_shape, dtype=np.int64)) if cfg_shape else 1
        imap = np.asarray(index_map, np.int64)
        n_rows = len(trace_items)

        meta = {"n_points": len(configs) * n_rows,
                "n_configs": len(configs), "n_unique": n_unique,
                "chunk_size": chunk, "n_chunks": n_chunks,
                # synth mode has no trace groups: one launch per chunk
                "n_launches": n_chunks * max(1, len(groups)),
                "mode": mode, "pipeline_depth": depth}
        if reduced:
            meta["reduce_keys"] = tuple(reduce_keys)

        writer = (ResultsWriter(stream_to, dims, coords, out_metrics,
                                meta=meta) if stream_to else None)

        by_trace: list[list] = [[None] * n_unique for _ in trace_items]
        flat_data = ({m: np.full((n_rows, n_flat), np.nan)
                      for m in out_metrics} if reduced else None)
        aggs: dict[str, tuple] = {}
        if exp.aggregate:
            assert reduced, "aggregate= needs reduce= (streamed metrics)"
            by_name = {m.name: m for m in resolved}
            for rn, (agg_name, metric_name) in dict(exp.aggregate).items():
                assert metric_name in by_name, (
                    f"aggregate {rn!r} refers to {metric_name!r}, which is "
                    f"not among the reduced metrics {out_metrics}")
                aggs[rn] = (metrics_lib.make_aggregator(
                    agg_name, by_name[metric_name]), metric_name)

        total = n_rows * n_unique
        state = {"done": 0}

    def advance(n):
        state["done"] += n
        if progress is not None:
            progress(state["done"], total)

    def fan_reduced(t: int, ci: int, red: np.ndarray):
        """One trace row × one chunk of the on-device reduction: apply
        the registered formulas vectorized over the chunk's unique
        points and scatter into the flat streamed arrays."""
        lo, hi = ci * chunk, ci * chunk + n_valid[ci]
        cols = {k: red[:n_valid[ci], j]
                for j, k in enumerate(reduce_keys)}
        pos = np.nonzero((imap >= lo) & (imap < hi))[0]
        src = imap[pos] - lo
        rows = np.empty((len(pos), len(resolved)), np.float64)
        for mi, m in enumerate(resolved):
            vals = np.asarray(m.fn(*[cols[d] for d in m.deps]),
                              np.float64)[src]
            flat_data[m.name][t, pos] = vals
            rows[:, mi] = vals
        gidx = t * n_flat + pos
        for agg, metric_name in aggs.values():
            agg.update(rows[:, out_metrics.index(metric_name)], gidx)
        if writer is not None:
            writer.write(gidx, rows)

    extras_by_t = [dict((exp.trace_metrics or {}).get(label, {}))
                   for label, _ in trace_items]

    def fan_full(t: int, ci: int, row: list):
        """Full-stats fan-out of one drained chunk row: store the
        unique-point cells and (optionally) stream the declared metric
        scalars for the covered flat grid points."""
        lo, hi = ci * chunk, ci * chunk + n_valid[ci]
        by_trace[t][lo:hi] = row[:n_valid[ci]]
        if writer is None:
            return
        extra = extras_by_t[t]
        pos = np.nonzero((imap >= lo) & (imap < hi))[0]
        src = imap[pos] - lo
        rows = np.empty((len(pos), len(out_metrics)), np.float64)
        for k, p in enumerate(pos):
            cell = row[src[k]] if not extra else {**row[src[k]], **extra}
            for mi, m in enumerate(out_metrics):
                v = cell.get(m)
                rows[k, mi] = (np.nan if v is None or np.ndim(v) > 0
                               else float(v))
        writer.write(t * n_flat + pos, rows)

    def fan_out(t: int, ci: int, row):
        """Hand one drained chunk row to the mode's fan-out."""
        with span("fan_out"):
            if reduced:
                fan_reduced(t, ci, row)
            else:
                fan_full(t, ci, list(row))

    # ---- stage once, then build the launch/drain work list ----------
    with span("stage"):
        # controller tier of the whole unique grid: one shared static
        # window size so every chunk rides one window-engine compile
        # (DESIGN.md §15)
        ctrl, win = sim_mod._launch_controller(unique)
        work: list[tuple[Callable, Callable]] = []

        if serving:
            from repro.serving.loop import engine as serve_eng
            sshape, sparams, swarmups = serve_eng.stage_serving(
                unique, unique, collect_steps=False)
            for ci in range(n_chunks):
                pch = rows_of(sparams, chunk_idx[ci])
                wch = swarmups[chunk_idx[ci]]

                def launch(pch=pch, wch=wch):
                    return serve_eng._launch_serving(
                        sshape, pch, wch, None, chunk, reduce_keys)

                def finish(out, ci=ci):
                    row = serve_eng._drain_serving(
                        out, chunk_cfgs[ci], sshape, chunk, reduce_keys)
                    fan_out(0, ci, row)
                    advance(n_valid[ci])

                work.append((launch, finish))

        if synth:
            (yshape, n_cores, max_len, n_steps, ystacked, wstack, ilstack,
             ywarmups) = sim_mod._stage_synth(unique, unique)
            backend = sim_mod._uniform_backend(unique)
            for ci in range(n_chunks):
                sch = rows_of(ystacked, chunk_idx[ci])
                wch = rows_of(wstack, chunk_idx[ci])
                ich = rows_of(ilstack, chunk_idx[ci])
                uch = ywarmups[chunk_idx[ci]]

                def launch(sch=sch, wch=wch, ich=ich, uch=uch):
                    return sim_mod._launch_synth(
                        yshape, n_cores, max_len, sch, wch, ich, uch,
                        n_steps, exp.rltl, chunk, backend=backend,
                        reduce_keys=reduce_keys, controller=ctrl,
                        window=win)

                def finish(out, ci=ci):
                    row = sim_mod._drain_synth(out, chunk_cfgs[ci], chunk,
                                               reduce_keys)
                    fan_out(0, ci, row)
                    advance(n_valid[ci])

                work.append((launch, finish))

        if mode == "trace":
            tshape, tstacked = sim_mod._grid_shape_and_params(unique, unique)
            ns_geoms, ns_idx = sim_mod._hoist_geoms(unique, unique)
            ns_idx = np.asarray(ns_idx)
            backend = sim_mod._uniform_backend(unique)
            single = not labeled and len(trace_items) == 1
            for batches in groups.values():
                max_len = max(b.gap.shape[1] for _, b in batches)
                padded = [pad_batch_to(b, max_len) for _, b in batches]
                if single:
                    trace = sim_mod._device_trace(padded[0])
                    n_req = int(padded[0].length.sum())
                    assert n_req < 2**24, (
                        "trace too long for the int32 cycle horizon")
                else:
                    assert backend == "ref", (
                        "sweep_traces runs the ref engine only; use a single "
                        "unlabeled batch for the pallas tier")
                    traces = jax.tree_util.tree_map(
                        lambda *xs: np.stack(xs),
                        *[sim_mod._device_trace(b) for b in padded])
                    n_cores_g, max_len_g = padded[0].gap.shape
                    n_steps_g = n_cores_g * max_len_g
                    assert n_steps_g < 2**24, (
                        "trace too long for the int32 cycle horizon")
                for ci in range(n_chunks):
                    sch = rows_of(tstacked, chunk_idx[ci])
                    nch = ns_idx[chunk_idx[ci]]
                    cfg0 = chunk_cfgs[ci][0]
                    if single:
                        warmup = np.int32(int(cfg0.warmup_frac * n_req))

                        def launch(sch=sch, nch=nch, warmup=warmup):
                            return sim_mod._launch_batch(
                                tshape, sch, trace, warmup, n_req, exp.rltl,
                                ns_geoms, nch, chunk, backend=backend,
                                reduce_keys=reduce_keys, controller=ctrl,
                                window=win)

                        def finish(out, ci=ci, batches=batches):
                            row = sim_mod._drain_batch(
                                out, chunk_cfgs[ci], padded[0].length, chunk,
                                reduce_keys)
                            t = batches[0][0]
                            fan_out(t, ci, row)
                            advance(n_valid[ci])
                    else:
                        warmups = np.asarray(
                            [int(cfg0.warmup_frac * int(b.length.sum()))
                             for b in padded], np.int32)

                        def launch(sch=sch, nch=nch, warmups=warmups,
                                   traces=traces, n_steps_g=n_steps_g):
                            return sim_mod._launch_grid(
                                tshape, sch, traces, warmups, n_steps_g,
                                exp.rltl, ns_geoms, nch, len(padded),
                                reduce_keys, controller=ctrl, window=win)

                        def finish(out, ci=ci, batches=batches,
                                   padded=padded):
                            rows = sim_mod._drain_grid(
                                out, chunk_cfgs[ci], padded, len(padded),
                                reduce_keys)
                            for (pos, _), row in zip(batches, rows):
                                fan_out(pos, ci, row)
                            advance(len(batches) * n_valid[ci])

                    work.append((launch, finish))

    ChunkScheduler(depth=depth).run(work)
    assert state["done"] == total, (state["done"], total)

    # ---- assemble ----------------------------------------------------
    with span("assemble"):
        if reduced:
            agg_out = {}
            for rn, (agg, _) in aggs.items():
                r = agg.result()
                if isinstance(r, dict) and "flat_index" in r \
                        and r["flat_index"] is not None:
                    idx = (np.unravel_index(r["flat_index"], shape)
                           if shape else ())
                    r = {**r, "coords": {d: coords[d][int(i)]
                                         for d, i in zip(dims, idx)}}
                agg_out[rn] = r
            if aggs:
                meta["aggregates"] = agg_out
            if writer is not None:
                writer.close(meta={"aggregates": agg_out} if aggs else {})
            data = {m: np.ascontiguousarray(a.reshape(shape))
                    for m, a in flat_data.items()}
            return Results(dims=dims, coords=coords, data=data,
                           metrics=out_metrics, meta=meta)

        if writer is not None:
            writer.close()
        cells = np.empty(shape, object)
        for t, (label, _) in enumerate(trace_items):
            extra = dict((exp.trace_metrics or {}).get(label, {}))
            for flat, u in enumerate(index_map):
                idx = np.unravel_index(flat, cfg_shape) if cfg_shape else ()
                full = ((t,) + tuple(idx)) if labeled else tuple(idx)
                cells[full] = {**by_trace[t][u], **extra}
        return Results(dims=dims, coords=coords, cells=cells,
                       metrics=out_metrics, meta=meta)
