"""The traced FR-FCFS window engine (DESIGN.md §15).

One ``lax.scan`` step = admit-then-serve: a ``while_loop`` admits from
the per-core issue fronts (per-core program order, MSHR- and
dependency-gated, exactly the in-order engine's issue formula) until an
attempt fails or ``W`` attempts have run (at most the traced window cap
stick; a failed attempt writes nothing, so every later one would fail
alike).  Then one masked argmin over the window picks the request to
serve — row hits first, oldest (admission sequence) first — and the
shared ``simulator._service`` executes it with a per-rank tRRD/tFAW ACT
floor.  The carry is the in-order ``SimState`` plus ``O(W + ranks)``
window/rank registers: small, masked writes only (the §2.1 perf rule).
The lanes of a vmapped launch run the admission loop together while
any of them still admits (a ``psum`` over the vmap axes they span); a
lane that is done re-runs its failed attempt, which writes nothing, so
no lane's result has to be masked.  The loop carries only what an
attempt writes (``Admission``).

Tier contract (tests/test_controller.py, tests/test_oracle.py):

* ``win_cap == 1`` (every ``controller="inorder"`` point riding a mixed
  grid) serves requests in exactly the in-order engine's order with the
  same timings — stats, core_end and events are bitwise-identical.
* ``frfcfs`` points never report fewer row hits than in-order on
  locality-heavy streams, and match the pure-numpy host oracle
  (``repro.controller.oracle``) exactly on pinned streams.

Layering: this module imports the core simulator; the core never
imports this module at module scope (``_launch_*`` import it lazily).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dram as dram_lib
from repro.core import simulator as sim_mod
from repro.core.dram import GeomParams, fold_address
from repro.core.simulator import INF, MechParams, SimShape, SimState

#: selection-key penalty for a non-row-hit window entry: admission
#: sequence numbers stay < 2**24 (the trace-length horizon), so
#: ``miss_key = HIT_PENALTY + seq < 2**27`` never collides with a hit
#: key and never overflows int32
HIT_PENALTY = np.int32(1 << 26)

#: rank ACT registers start deep in the past so the first ACT of a rank
#: is unconstrained (an init of 0 would impose tRRD/tFAW on cycle-0
#: traffic); NEG + tFAW stays far below any real cycle
NEG = np.int32(-(2 ** 28))

#: tFAW constrains a rolling window of four ACTs per rank (DDR3)
FAW_DEPTH = 4


class WindowState(NamedTuple):
    """Scan carry: the in-order ``SimState`` plus the request window and
    the per-rank ACT history.  ``NR`` is the static envelope bank count
    (rank id = ``bank // n_banks`` <= banks_total - 1, so the envelope
    bound covers every traced geometry; unused entries stay at init)."""
    sim: SimState
    # request window, [W] each; a slot is live iff w_valid
    w_valid: jnp.ndarray   # bool
    w_core: jnp.ndarray    # issuing core
    w_idx: jnp.ndarray     # per-core request index (program order)
    w_bank: jnp.ndarray    # folded bank at admission
    w_row: jnp.ndarray     # folded row at admission
    w_write: jnp.ndarray   # bool
    w_ns: jnp.ndarray      # next_same queue-hit lookahead (bool)
    w_arr: jnp.ndarray     # issue (arrival-at-controller) cycle
    w_seq: jnp.ndarray     # global admission sequence (oldest-first key)
    # per-core admission gates, [C]
    yg_served: jnp.ndarray  # youngest admitted request serviced? (bool)
    yg_done: jnp.ndarray    # its completion cycle (the dep bound)
    ring_served: jnp.ndarray  # [C, mshr] slot's occupant serviced? (bool)
    # per-rank ACT windows, [NR]
    rank_last_act: jnp.ndarray  # newest ACT cycle (running max)
    faw_ring: jnp.ndarray       # [NR, FAW_DEPTH] last four ACT cycles
    faw_ptr: jnp.ndarray        # [NR] ring slot of the *oldest* of the 4
    # controller clock + admission counter (scalars)
    now: jnp.ndarray   # decision horizon: requests issued <= now admit
    seq: jnp.ndarray
    # admission attempts this lane has run, the failing one included
    # (``repro.obs.admit_attempts``; no stat reads it)
    attempts: jnp.ndarray
    # newest ACT cycle (running max) per (rank, bank group) slot
    # (``dram.bank_group_slot``), for tRRD_L: present only on the
    # bank-group path (DESIGN.md §16), else no carry leaf at all
    bg_last_act: jnp.ndarray | None = None  # [NR]


def _init_window(shape: SimShape, n_cores: int, max_len: int,
                 W: int) -> WindowState:
    nr = shape.envelope.max_banks_total
    zW = lambda dt: jnp.zeros((W,), dt)
    return WindowState(
        sim=sim_mod._init_state(shape, n_cores, max_len),
        w_valid=jnp.zeros((W,), bool),
        w_core=zW(jnp.int32), w_idx=zW(jnp.int32),
        w_bank=zW(jnp.int32), w_row=zW(jnp.int32),
        w_write=jnp.zeros((W,), bool), w_ns=jnp.zeros((W,), bool),
        w_arr=zW(jnp.int32), w_seq=zW(jnp.int32),
        yg_served=jnp.ones((n_cores,), bool),
        yg_done=jnp.zeros((n_cores,), jnp.int32),
        ring_served=jnp.ones((n_cores, shape.mshr), bool),
        rank_last_act=jnp.full((nr,), NEG, jnp.int32),
        faw_ring=jnp.full((nr, FAW_DEPTH), NEG, jnp.int32),
        faw_ptr=jnp.zeros((nr,), jnp.int32),
        now=jnp.int32(0), seq=jnp.int32(0), attempts=jnp.int32(0),
        bg_last_act=(jnp.full((nr,), NEG, jnp.int32)
                     if shape.envelope.max_bank_groups > 1 else None),
    )


class Admission(NamedTuple):
    """The admission loop's carry: what an attempt writes — the cores'
    issue fronts (``SimState.ptr`` / ``last_issue``) and the
    ``WindowState`` registers of the same names."""
    ptr: jnp.ndarray
    last_issue: jnp.ndarray
    w_valid: jnp.ndarray
    w_core: jnp.ndarray
    w_idx: jnp.ndarray
    w_bank: jnp.ndarray
    w_row: jnp.ndarray
    w_write: jnp.ndarray
    w_ns: jnp.ndarray
    w_arr: jnp.ndarray
    w_seq: jnp.ndarray
    yg_served: jnp.ndarray
    ring_served: jnp.ndarray
    now: jnp.ndarray
    seq: jnp.ndarray

    @classmethod
    def of(cls, ws: WindowState) -> "Admission":
        st = ws.sim
        return cls(st.ptr, st.last_issue,
                   *(getattr(ws, f) for f in cls._fields[2:]))

    def into(self, ws: WindowState) -> WindowState:
        sim = ws.sim._replace(ptr=self.ptr, last_issue=self.last_issue)
        return ws._replace(sim=sim, **{f: getattr(self, f)
                                       for f in self._fields[2:]})


def _make_window_step(shape: SimShape, W: int, p: MechParams, trace: dict,
                      warmup_steps, collect_events: bool = True,
                      lane_axes: tuple = ()):
    gap = trace["gap"]
    bank = trace["bank"]
    row = trace["row"]
    is_write = trace["is_write"]
    dep = trace["dep"]
    next_same = trace["next_same"]
    length = trace["length"]
    n_cores, L = gap.shape
    mshr = shape.mshr
    T = p.timing
    cores = jnp.arange(n_cores)
    bg_path = shape.envelope.max_bank_groups > 1

    def admit_one(a: Admission, mshr_ring, yg_done):
        """Try to admit one request: the earliest-issue eligible core's
        front request, if the window has capacity and the request has
        arrived (``issue <= now``; an empty window instead fast-forwards
        ``now`` — the controller idles until the next arrival).  Returns
        the new carry and whether the request was admitted; a failed
        attempt returns the carry unchanged."""
        ptr_c = jnp.clip(a.ptr, 0, L - 1)
        take = lambda arr: jnp.take_along_axis(arr, ptr_c[:, None],
                                               axis=1)[:, 0]
        g = take(gap)
        d = take(dep)
        # program-order MSHR slot: request i occupies slot i % mshr (the
        # in-order engine's ring_idx is ptr % mshr by construction, so
        # the gathered completion bound is the identical value)
        pos = jnp.mod(a.ptr, mshr)
        issue = jnp.maximum(a.last_issue + g, mshr_ring[cores, pos])
        issue = jnp.maximum(issue, jnp.where(d, yg_done, 0))
        # a core is eligible when it has requests left, its MSHR slot's
        # occupant (request i - mshr) has been serviced (completion time
        # known), and a dependency's producer (the core's youngest
        # admitted request) has been serviced
        elig = ((a.ptr < length) & a.ring_served[cores, pos]
                & (~d | a.yg_served))
        issue = jnp.where(elig, issue, INF)
        c = jnp.argmin(issue).astype(jnp.int32)
        t_iss = issue[c]

        occ = jnp.sum(a.w_valid.astype(jnp.int32))
        can = ((occ < p.win_cap) & (t_iss < INF)
               & ((t_iss <= a.now) | (occ == 0)))
        slot = jnp.argmin(a.w_valid).astype(jnp.int32)  # first free
        b_f, r_f = fold_address(p.geom, bank[c, ptr_c[c]],
                                row[c, ptr_c[c]])
        wr = lambda arr, val: arr.at[slot].set(
            jnp.where(can, val, arr[slot]))
        return Admission(
            ptr=a.ptr.at[c].add(can.astype(jnp.int32)),
            last_issue=a.last_issue.at[c].set(
                jnp.where(can, t_iss, a.last_issue[c])),
            w_valid=wr(a.w_valid, True),
            w_core=wr(a.w_core, c),
            w_idx=wr(a.w_idx, a.ptr[c]),
            w_bank=wr(a.w_bank, b_f),
            w_row=wr(a.w_row, r_f),
            w_write=wr(a.w_write, is_write[c, ptr_c[c]]),
            w_ns=wr(a.w_ns, next_same[c, ptr_c[c]]),
            w_arr=wr(a.w_arr, t_iss),
            w_seq=wr(a.w_seq, a.seq),
            yg_served=a.yg_served.at[c].set(
                jnp.where(can, False, a.yg_served[c])),
            ring_served=a.ring_served.at[c, pos[c]].set(
                jnp.where(can, False, a.ring_served[c, pos[c]])),
            now=jnp.where(can & (occ == 0),
                          jnp.maximum(a.now, t_iss), a.now),
            seq=a.seq + can.astype(jnp.int32),
        ), can

    def step(ws: WindowState, step_idx):
        # 1. admission: attempts refill the window until one fails or W
        # have run (at most win_cap stick).  A failed attempt changes
        # nothing, so every later one in this step would fail alike.  The
        # lanes of a vmapped launch loop together while any lane is live
        # (``lane_axes``: the vmap axes they span); a lane whose loop has
        # ended re-runs its failed attempt, a no-op it does not count.
        def attempt(c):
            i, live, n_try, a = c
            a, can = admit_one(a, ws.sim.mshr_ring, ws.yg_done)
            return i + 1, live & can, n_try + live.astype(jnp.int32), a

        def again(c):
            i, live, _, _ = c
            if lane_axes:
                live = jax.lax.psum(live.astype(jnp.int32), lane_axes) > 0
            return (i < W) & live

        _, _, n_try, adm = jax.lax.while_loop(
            again, attempt,
            (jnp.int32(0), jnp.bool_(True), jnp.int32(0), Admission.of(ws)))
        ws = adm.into(ws)._replace(attempts=ws.attempts + n_try)
        st = ws.sim

        # 2. FR-FCFS selection: masked argmin over (hit-first, oldest
        # admission) — seq < 2**24 keeps the key collision-free
        hitv = ws.w_valid & (st.open_row[ws.w_bank] == ws.w_row)
        key = jnp.where(
            ws.w_valid,
            jnp.where(hitv, 0, HIT_PENALTY) + ws.w_seq,
            jnp.int32(2 ** 31 - 1))
        e = jnp.argmin(key).astype(jnp.int32)
        alive = ws.w_valid[e]
        cc = ws.w_core[e]
        bi = ws.w_bank[e]
        t_arr = jnp.where(alive, ws.w_arr[e], INF)
        measure = (step_idx >= warmup_steps) & alive

        # 3. rank ACT floor: global rank id = bank // n_banks (the
        # envelope bank count bounds it, see WindowState); the floor
        # binds only for frfcfs points — in-order riders get 0, which
        # ``max`` ignores (t_act >= 0 always)
        rank = bi // p.geom.n_banks
        floor = jnp.maximum(
            ws.rank_last_act[rank] + T.tRRD,
            ws.faw_ring[rank, ws.faw_ptr[rank]] + T.tFAW)
        floor = jnp.where(p.frfcfs, floor, 0)
        floor_bg = None
        if bg_path:
            # tRRD_L: ACT to ACT within one bank group of the rank
            g_slot = dram_lib.bank_group_slot(p.geom, bi)
            floor_bg = jnp.where(
                p.frfcfs,
                jnp.maximum(floor, ws.bg_last_act[g_slot] + T.tRRD_L), 0)

        st2, done, events, (t_act, needs_act) = sim_mod._service(
            shape, p, st, t_arr, bi, ws.w_row[e], ws.w_write[e],
            ws.w_ns[e], measure, alive, act_floor=floor,
            act_floor_bg=floor_bg)

        # 4. rank window update (real ACTs of frfcfs points only).  The
        # running max keeps the register monotone even when an old miss
        # is served after a younger one activated later — a documented
        # deterministic model choice, mirrored by the oracle.
        upd = needs_act & alive & p.frfcfs
        fslot = ws.faw_ptr[rank]
        rank_last_act = ws.rank_last_act.at[rank].set(
            jnp.where(upd, jnp.maximum(ws.rank_last_act[rank], t_act),
                      ws.rank_last_act[rank]))
        faw_ring = ws.faw_ring.at[rank, fslot].set(
            jnp.where(upd, t_act, ws.faw_ring[rank, fslot]))
        faw_ptr = ws.faw_ptr.at[rank].set(
            jnp.where(upd, jnp.mod(fslot + 1, FAW_DEPTH), fslot))

        # 5. core/window bookkeeping (masked: dead steps change nothing)
        w = lambda new, old: jnp.where(alive, new, old)
        pos = jnp.mod(ws.w_idx[e], mshr)
        youngest = alive & (ws.w_idx[e] == st2.ptr[cc] - 1)
        sim3 = st2._replace(
            last_complete=st2.last_complete.at[cc].set(
                w(done, st2.last_complete[cc])),
            mshr_ring=st2.mshr_ring.at[cc, pos].set(
                w(done, st2.mshr_ring[cc, pos])),
            core_end=st2.core_end.at[cc].set(
                w(jnp.maximum(st2.core_end[cc], done),
                  st2.core_end[cc])),
        )
        ch = dram_lib.channel_of(p.geom, bi)
        ws = ws._replace(
            sim=sim3,
            w_valid=ws.w_valid.at[e].set(jnp.where(alive, False,
                                                   ws.w_valid[e])),
            yg_served=ws.yg_served.at[cc].set(
                jnp.where(youngest, True, ws.yg_served[cc])),
            yg_done=ws.yg_done.at[cc].set(
                jnp.where(youngest, done, ws.yg_done[cc])),
            ring_served=ws.ring_served.at[cc, pos].set(
                w(True, ws.ring_served[cc, pos])),
            rank_last_act=rank_last_act,
            faw_ring=faw_ring,
            faw_ptr=faw_ptr,
            # the next scheduling decision happens once this service's
            # commands have gone out on its channel's command bus
            now=jnp.where(alive,
                          jnp.maximum(ws.now, sim3.cmd_bus_free[ch]),
                          ws.now),
        )
        if bg_path:
            ws = ws._replace(bg_last_act=ws.bg_last_act.at[g_slot].set(
                jnp.where(upd, jnp.maximum(ws.bg_last_act[g_slot], t_act),
                          ws.bg_last_act[g_slot])))
        return ws, (events if collect_events else None)

    return step


def _run_window_impl(shape: SimShape, W: int, params: MechParams,
                     trace: dict, warmup_steps, n_steps: int,
                     collect_events: bool = True, lane_axes: tuple = ()):
    """Window-engine sibling of ``simulator._run_impl``: same trace
    contract (``next_same`` recomputed over the folded stream when
    absent), same ``(stats, core_end, events)`` return followed by the
    lane's admission attempts (``WindowState.attempts``), same
    trailing-REF retire.  ``lane_axes`` names the vmap axes the caller
    runs lanes over (none for one point)."""
    n_cores, L = trace["gap"].shape
    trace = dict(trace)
    if "next_same" not in trace:
        fb, fr = fold_address(params.geom, trace["bank"], trace["row"])
        trace["next_same"] = sim_mod._next_same_folded(
            shape.envelope.max_banks_total, fb, fr, trace["length"])
    ws = _init_window(shape, n_cores, L, W)
    step = _make_window_step(shape, W, params, trace, warmup_steps,
                             collect_events, lane_axes)
    ws, events = jax.lax.scan(step, ws,
                              jnp.arange(n_steps, dtype=jnp.int32))
    stats = sim_mod._retire_trailing_refs(ws.sim.stats, ws.sim.core_end,
                                          params)
    return stats, ws.sim.core_end, events, ws.attempts


@functools.partial(jax.jit, static_argnums=(0, 1, 5, 6))
def _run_window(shape: SimShape, W: int, params: MechParams, trace: dict,
                warmup_steps, n_steps: int, collect_events: bool = True):
    """One window-engine point (the ``simulate()`` route for
    ``controller="frfcfs"``)."""
    return _run_window_impl(shape, W, params, trace, warmup_steps,
                            n_steps, collect_events)


@functools.partial(jax.jit, static_argnums=(0, 1, 5, 6, 9))
def _run_window_batched(shape: SimShape, W: int, params: MechParams,
                        trace: dict, warmup_steps, n_steps: int,
                        collect_events: bool = True,
                        ns_geoms: GeomParams | None = None, ns_idx=None,
                        reduce_keys: tuple | None = None):
    """The vmapped window-engine grid: mirrors ``_run_batched`` —
    hoisted per-distinct-geometry ``next_same`` tables, optional
    on-device reduction — with the static window depth ``W`` shared by
    every point (in-order riders run with traced ``win_cap=1``)."""
    lanes = ("point",)
    if ns_geoms is None:
        out = jax.vmap(
            lambda p: _run_window_impl(shape, W, p, trace, warmup_steps,
                                       n_steps, collect_events, lanes),
            axis_name="point")(params)
    else:
        ns = sim_mod._ns_tables(shape, trace, ns_geoms)

        def one(p, gi):
            return _run_window_impl(shape, W, p,
                                    {**trace, "next_same": ns[gi]},
                                    warmup_steps, n_steps,
                                    collect_events, lanes)
        out = jax.vmap(one, axis_name="point")(params, ns_idx)
    if reduce_keys is not None:
        return sim_mod._reduce_device(out[0], out[1], reduce_keys)
    return out


@functools.partial(jax.jit, static_argnums=(0, 1, 5, 6, 9))
def _run_window_grid(shape: SimShape, W: int, params: MechParams,
                     traces: dict, warmups, n_steps: int,
                     collect_events: bool = False,
                     ns_geoms: GeomParams | None = None, ns_idx=None,
                     reduce_keys: tuple | None = None):
    """Nested [batch, grid] window engine (``sweep_traces`` route)."""
    lanes = ("trace", "point")

    def per_trace(trace, warmup):
        if ns_geoms is None:
            return jax.vmap(
                lambda p: _run_window_impl(shape, W, p, trace, warmup,
                                           n_steps, collect_events, lanes),
                axis_name="point")(params)
        ns = sim_mod._ns_tables(shape, trace, ns_geoms)

        def one(p, gi):
            return _run_window_impl(shape, W, p,
                                    {**trace, "next_same": ns[gi]},
                                    warmup, n_steps, collect_events, lanes)
        return jax.vmap(one, axis_name="point")(params, ns_idx)
    out = jax.vmap(per_trace, axis_name="trace")(traces, warmups)
    if reduce_keys is not None:
        return sim_mod._reduce_device(out[0], out[1], reduce_keys)
    return out


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 8, 9, 10))
def _run_window_synth_batched(shape: SimShape, W: int, n_cores: int,
                              max_len: int, params: MechParams, wparams,
                              ilparams, warmups, n_steps: int,
                              collect_events: bool = True,
                              reduce_keys: tuple | None = None):
    """Synthetic-stream window engine (``sweep_synth`` route): per-point
    on-device generation feeding the window scan, one compile for the
    whole grid."""
    from repro.workloads.generator import generate

    def one(p, wp, il, wu):
        trace = generate(n_cores, max_len, wp, p.geom, il)
        return _run_window_impl(shape, W, p, trace, wu, n_steps,
                                collect_events, ("point",))
    out = jax.vmap(one, axis_name="point")(params, wparams, ilparams,
                                           warmups)
    if reduce_keys is not None:
        return sim_mod._reduce_device(out[0], out[1], reduce_keys)
    return out
