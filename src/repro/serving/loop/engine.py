"""The fused continuous-batching serving scan (DESIGN.md §12).

One ``lax.scan`` per grid point runs the whole serving closed loop —
arrivals drawn from the counter-based PRNG, a fixed-slot active set
with validity masks, registry-folded admission/preemption, hot-page
(KV charge) table updates, and the DRAM simulator's per-access
``_service`` step — in a single carry, so the KV page charge and the
DRAM bank state evolve in the *same* compiled program.  ``vmap`` over
stacked ``ServingParams`` makes policy x arrival_rate x burstiness x
mechanism (x geometry x temperature) ONE compile, and nothing about
the stream is ever materialized on the host.

Step order mirrors the host ``repro.serving.scheduler.Scheduler`` (the
parity oracle, tests/test_serving_loop.py):

  1. arrivals  — accept up to ``arrivals_max`` drawn requests into free
     queue slots; prefill-touch their prompt pages (hot inserts + DRAM
     writes), exactly like ``Scheduler.submit``.
  2. preempt   — policy-gated: requeue the active request with the most
     remaining work when the queue is long (no host analogue).
  3. admit     — fill free slots from the queue, best score first, FIFO
     on ties (the host's stable sort).
  4. probe     — read-only hot-table probes of first-decode requests'
     pages (the ``admit_probes`` / ``admit_hot`` metric).
  5. decode    — every active request streams ALL its KV pages (the
     attention read) through the hot table and the DRAM simulator, then
     advances one token.
  6. retire    — free slots of finished requests; advance the clock.

Per-step work is statically bounded (``arrivals_max x prompt_pages_max``
prefill accesses + ``max_batch x pages_max`` decode accesses), masked
per access, so the scan shape is independent of the traffic drawn.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hcrac as hcl
from repro.core import metrics as metrics_lib
from repro.core import simulator as sim_mod
from repro.obs import span
from repro.serving.loop import policies as pol_mod
from repro.serving.loop.spec import ServingSpec
from repro.workloads import arrivals as arr_mod
from repro.workloads import prng

__all__ = ["ServingShape", "ServingParams", "run_sweep",
           "simulate_serving", "page_gid", "SERVE_REDUCE_KEYS",
           "stage_serving"]

# independent lanes for the page -> (hot gid, DRAM bank, DRAM row) maps
_L_GID, _L_BANK, _L_ROW = prng.lanes(3)

#: intra-step DRAM spacing between a step's page accesses (cycles) —
#: matches the host ``Scheduler.emit_trace`` same-timestamp gap
_INTRA = 4


def page_gid(xp, rid, page):
    """Hot-table key of (request, page): full-avalanche 32-bit hash
    (cf. ``HotPageTracker.page_to_dram``'s splitmix64 rationale — a
    strided id would alias table sets).  Exposed with the ``xp``
    convention so the host parity oracle mirrors it bitwise."""
    h = prng.hash_u32(xp, rid, page, _L_GID)
    return (h & xp.uint32(0x7FFF_FFFF)).astype(xp.int32)


class ServingShape(NamedTuple):
    """Static half of a serving grid (hashable; jit static argument)."""
    sim: sim_mod.SimShape
    hot: hcl.HCRACConfig      # padded hot-table shape carrier
    max_batch: int
    queue_cap: int
    arrivals_max: int
    prompt_pages_max: int     # static prefill fan-out bound
    pages_max: int            # static per-slot page-stream bound
    n_steps: int
    collect_steps: bool       # emit per-step (occ, qlen, arrivals)


class ServingParams(NamedTuple):
    """Traced half — stacked along the grid axis and vmapped."""
    mech: sim_mod.MechParams
    arrival: arr_mod.ArrivalParams
    hot: hcl.HCRACParams
    policy: dict              # registry blocks {name: {leaf: array}}
    cycles_per_step: jnp.ndarray  # i32
    page_tokens: jnp.ndarray      # i32


class _RestParams(NamedTuple):
    """ServingParams minus mech (which ``_grid_shape_and_params``
    already stacks with grid-wide padding hints)."""
    arrival: arr_mod.ArrivalParams
    hot: hcl.HCRACParams
    policy: dict
    cycles_per_step: jnp.ndarray
    page_tokens: jnp.ndarray


class LoopState(NamedTuple):
    sim: sim_mod.SimState     # bank/bus/HCRAC/stats state (core fields idle)
    hot: hcl.HCRACState       # KV hot-page table
    # fixed decode slots [S]; rid < 0 = free
    slot_rid: jnp.ndarray
    slot_done: jnp.ndarray
    slot_max: jnp.ndarray
    slot_pages: jnp.ndarray   # prompt pages
    # admission queue [Q]; rid < 0 = free
    q_rid: jnp.ndarray
    q_done: jnp.ndarray
    q_max: jnp.ndarray
    q_pages: jnp.ndarray
    q_touch: jnp.ndarray      # last page-touch cycle (charge prediction)
    q_seq: jnp.ndarray        # arrival sequence (FIFO key)
    n_arrived: jnp.ndarray    # i32
    next_seq: jnp.ndarray     # i32
    now: jnp.ndarray          # i32 scheduler clock
    stats: dict


SERVE_STAT_KEYS = ("arrived", "dropped", "admitted", "retired",
                   "preempted", "admit_probes", "admit_hot",
                   "occ_sum", "qlen_sum")

#: every key the serving launch can lower on device (DESIGN.md §13):
#: the DRAM-side counters (``total_cycles`` = the final scheduler
#: clock), the serving-loop counters, and the static step count (an
#: ingredient of ``occ_mean``/``qlen_mean``).
SERVE_REDUCE_KEYS = sim_mod.REDUCE_KEYS + SERVE_STAT_KEYS + ("n_steps",)


def _init_loop_state(shape: ServingShape) -> LoopState:
    S, Q = shape.max_batch, shape.queue_cap
    neg = lambda n: jnp.full((n,), -1, jnp.int32)
    z = lambda n: jnp.zeros((n,), jnp.int32)
    return LoopState(
        sim=sim_mod._init_state(shape.sim, n_cores=1, max_len=1),
        hot=hcl.init(shape.hot),
        slot_rid=neg(S), slot_done=z(S), slot_max=z(S), slot_pages=z(S),
        q_rid=neg(Q), q_done=z(Q), q_max=z(Q), q_pages=z(Q),
        q_touch=z(Q), q_seq=z(Q),
        n_arrived=jnp.int32(0), next_seq=jnp.int32(0), now=jnp.int32(0),
        stats={k: jnp.int32(0) for k in SERVE_STAT_KEYS},
    )


def _probe_many(hshape: hcl.HCRACConfig, st: hcl.HCRACState, gids, t,
                p: hcl.HCRACParams):
    """Batched read-only hot-table lookup (no LRU side effect) — the
    vectorized form of ``hcrac.lookup(..., enable=False)``."""
    set_idx = jnp.mod(gids, p.n_sets).astype(jnp.int32)      # [N]
    tags = hcl._row(hshape, st.tags, set_idx).T              # [N, W]
    itime = hcl._row(hshape, st.itime, set_idx).T
    alive = hcl._alive(hshape, set_idx[:, None], itime, t, p)
    return jnp.any((tags != hcl.NO_TAG) & alive
                   & (tags == gids[:, None]), axis=1)


def _make_step(shape: ServingShape, p: ServingParams, warmup):
    S, Q, A = shape.max_batch, shape.queue_cap, shape.arrivals_max
    Pp, Pt = shape.prompt_pages_max, shape.pages_max
    geom = p.mech.geom
    hshape = shape.hot
    INF = sim_mod.INF

    def dram_of(rid, page):
        bank = (prng.hash_u32(jnp, rid, page, _L_BANK)
                % geom.banks_total.astype(jnp.uint32)).astype(jnp.int32)
        row = (prng.hash_u32(jnp, rid, page, _L_ROW)
               % geom.n_rows.astype(jnp.uint32)).astype(jnp.int32)
        return bank, row

    def access_scan(sim, hot, t, cnt, rids, ks, en, is_write, measure):
        """Stream masked (rid, page) accesses through the hot table and
        the DRAM step; ``cnt`` spaces them ``_INTRA`` cycles apart."""
        gids = page_gid(jnp, rids, ks)
        banks, rows = dram_of(rids, ks)

        def body(carry, x):
            sim, hot, cnt = carry
            gid, bank, row, e, m = x
            hot = hcl.insert(hshape, hot, gid, t, enable=e, params=p.hot)
            sim, _, _ = sim_mod._service(
                shape.sim, p.mech, sim, t + _INTRA * cnt, bank, row,
                jnp.bool_(is_write), jnp.bool_(False),
                measure=m, enable=e)
            return (sim, hot, cnt + e.astype(jnp.int32)), None

        (sim, hot, cnt), _ = jax.lax.scan(
            body, (sim, hot, cnt), (gids, banks, rows, en, measure))
        return sim, hot, cnt

    def step(st: LoopState, xs):
        step_idx, n_drawn = xs
        t = st.now
        stats = dict(st.stats)
        measure_step = step_idx >= warmup

        # ---- 1. arrivals: fill free queue slots in position order -----
        q_invalid = st.q_rid < 0
        free_q = jnp.sum(q_invalid.astype(jnp.int32))
        budget = p.arrival.n_reqs - st.n_arrived
        want = jnp.minimum(n_drawn, budget)
        n_new = jnp.minimum(jnp.minimum(want, free_q), jnp.int32(A))
        inv_rank = jnp.cumsum(q_invalid.astype(jnp.int32)) - 1   # [Q]
        is_dest = q_invalid & (inv_rank < n_new)
        rid_new = st.n_arrived + inv_rank
        pages_new, dec_new = arr_mod.request_attrs(jnp, p.arrival, rid_new)
        q_rid = jnp.where(is_dest, rid_new, st.q_rid)
        q_done = jnp.where(is_dest, 0, st.q_done)
        q_pages = jnp.where(is_dest, pages_new, st.q_pages)
        q_max = jnp.where(is_dest, dec_new, st.q_max)
        q_touch = jnp.where(is_dest, t, st.q_touch)
        q_seq = jnp.where(is_dest, st.next_seq + inv_rank, st.q_seq)
        n_arrived = st.n_arrived + n_new
        next_seq = st.next_seq + n_new

        # prefill: each accepted arrival touches its prompt pages
        # (hot inserts + DRAM writes), like ``Scheduler.submit``
        a_idx = jnp.repeat(jnp.arange(A, dtype=jnp.int32), Pp)
        ka = jnp.tile(jnp.arange(Pp, dtype=jnp.int32), A)
        rid_a = st.n_arrived + a_idx
        pg_a, _ = arr_mod.request_attrs(jnp, p.arrival, rid_a)
        en_a = (a_idx < n_new) & (ka < pg_a)
        sim, hot, cnt = access_scan(
            st.sim, st.hot, t, jnp.int32(0), rid_a, ka, en_a,
            True, en_a & measure_step)

        # ---- 2. preemption (policy-gated, at most one per step) -------
        q_len = (Q - free_q) + n_new
        want_p = pol_mod.preempt_decision(
            p.policy, pol_mod.PreemptCtx(now=t, q_len=q_len))
        slot_valid = st.slot_rid >= 0
        remaining = st.slot_max - st.slot_done
        cand_p = slot_valid & (remaining >= 2)
        pe = want_p & (free_q - n_new > 0) & jnp.any(cand_p)
        victim = jnp.argmax(jnp.where(cand_p, remaining, -1))
        qdest = jnp.argmin((q_rid >= 0).astype(jnp.int32))  # first free
        put = lambda arr, val, old: arr.at[qdest].set(
            jnp.where(pe, val, old))
        q_rid = put(q_rid, st.slot_rid[victim], q_rid[qdest])
        q_done = put(q_done, st.slot_done[victim], q_done[qdest])
        q_max = put(q_max, st.slot_max[victim], q_max[qdest])
        q_pages = put(q_pages, st.slot_pages[victim], q_pages[qdest])
        # its pages were last streamed on the previous decode step
        q_touch = put(q_touch, t - p.cycles_per_step, q_touch[qdest])
        q_seq = put(q_seq, next_seq, q_seq[qdest])  # back of the line
        next_seq = next_seq + pe.astype(jnp.int32)
        slot_rid = st.slot_rid.at[victim].set(
            jnp.where(pe, -1, st.slot_rid[victim]))

        # ---- 3. admission: best score first, FIFO (q_seq) on ties -----
        score = pol_mod.admission_scores(
            p.policy, pol_mod.AdmitCtx(
                now=t, q_touch=q_touch, q_seq=q_seq, q_valid=q_rid >= 0,
                caching_cycles=p.hot.caching_cycles))
        slot_done, slot_max, slot_pages = (
            st.slot_done, st.slot_max, st.slot_pages)

        def admit_body(carry, _):
            slot_rid, slot_done, slot_max, slot_pages, q_rid, adm = carry
            qv = q_rid >= 0
            sv = slot_rid >= 0
            can = jnp.any(qv) & jnp.any(~sv)
            sc = jnp.where(qv, score, -jnp.inf)
            tie = qv & (sc >= jnp.max(sc))
            pick = jnp.argmin(jnp.where(tie, q_seq, INF))
            dest = jnp.argmin(sv.astype(jnp.int32))      # first free slot
            mv = lambda arr, val: arr.at[dest].set(
                jnp.where(can, val, arr[dest]))
            slot_rid = mv(slot_rid, q_rid[pick])
            slot_done = mv(slot_done, q_done[pick])
            slot_max = mv(slot_max, q_max[pick])
            slot_pages = mv(slot_pages, q_pages[pick])
            q_rid = q_rid.at[pick].set(jnp.where(can, -1, q_rid[pick]))
            return (slot_rid, slot_done, slot_max, slot_pages, q_rid,
                    adm + can.astype(jnp.int32)), None

        (slot_rid, slot_done, slot_max, slot_pages, q_rid, n_adm), _ = (
            jax.lax.scan(admit_body,
                         (slot_rid, slot_done, slot_max, slot_pages,
                          q_rid, jnp.int32(0)),
                         None, length=S))

        # ---- 4. read-only probes of first-decode requests' pages ------
        s_idx = jnp.repeat(jnp.arange(S, dtype=jnp.int32), Pt)
        ks = jnp.tile(jnp.arange(Pt, dtype=jnp.int32), S)
        rid_s = slot_rid[s_idx]
        slot_valid = slot_rid >= 0
        first = slot_valid & (slot_done == 0)
        en_pr = first[s_idx] & (ks < slot_pages[s_idx])
        hits = _probe_many(hshape, hot, page_gid(jnp, rid_s, ks), t, p.hot)
        stats["admit_probes"] = stats["admit_probes"] + jnp.sum(
            en_pr.astype(jnp.int32))
        stats["admit_hot"] = stats["admit_hot"] + jnp.sum(
            (hits & en_pr).astype(jnp.int32))

        # ---- 5. decode: stream every active request's KV pages --------
        npages = slot_pages + (slot_done + p.page_tokens - 1) \
            // p.page_tokens
        en_d = slot_valid[s_idx] & (ks < npages[s_idx])
        sim, hot, cnt = access_scan(sim, hot, t, cnt, rid_s, ks, en_d,
                                    False, en_d & measure_step)
        slot_done = slot_done + slot_valid.astype(jnp.int32)

        # ---- 6. retire ------------------------------------------------
        fin = slot_valid & (slot_done >= slot_max)
        n_ret = jnp.sum(fin.astype(jnp.int32))
        occ = jnp.sum(slot_valid.astype(jnp.int32))  # post-admit
        slot_rid = jnp.where(fin, -1, slot_rid)
        qlen = jnp.sum((q_rid >= 0).astype(jnp.int32))

        stats["arrived"] = stats["arrived"] + n_new
        stats["dropped"] = stats["dropped"] + (want - n_new)
        stats["admitted"] = stats["admitted"] + n_adm
        stats["retired"] = stats["retired"] + n_ret
        stats["preempted"] = stats["preempted"] + pe.astype(jnp.int32)
        stats["occ_sum"] = stats["occ_sum"] + occ
        stats["qlen_sum"] = stats["qlen_sum"] + qlen

        new_st = LoopState(
            sim=sim, hot=hot,
            slot_rid=slot_rid, slot_done=slot_done, slot_max=slot_max,
            slot_pages=slot_pages,
            q_rid=q_rid, q_done=q_done, q_max=q_max, q_pages=q_pages,
            q_touch=q_touch, q_seq=q_seq,
            n_arrived=n_arrived, next_seq=next_seq,
            now=t + p.cycles_per_step, stats=stats)
        ys = (occ, qlen, n_new) if shape.collect_steps else None
        return new_st, ys

    return step


def _run_serving_impl(shape: ServingShape, p: ServingParams, warmup,
                      counts):
    if counts is None:
        counts = arr_mod.step_counts(
            jnp, p.arrival, jnp.arange(shape.n_steps, dtype=jnp.int32))
    step = _make_step(shape, p, warmup)
    final, ys = jax.lax.scan(
        step, _init_loop_state(shape),
        (jnp.arange(shape.n_steps, dtype=jnp.int32),
         counts.astype(jnp.int32)))
    return final.sim.stats, final.stats, final.now, ys


def _serve_reduce(shape: ServingShape, sim_stats, serve_stats, now,
                  reduce_keys):
    """[grid, len(reduce_keys)] i32 column stack — the serving form of
    ``simulator._reduce_device`` (``total_cycles`` is the final clock,
    ``n_steps`` the static horizon)."""
    cols = []
    for k in reduce_keys:
        if k == "total_cycles":
            cols.append(now)
        elif k == "n_steps":
            cols.append(jnp.full_like(now, shape.n_steps))
        elif k in serve_stats:
            cols.append(serve_stats[k])
        elif k in sim_stats:
            cols.append(sim_stats[k])
        else:  # a bank-group counter off that path (DESIGN.md §16)
            cols.append(jnp.zeros_like(now))
    return jnp.stack(cols, axis=-1)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _run_serving_batched(shape: ServingShape, params: ServingParams,
                         warmups, reduce_keys=None):
    """The serving grid engine: arrivals drawn on device per point.
    All ``params`` leaves and ``warmups`` carry a leading [grid] axis;
    one compilation serves every (policy, arrival, mechanism, geometry)
    point — the one-compile fact ``benchmarks/serving_loop.py`` asserts.
    With ``reduce_keys`` (static) set, the on-device §13 reduction runs
    inside the same compiled program.
    """
    out = jax.vmap(
        lambda p, w: _run_serving_impl(shape, p, w, None))(
        params, warmups)
    if reduce_keys is None:
        return out
    return _serve_reduce(shape, out[0], out[1], out[2], reduce_keys)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _run_serving_pinned(shape: ServingShape, params: ServingParams,
                        warmups, counts, reduce_keys=None):
    """Pinned-arrival variant: per-point [grid, n_steps] counts override
    the drawn process (the host-parity harness)."""
    out = jax.vmap(
        lambda p, w, c: _run_serving_impl(shape, p, w, c))(
        params, warmups, counts)
    if reduce_keys is None:
        return out
    return _serve_reduce(shape, out[0], out[1], out[2], reduce_keys)


def _resolve_static(specs: Sequence[ServingSpec],
                    collect_steps: bool,
                    sim_shape: sim_mod.SimShape) -> ServingShape:
    s0 = specs[0]
    for sp in specs:
        assert sp.max_batch == s0.max_batch, \
            "serving grids must share max_batch"
        assert sp.queue_cap == s0.queue_cap
        assert sp.arrivals_max == s0.arrivals_max
        assert sp.hot_ways == s0.hot_ways
        assert sp.hot_exact == s0.hot_exact
    hot_sets_max = max(sp.hot_cfg().n_sets for sp in specs)
    return ServingShape(
        sim=sim_shape,
        hot=hcl.padded_shape(s0.hot_cfg(), hot_sets_max),
        max_batch=s0.max_batch,
        queue_cap=s0.queue_cap,
        arrivals_max=s0.arrivals_max,
        prompt_pages_max=max(sp.arrival.prompt_pages_max for sp in specs),
        pages_max=max(sp.pages_max() for sp in specs),
        n_steps=max(sp.steps() for sp in specs),
        collect_steps=collect_steps,
    )


@functools.lru_cache(maxsize=4096)
def _point_rest_np(sp: ServingSpec):
    """One spec's non-mech traced params as flat numpy leaves, cached by
    the (hashable) ``ServingSpec`` — a 10⁵-point grid over a few dozen
    distinct serving specs stages from that many cache entries."""
    r = _RestParams(
        arrival=arr_mod.arrival_params(sp.arrival, sp.n_reqs),
        hot=hcl.params_of(sp.hot_cfg()),
        policy=pol_mod.build_blocks(sp),
        cycles_per_step=jnp.int32(sp.cycles_per_step),
        page_tokens=jnp.int32(sp.page_tokens),
    )
    leaves, treedef = jax.tree_util.tree_flatten(r)
    return tuple(np.asarray(x) for x in leaves), treedef


def stage_serving(grid, shape_grid=None, collect_steps: bool = False):
    """Host staging of a serving launch: the static ``ServingShape``
    plus numpy-stacked ``ServingParams``/warmups (the §13 runner stages
    the unique grid once and slices numpy views per chunk)."""
    grid = list(grid)
    assert grid, "empty serving sweep grid"
    shape_grid_l = list(shape_grid) if shape_grid is not None else grid
    for cfg in grid + shape_grid_l:
        assert cfg.serving is not None, (
            "run_sweep needs cfg.serving set on every grid point")
    sshape, mech_stacked = sim_mod._grid_shape_and_params(grid, shape_grid)
    shape = _resolve_static(
        [cfg.serving for cfg in grid + shape_grid_l], collect_steps,
        sshape)

    n_steps = shape.n_steps
    assert n_steps < 2**24, "serving stream too long for the scan horizon"
    max_cps = max(cfg.serving.cycles_per_step for cfg in grid)
    slack = _INTRA * (shape.arrivals_max * shape.prompt_pages_max
                      + shape.max_batch * shape.pages_max)
    assert n_steps * max_cps + slack < 2**30, (
        "serving clock exceeds the int32 cycle horizon — lower n_steps "
        "or cycles_per_step")

    rest = sim_mod._stack_cached(
        grid,
        point_key=lambda cfg: cfg.serving,
        point_leaves=lambda cfg: _point_rest_np(cfg.serving))
    params = ServingParams(mech=mech_stacked, arrival=rest.arrival,
                           hot=rest.hot, policy=rest.policy,
                           cycles_per_step=rest.cycles_per_step,
                           page_tokens=rest.page_tokens)
    # steps-based warmup: the measured window of the DRAM-side stats
    warmups = np.asarray(
        [int(cfg.warmup_frac * n_steps) for cfg in grid], np.int32)
    return shape, params, warmups


def _launch_serving(shape: ServingShape, params: ServingParams, warmups,
                    counts, n_grid: int, reduce_keys: tuple | None = None):
    """Async dispatch of one serving launch (unblocked device out)."""
    if counts is not None:
        counts = np.asarray(counts, np.int32)
        if counts.ndim == 1:
            counts = np.broadcast_to(counts, (n_grid,) + counts.shape)
        assert counts.shape == (n_grid, shape.n_steps), (
            f"pinned counts must be [n_steps={shape.n_steps}] or "
            f"[G={n_grid}, n_steps]; got {counts.shape}")
        counts = np.ascontiguousarray(counts)
        (params, warmups, counts), _ = sim_mod._shard_grid(
            (params, warmups, counts), n_grid)
        return _run_serving_pinned(shape, params, warmups, counts,
                                   reduce_keys)
    (params, warmups), _ = sim_mod._shard_grid(
        (params, warmups), n_grid)
    return _run_serving_batched(shape, params, warmups, reduce_keys)


def _drain_serving(out, grid, shape: ServingShape, n_grid: int,
                   reduce_keys: tuple | None = None):
    if reduce_keys is not None:
        with span("d2h"):
            return np.asarray(out)[:n_grid]
    sim_stats, serve_stats, final_now, ys = out
    with span("d2h"):
        sim_np = {k: np.asarray(v) for k, v in sim_stats.items()}
        serve_np = {k: np.asarray(v) for k, v in serve_stats.items()}
        now_np = np.asarray(final_now)
        ys_np = (None if ys is None
                 else tuple(np.asarray(y) for y in ys))
    n_steps = shape.n_steps
    out_rows = []
    with span("finalize"):
        for g in range(n_grid):
            res = sim_mod._finalize(
                {k: v[g] for k, v in sim_np.items()}, now_np[g:g + 1],
                (None, None), np.asarray([grid[g].serving.n_reqs]),
                grid[g])
            for k in SERVE_STAT_KEYS:
                res[k] = int(serve_np[k][g])
            res["n_steps"] = n_steps
            # derived serving scalars come from the same registry table
            # the reduce path applies — one formula source (DESIGN.md §13)
            metrics_lib.finalize_scalars(res)
            if ys_np is not None:
                res["steps"] = {"occ": ys_np[0][g], "qlen": ys_np[1][g],
                                "arrivals": ys_np[2][g]}
            out_rows.append(res)
    return out_rows


def run_sweep(grid, shape_grid=None, counts=None,
              collect_steps: bool = False,
              reduce_keys: tuple | None = None):
    """Evaluate a serving config grid — every ``cfg.serving`` set — as
    one vmapped fused scan (the serving analogue of ``sweep_synth``).

    ``shape_grid`` pads static facts for a larger grid than launched
    (the experiment runner's chunking mode), ``counts`` pins the
    per-step arrival schedule ([n_steps] shared or [G, n_steps]) for
    the host-parity harness, and ``collect_steps`` returns per-step
    (occupancy, queue length, arrivals) arrays per point.  With
    ``reduce_keys`` (entries of ``SERVE_REDUCE_KEYS``) the launch
    reduces on device and returns ``[grid, n_keys]`` int32 (per-step
    arrays are never collected in this mode).
    """
    grid = list(grid)
    if reduce_keys is not None:
        collect_steps = False
    shape, params, warmups = stage_serving(grid, shape_grid,
                                           collect_steps)
    n_grid = len(grid)
    out = _launch_serving(shape, params, warmups, counts, n_grid,
                          reduce_keys)
    return _drain_serving(out, grid, shape, n_grid, reduce_keys)


def simulate_serving(cfg, counts=None, collect_steps: bool = True) -> dict:
    """One serving grid point, fused end to end (the single-point view
    of ``run_sweep``; per-step arrays collected by default)."""
    assert cfg.serving is not None, "simulate_serving needs cfg.serving"
    return run_sweep([dataclasses.replace(cfg, backend="ref")],
                     counts=counts, collect_steps=collect_steps)[0]
