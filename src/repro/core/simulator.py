"""Trace-driven DRAM system simulator (the Ramulator stand-in), in JAX.

One ``lax.scan`` step = one memory request, end to end:

1. **CPU issue model** — each core issues its next request after its
   front-end gap, subject to an 8-entry MSHR window and (for dependent
   requests) the previous request's completion — Table 5.1's 3-wide,
   128-entry-window core reduced to the memory-facing behaviour that the
   mechanism responds to.  The core with the earliest issue time goes next
   (multi-core interleaving is therefore *dynamic*: lower DRAM latency
   re-times every subsequent request, which is what produces speedup).
2. **Memory controller / bank state machine** — row hit / closed / conflict
   resolution with full DDR3 timing (tRCD/tRAS/tRP/tCL/tCWL/tBL/tRTP/tWR,
   command and data bus serialization, rolling refresh stalls), open-row or
   closed-row policy (closed-row uses per-bank queue-hit lookahead).
3. **Mechanisms** — ChargeCache (HCRAC insert on PRE, lookup on ACT,
   lowered tRCD/tRAS on hit), NUAT (closed-form time-since-refresh bins),
   ChargeCache+NUAT (min of both), LL-DRAM (always lowered), or baseline.

Stats (hit rates, RLTL histograms, latency, per-core end times, energy
counters) accumulate in-scan with warm-up masking.

**Batched experiment engine** (DESIGN.md §4, §8): a configuration is
split into a static *shape* (``SimShape`` — the padded DRAM envelope,
HCRAC array sizes, MSHR depth) and a traced *params* pytree
(``MechParams`` — every timing value, the active DRAM geometry
(``GeomParams``), HCRAC capacity/duration, one gated param block per
registered mechanism policy).  The scan body takes params as data,
folds trace addresses into the active geometry by modular arithmetic
(``dram.fold_address``), and delegates timing
selection to the mechanism registry (``repro.experiment.registry``), so
mechanism choice is a fold of data-driven policies rather than Python
branching, one compiled program serves every registered mechanism kind,
and ``sweep()`` evaluates a whole evaluation grid by ``vmap``-ing over
stacked params — one XLA compilation for the entire grid, sharded across
devices when more than one is available.

Approximations vs. Ramulator (documented in DESIGN.md): the default
*in-order* controller tier approximates FR-FCFS by per-bank in-order
service with dynamic multi-core interleave + closed-row queue-hit
lookahead, and leaves tRRD/tFAW unenforced (second-order for the studied
mechanism, which alters tRCD/tRAS only).  The opt-in
``SimConfig.controller="frfcfs"`` tier (``repro.controller``, DESIGN.md
§15) removes both approximations: a real bounded request window with
row-hit-first / oldest-first selection and per-rank tRRD/tFAW sliding
ACT windows, cross-validated against a cycle-stepped numpy host oracle.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aldram as aldram_lib
from repro.core import hcrac as hcrac_lib
from repro.core import dram as dram_lib
from repro.core.dram import (DRAMConfig, DDR3_SYSTEM, DRAMEnvelope,
                             GeomParams, InterleaveConfig, NO_ROW,
                             envelope_of, fold_address, geom_params,
                             interleave_params, refresh_adjust,
                             time_since_refresh)
from repro.core import timing as timing_lib
from repro.core.timing import (TimingParams, TimingVec, DDR3_1600,
                               ms_to_cycles)
from repro.core.traces import TraceBatch, WorkloadSpec, WORKLOAD_BY_NAME
from repro.core import mechanisms as registry
from repro.core import metrics as metrics_lib
from repro.core.mechanisms import default_nuat_bins  # noqa: F401 (re-export)
from repro import obs
from repro.obs import span

# np scalar so Pallas kernel bodies may close over it (see dram.NO_ROW)
INF = np.int32(2**30)

#: RLTL histogram bucket upper edges, in ms (thesis Fig 3.2 uses
#: 0.125..32 ms; we add finer + coarser tails).
RLTL_EDGES_MS = (0.125, 0.25, 0.5, 1.0, 2.0, 4.0, 8.0, 16.0, 32.0)


@dataclasses.dataclass(frozen=True)
class MechanismConfig:
    #: any kind registered in ``repro.experiment.registry`` (builtins:
    #: base | chargecache | nuat | cc_nuat | lldram | rltl | aldram |
    #: cc_aldram)
    kind: str = "chargecache"
    hcrac: hcrac_lib.HCRACConfig = hcrac_lib.HCRACConfig()
    lowered: TimingParams = dataclasses.field(
        default_factory=lambda: DDR3_1600.with_reduction(4, 8))
    nuat_bins: tuple = ()
    #: AL-DRAM module profile (temperature / process bin) — consumed by
    #: the ``aldram`` policy's per-bank timing table (DESIGN.md §9)
    aldram: aldram_lib.ALDRAMConfig = aldram_lib.ALDRAMConfig()
    #: piecewise-constant temperature drift along the stream (DESIGN.md
    #: §14): scales the leak clock NUAT bins read and re-derives the
    #: AL-DRAM per-bank tables per segment.  Empty = no drift (bitwise
    #: identical to the pre-drift engine).
    thermal: aldram_lib.ThermalConfig = aldram_lib.ThermalConfig()

    def __post_init__(self):
        assert self.kind in registry.names(), (
            f"unregistered mechanism kind {self.kind!r}; "
            f"known: {registry.names()}")
        if "nuat" in registry.components(self.kind) and not self.nuat_bins:
            object.__setattr__(self, "nuat_bins", default_nuat_bins())


@dataclasses.dataclass(frozen=True)
class SimConfig:
    dram: DRAMConfig = DDR3_SYSTEM
    timing: TimingParams = DDR3_1600
    mech: MechanismConfig = MechanismConfig()
    policy: str = "open"      # "open" (1-core) | "closed" (8-core), Table 5.1
    mshr: int = 8
    warmup_frac: float = 0.05
    #: synthetic-workload selection for the streamed-generation path
    #: (``simulate_synth`` / ``sweep_synth``, DESIGN.md §10); ``None``
    #: means trace-driven (a ``TraceBatch`` is supplied by the caller)
    workload: WorkloadSpec | None = None
    #: channel-interleave policy for on-device address composition —
    #: only consumed when ``workload`` is set (host traces address
    #: global banks directly, the "bank" identity policy)
    interleave: InterleaveConfig = InterleaveConfig()
    #: engine tier for the batched entry points (DESIGN.md §11):
    #: "ref" is the authoritative ``lax.scan`` engine; "pallas" routes
    #: ``sweep()`` / ``sweep_synth()`` through the ``kernels.sim_step``
    #: Pallas kernel (grid-parallel over the sweep batch dimension,
    #: interpret-mode on CPU) — bitwise-identical by contract (tested).
    #: ``simulate()`` / ``simulate_synth()`` are the single-point
    #: *reference* views and always run the ref engine.
    backend: str = "ref"
    #: serving-loop selection (a ``repro.serving.loop.ServingSpec``) for
    #: the fused continuous-batching path (``simulate_serving`` /
    #: ``sweep_serving``, DESIGN.md §12); ``None`` means trace- or
    #: workload-driven as above
    serving: object | None = None
    #: refresh tier (DESIGN.md §14): "stateful" (default) issues REF
    #: commands from per-bank counters in the scan carry — the bank
    #: blocks for tRFC and the leak clock keys off the *actual* last
    #: REF; "legacy" keeps the closed-form ``refresh_adjust`` blackout
    #: (group-gated) as an opt-in parity tier.  A traced leaf, so mixed
    #: refresh × mechanism grids share one compile.
    refresh_mode: str = "stateful"
    #: controller tier (DESIGN.md §15): "inorder" is the classic engine
    #: above — one request serviced per scan step in earliest-issue
    #: order; "frfcfs" routes the launch through the
    #: ``repro.controller`` window engine: a bounded FR-FCFS scheduler
    #: window with row-hit-first / oldest-first selection (masked
    #: argmin in the scan carry) and per-rank tRRD/tFAW ACT windows.
    #: A grid containing any frfcfs point runs whole through the window
    #: engine (one compile); its in-order points run with ``win_cap=1``,
    #: bitwise-identical to the ref engine (tested).
    controller: str = "inorder"
    #: FR-FCFS scheduler window depth (requests visible to selection
    #: per scheduling decision); consumed only when controller="frfcfs"
    window: int = 8

    def __post_init__(self):
        assert self.policy in ("open", "closed")
        assert self.refresh_mode in ("legacy", "stateful"), self.refresh_mode
        assert self.backend in ("ref", "pallas"), self.backend
        if self.serving is not None:
            assert self.backend == "ref", (
                "the serving loop runs the ref engine only")
        assert self.controller in ("inorder", "frfcfs"), self.controller
        assert self.window >= 1, self.window
        if self.controller == "frfcfs":
            assert self.backend == "ref", (
                "the FR-FCFS controller tier runs the ref engine only "
                "(the sim_step kernel models the in-order scan)")
            assert self.serving is None, (
                "the serving loop models the in-order controller only")


# --------------------------------------------------------------------------
# Static shape vs traced params (the batched experiment engine's core split)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SimShape:
    """The static half of a configuration: everything that determines array
    shapes or trace structure.  Two configs with equal ``SimShape`` (and
    equal trace/step shapes) share one XLA compilation; all remaining
    knobs — including the *active* DRAM geometry — live in ``MechParams``
    and are traced."""
    envelope: DRAMEnvelope        # padded geometry layout (DESIGN.md §8)
    hcrac: hcrac_lib.HCRACConfig  # shape carrier: max sets / ways / expiry
    mshr: int


class MechParams(NamedTuple):
    """The traced half: one pytree of int32/bool scalars plus one params
    block per registered mechanism policy (``mech[name]`` — every block
    present at every grid point, gated by its traced ``enable`` leaf).
    ``sweep()`` stacks these along a leading grid axis and ``vmap``s the
    simulator over it."""
    timing: TimingVec            # full DDR3 timing set, traced
    geom: GeomParams             # active DRAM geometry, traced
    closed_policy: jnp.ndarray   # bool: closed-row policy (auto-precharge)
    hcrac: hcrac_lib.HCRACParams
    mech: dict                   # registry blocks: {policy: {leaf: array}}
    refresh_stateful: jnp.ndarray  # bool: stateful REF tier (DESIGN.md §14)
    thermal: aldram_lib.ThermalParams  # temperature drift along the stream
    # controller tier (DESIGN.md §15): both leaves are only consumed by
    # the repro.controller window engine — the in-order engines ignore
    # them, so the ref/pallas tiers stay bitwise-intact
    frfcfs: jnp.ndarray          # bool: enforce tRRD/tFAW + FR-FCFS select
    win_cap: jnp.ndarray         # int32 active window depth (1 = in-order)


def sim_shape(cfg: SimConfig, n_sets_max: int | None = None,
              envelope: DRAMEnvelope | None = None) -> SimShape:
    """The static shape of ``cfg``; ``n_sets_max`` pads the HCRAC arrays
    and ``envelope`` pads the DRAM geometry so a whole grid shares one
    shape."""
    h = cfg.mech.hcrac
    env = envelope if envelope is not None else envelope_of([cfg.dram])
    assert env.covers(cfg.dram), (env, cfg.dram)
    return SimShape(
        envelope=env,
        hcrac=hcrac_lib.padded_shape(h, n_sets_max or h.n_sets),
        mshr=cfg.mshr,
    )


def _check_point(cfg: SimConfig) -> None:
    """Refuse a grid point whose numbers the engine would misread.  Run
    on every launched point (``mech_params``), not on the intermediate
    configs an ``Experiment``'s axes pass through."""
    # the bank-group path compiles only for a bank-grouped envelope
    # (DESIGN.md §16): a rule it would silently drop is refused
    assert not cfg.timing.bank_grouped or cfg.dram.n_bank_groups > 1, (
        "bank-group timings (tCCD_S/tCCD_L/tRRD_L) need a bank-grouped "
        "geometry (DRAMConfig.n_bank_groups > 1)")
    # the lowered set must be in this point's clock: the duration
    # axis derives it from the point's timing, so a timing axis
    # applied after it (or a hand-built DDR3 set) is refused
    uses_lowered = any("lowered" in registry.get(n).consumes
                       for n in registry.components(cfg.mech.kind))
    assert not uses_lowered or \
        cfg.mech.lowered.tCK_ns == cfg.timing.tCK_ns, (
            "the mechanism's lowered timings are in another clock's "
            "cycles than the point's timing: derive them with "
            "lowered_for_duration(ms, timing), or put a timing axis "
            "before the duration_ms axis")
    # NUAT's default bins are DDR3-1600 cycles; another clock needs
    # its own (``default_nuat_bins(timing)``), never DDR3's silently
    assert not ("nuat" in registry.components(cfg.mech.kind)
                and cfg.timing.tCK_ns != timing_lib.CYCLE_NS
                and cfg.mech.nuat_bins == default_nuat_bins()), (
        "NUAT bins default to DDR3-1600 cycles; pass "
        "MechanismConfig(nuat_bins=default_nuat_bins(timing)) for a "
        "timing set with another clock")


def mech_params(cfg: SimConfig, hints: dict | None = None,
                envelope: DRAMEnvelope | None = None) -> MechParams:
    """Flatten ``cfg``'s numeric content into the traced params pytree.

    Each registered mechanism policy contributes its own block (see
    ``repro.experiment.registry``); ``hints`` carries grid-wide padding
    facts (e.g. the max NUAT bin count) so every point of a sweep shares
    one block structure.  ``envelope`` is the grid's padded geometry
    (defaults to this config's exact envelope, matching ``sim_shape``);
    its bank count is injected into every policy's hints as the reserved
    ``n_banks_padded`` key, so per-bank param tables (the ``aldram``
    block) size to the shared envelope.  All padding is
    behaviour-neutral (bitwise).
    """
    _check_point(cfg)
    env = envelope if envelope is not None else envelope_of([cfg.dram])
    hints = hints if hints is not None else registry.pad_hints([cfg.mech])
    hints = {n: {**h, "n_banks_padded": env.max_banks_total}
             for n, h in hints.items()}
    # grid-wide thermal segment count (the aldram policy's pad hint); a
    # no-drift grid has S == 0 and every drift branch is statically gone
    n_segs = hints.get("aldram", {}).get("n_segs", cfg.mech.thermal.n_segs)
    th_en, th_edge, th_leak = aldram_lib.thermal_params_np(
        cfg.mech.thermal, n_segs, cfg.timing.tCK_ns)
    return MechParams(
        timing=timing_lib.traced(cfg.timing),
        geom=geom_params(cfg.dram),
        closed_policy=jnp.bool_(cfg.policy == "closed"),
        hcrac=hcrac_lib.params_of(cfg.mech.hcrac),
        mech=registry.build_blocks(cfg.mech, cfg.timing, hints),
        refresh_stateful=jnp.bool_(cfg.refresh_mode == "stateful"),
        thermal=aldram_lib.ThermalParams(
            enable=jnp.asarray(th_en),
            seg_edge=jnp.asarray(th_edge),
            seg_leak=jnp.asarray(th_leak)),
        frfcfs=jnp.bool_(cfg.controller == "frfcfs"),
        win_cap=jnp.int32(cfg.window if cfg.controller == "frfcfs" else 1),
    )


class SimState(NamedTuple):
    # per-core issue model
    ptr: jnp.ndarray           # [C] next request index
    last_issue: jnp.ndarray    # [C]
    last_complete: jnp.ndarray  # [C]
    mshr_ring: jnp.ndarray     # [C, MSHR] completion times
    ring_idx: jnp.ndarray      # [C]
    core_end: jnp.ndarray      # [C] completion of last request so far
    # per-bank state (NB = the padded envelope's max_banks_total; banks
    # beyond the traced active count are never addressed)
    open_row: jnp.ndarray      # [NB]
    ready_act: jnp.ndarray     # [NB]
    ready_rdwr: jnp.ndarray    # [NB]
    ready_pre: jnp.ndarray     # [NB]
    last_pre_gid: jnp.ndarray  # [NB] row id of the bank's latest PRE
    last_pre_t: jnp.ndarray    # [NB] cycle of that PRE (RLTL registers)
    ref_k: jnp.ndarray         # [NB] REF windows issued so far (stateful
                               # refresh tier, DESIGN.md §14)
    last_ref_t: jnp.ndarray    # [NB] issue cycle of the bank's latest REF
    # per-channel buses
    cmd_bus_free: jnp.ndarray  # [NCH]
    data_bus_free: jnp.ndarray  # [NCH]
    # mechanism state
    hcrac: hcrac_lib.HCRACState
    # accumulators (int32 scalars; NO large arrays — see perf note in _run)
    stats: dict
    # bank-group column registers (DESIGN.md §16): the newest RD/WR cycle
    # per channel and per (rank, bank group) slot (``dram.bank_group_slot``).
    # None — no carry leaf at all — unless the envelope has bank groups.
    last_cas: jnp.ndarray | None = None     # [NCH]
    last_cas_bg: jnp.ndarray | None = None  # [NB]


STAT_KEYS = ("n_req", "lat_sum", "acts", "acts_lowered", "hcrac_hits",
             "hcrac_lookups", "row_hits", "row_closed", "row_conflicts",
             "reads", "writes", "pres", "act_ras_sum", "refresh8ms_acts",
             "refs_issued", "ref_blocked_cycles")

#: the bank-group counters (DESIGN.md §16), carried only on the bank-group
#: path and reported as 0 elsewhere: cycles tCCD_S/tCCD_L pushed a
#: measured RD/WR past every other rule, and cycles tRRD_L pushed a
#: measured ACT past tRRD/tFAW (FR-FCFS tier)
BG_STAT_KEYS = ("ccd_wait_cycles", "rrd_l_wait_cycles")

#: [NB]-shaped stat accumulators (sized to the padded envelope, scattered
#: at the folded bank index, so entries past the active ``banks_total``
#: stay zero — the per-bank view AL-DRAM's offset study and the
#: geometry-masking tests read; DESIGN.md §9)
BANK_STAT_KEYS = ("bank_acts", "bank_act_ras_sum")

#: the integer metric *ingredients* a trace/synth launch can lower to a
#: ``[grid, n_deps]`` int32 array on device (DESIGN.md §13): the scalar
#: scan counters plus the engine-derived ``total_cycles`` (``max`` over
#: the per-core end times).  Serving launches extend this with their own
#: counters (``serving.loop.engine.SERVE_REDUCE_KEYS``).
REDUCE_KEYS = STAT_KEYS + BG_STAT_KEYS + ("total_cycles",)


def _reduce_device(raw_stats: dict, core_end, reduce_keys: tuple):
    """On-device metric-ingredient reduction: stack the requested scalar
    counters into an int32 ``[..., n_deps]`` column array.  Runs inside
    the engine jits (``reduce_keys`` is a static arg), so a reduced
    chunk launch transfers ``n_deps`` ints per point instead of the full
    stat pytree + per-bank arrays."""
    cols = []
    for k in reduce_keys:
        if k == "total_cycles":
            cols.append(jnp.max(core_end, axis=-1))
        elif k not in raw_stats:  # a bank-group counter off that path
            cols.append(jnp.zeros(core_end.shape[:-1], jnp.int32))
        else:
            cols.append(raw_stats[k])
    return jnp.stack(cols, axis=-1)


@functools.partial(jax.jit, static_argnums=(2,))
def _reduce_jit(raw_stats: dict, core_end, reduce_keys: tuple):
    """Standalone jitted reduction for engines whose launch is already
    compiled elsewhere (the Pallas kernel tier)."""
    return _reduce_device(raw_stats, core_end, reduce_keys)


class Events(NamedTuple):
    """Per-step ACT/PRE event record (scan outputs, for the RLTL post-pass).

    RLTL needs "cycle of last PRE of this row" at every ACT.  Keeping a
    [banks, rows] array in the scan carry and gathering from it is a ~300x
    slowdown on the CPU backend (the data-dependent read of an in-place
    carry buffer forces a full-array copy per step — measured).  Emitting
    events and matching ACTs to PREs in a vectorized post-pass is exact
    and keeps the carry tiny.
    """
    act_gid: jnp.ndarray    # global row id of ACT, -1 if none/unmeasured
    act_t: jnp.ndarray
    act_ref8: jnp.ndarray   # ACT within 8 ms of the row's refresh (bool)
    pre1_gid: jnp.ndarray   # conflict-PRE of the old open row, -1 if none
    pre1_t: jnp.ndarray
    pre2_gid: jnp.ndarray   # auto-PRE (closed-row policy), -1 if none
    pre2_t: jnp.ndarray
    pre3_gid: jnp.ndarray   # REF-implied PRE of the open row (stateful
    pre3_t: jnp.ndarray     # refresh tier, DESIGN.md §14), -1 if none


def _init_state(shape: SimShape, n_cores: int, max_len: int) -> SimState:
    nb = shape.envelope.max_banks_total
    nch = shape.envelope.max_channels
    z = lambda *s: jnp.zeros(s, jnp.int32)
    stats = {k: jnp.int32(0) for k in STAT_KEYS}
    stats.update({k: z(nb) for k in BANK_STAT_KEYS})
    bg = {}
    if shape.envelope.max_bank_groups > 1:
        stats.update({k: jnp.int32(0) for k in BG_STAT_KEYS})
        # deep in the past, so the first column command is unconstrained
        bg = dict(last_cas=jnp.full((nch,), -INF, jnp.int32),
                  last_cas_bg=jnp.full((nb,), -INF, jnp.int32))
    return SimState(
        ptr=z(n_cores), last_issue=z(n_cores), last_complete=z(n_cores),
        mshr_ring=z(n_cores, shape.mshr), ring_idx=z(n_cores),
        core_end=z(n_cores),
        open_row=jnp.full((nb,), NO_ROW, jnp.int32),
        ready_act=z(nb), ready_rdwr=z(nb), ready_pre=z(nb),
        last_pre_gid=jnp.full((nb,), -1, jnp.int32), last_pre_t=z(nb),
        ref_k=z(nb), last_ref_t=z(nb),
        cmd_bus_free=z(nch), data_bus_free=z(nch),
        hcrac=hcrac_lib.init(shape.hcrac),
        stats=stats,
        **bg,
    )


def _acc(stats, key, val):
    stats[key] = stats[key] + jnp.asarray(val, jnp.int32)


def _service(shape: SimShape, p: MechParams, st: SimState, t_arr, bank, row,
             is_write, next_same, measure, enable, act_floor=None,
             act_floor_bg=None):
    """Serve one request; returns (new bank/bus/hcrac state pieces, done).

    ``enable`` marks a live scan step: padded no-op steps (see ``_run``)
    still trace through here, but their state writes are discarded by the
    caller and their events are masked out below.

    ``act_floor`` is the FR-FCFS controller tier's rank-constraint hook
    (DESIGN.md §15): when given, an activating request's ACT is delayed
    to at least that cycle (the caller's per-rank tRRD/tFAW window), and
    the return grows a fourth element ``(t_act, needs_act)`` so the
    caller can update its rank ACT registers.  ``None`` (every in-order
    caller) leaves the traced computation statically identical to the
    pre-controller engine.  ``act_floor_bg`` (bank-group path only) is
    the tighter floor with the same-group tRRD_L; the cycles it adds over
    ``act_floor`` count as ``rrd_l_wait_cycles``.

    The bank-group path (DESIGN.md §16) is compiled only when the
    envelope has bank groups (``max_bank_groups > 1``): a RD/WR then
    issues no earlier than tCCD_S after the channel's newest column
    command and tCCD_L after its (rank, bank group)'s.
    """
    T = p.timing
    geom = p.geom
    hshape = shape.hcrac
    ch = dram_lib.channel_of(geom, bank)
    stats = dict(st.stats)
    bg_path = shape.envelope.max_bank_groups > 1

    t0 = jnp.maximum(t_arr, st.cmd_bus_free[ch])

    # HCRAC substrate gate: any registered policy that declared
    # ``uses_hcrac`` and is enabled at this grid point (traced data).
    hc_gate = registry.hcrac_gate(p.mech)

    # --- rolling refresh (DESIGN.md §14) ---------------------------------
    # Two tiers selected by the traced ``refresh_stateful`` leaf.  The
    # stateful tier catches the bank's per-bank REF counter up to the
    # schedule (window k's REF issues at k*tREFI and refreshes group
    # k mod n_refresh_groups): only the newest pending REF can still
    # block — earlier ones completed during the bank's idle windows — so
    # the catch-up is O(1) per step.  A REF implies a precharge (folded
    # into tRFC), which closes the open row, restores its charge (HCRAC
    # insert, like any PRE) and advances every bank-ready clock to the
    # end of the tRFC blackout.
    stateful = p.refresh_stateful
    legacy = ~stateful
    ref_due = t0 // T.tREFI + 1           # REFs scheduled at or before t0
    n_pend = jnp.maximum(ref_due - st.ref_k[bank], 0)
    do_ref = stateful & (n_pend > 0) & enable
    busy0 = jnp.maximum(jnp.maximum(st.ready_act[bank], st.ready_pre[bank]),
                        st.ready_rdwr[bank])
    ref_t = jnp.maximum((ref_due - 1) * T.tREFI, st.ready_pre[bank])
    ref_done = ref_t + T.tRFC
    openr0 = st.open_row[bank]
    ref_pre = do_ref & (openr0 != NO_ROW)
    openr = jnp.where(do_ref, NO_ROW, openr0)
    clamp = lambda rdy: jnp.where(do_ref, jnp.maximum(rdy, ref_done), rdy)
    r_act_b = clamp(st.ready_act[bank])
    r_pre_b = clamp(st.ready_pre[bank])
    r_rdwr_b = clamp(st.ready_rdwr[bank])
    gid_ref = dram_lib.global_row_id(geom, bank,
                                     jnp.where(ref_pre, openr0, 0))
    hc0 = hcrac_lib.insert(hshape, st.hcrac, gid_ref, ref_t,
                           enable=ref_pre & hc_gate, params=p.hcrac)
    # legacy tier: the closed-form blackout, gated to the request row's
    # refresh group (matching dram.py's rolling schedule — satellite 2)
    radj = lambda tt: jnp.where(legacy, refresh_adjust(T, tt, row), tt)

    is_hit = openr == row
    is_closed = openr == NO_ROW
    is_conflict = ~is_hit & ~is_closed

    # --- conflict path: PRE the open row (insert it into the HCRAC) ------
    t_pre = radj(jnp.maximum(t0, r_pre_b))
    gid_old = dram_lib.global_row_id(geom, bank,
                                     jnp.where(is_conflict, openr, 0))
    hc = hcrac_lib.insert(hshape, hc0, gid_old, t_pre,
                          enable=is_conflict & hc_gate & enable,
                          params=p.hcrac)

    # --- ACT ---------------------------------------------------------------
    t_act = jnp.where(
        is_conflict,
        radj(t_pre + T.tRP),
        radj(jnp.maximum(t0, r_act_b)))
    needs_act = ~is_hit
    if act_floor is not None:
        # FR-FCFS rank windows: only an actual ACT is floor-constrained
        # (a row hit issues no ACT; its t_act is only a mechanism-clock
        # read and must stay untouched)
        t_act = jnp.where(needs_act, jnp.maximum(t_act, act_floor), t_act)
    if act_floor_bg is not None:
        t_act_s = t_act
        t_act = jnp.where(needs_act, jnp.maximum(t_act, act_floor_bg), t_act)
        _acc(stats, "rrd_l_wait_cycles",
             measure.astype(jnp.int32) * (t_act - t_act_s))

    gid = dram_lib.global_row_id(geom, bank, row)
    cc_hit, hc = hcrac_lib.lookup(hshape, hc, gid, t_act, enable=enable,
                                  params=p.hcrac)
    cc_hit = cc_hit & needs_act & hc_gate

    # per-bank last-PRE registers: cycles since this row's own latest PRE,
    # exact when it was the bank's most recent PRE (the RLTL mechanism's
    # signal; per-bank t_act monotonicity keeps the difference >= 0).
    tslp = jnp.where(st.last_pre_gid[bank] == gid,
                     t_act - st.last_pre_t[bank], INF)

    # mechanism timing selection: fold the registered policies over the
    # baseline timings, in registration order (LL-DRAM base, then
    # ChargeCache hit override, then NUAT minimum — DESIGN.md §7.2).
    # Selection stays data-driven: each policy gates on its own traced
    # ``enable`` leaf, so one compiled body serves every registered kind.
    # leak clock: the legacy tier uses the closed-form schedule phase;
    # the stateful tier keys off the *actual* last REF of the row's
    # group.  Post-catch-up the bank's newest REF index is kw; the
    # newest window that refreshed group g is j_g (≡ g mod groups).  If
    # that is the bank's own newest REF its true (possibly delayed)
    # issue cycle is the carry's register; older windows' REFs completed
    # on schedule at j_g*tREFI.  Windows before the stream start fall
    # back to the closed form (the pre-history schedule).
    tsr_closed = time_since_refresh(geom, T, row, t_act)
    kw = ref_due - 1
    j_g = kw - jnp.mod(kw - jnp.mod(row, T.n_refresh_groups),
                       T.n_refresh_groups)
    new_last_ref_t = jnp.where(do_ref, ref_t, st.last_ref_t[bank])
    t_ref = jnp.where(j_g == kw, new_last_ref_t, j_g * T.tREFI)
    tsr = jnp.where(stateful & (j_g >= 0),
                    jnp.maximum(t_act - t_ref, 0), tsr_closed)
    # thermal drift (DESIGN.md §14): in hot segments the leak clock runs
    # fast — NUAT sees an *effective* age scaled by the leak-rate
    # multiplier.  S == 0 (no drift anywhere in the grid) skips this
    # statically, keeping the no-drift engine bitwise intact.
    if p.thermal.seg_edge.shape[-1] > 0:
        seg = jnp.sum((t_act >= p.thermal.seg_edge).astype(jnp.int32)) - 1
        seg = jnp.clip(seg, 0, p.thermal.seg_edge.shape[-1] - 1)
        tsr_eff = jnp.where(
            p.thermal.enable,
            jnp.round(tsr.astype(jnp.float32)
                      * p.thermal.seg_leak[seg]).astype(jnp.int32),
            tsr)
    else:
        seg = jnp.int32(0)
        tsr_eff = tsr
    ctx = registry.SelectCtx(timing=T, geom=geom, hcrac_hit=cc_hit,
                             tsr=tsr_eff, tslp=tslp, needs_act=needs_act,
                             bank=bank, seg=seg)
    rcd, ras = registry.select_timings(p.mech, ctx)
    lowered_used = needs_act & ((rcd < T.tRCD) | (ras < T.tRAS))

    # --- READ / WRITE -------------------------------------------------------
    t_rdwr_act = t_act + rcd
    t_rdwr_hit = jnp.maximum(t0, r_rdwr_b)
    t_rdwr = jnp.where(is_hit, t_rdwr_hit, t_rdwr_act)
    cas = jnp.where(is_write, T.tCWL, T.tCL)
    # data bus occupancy: burst occupies [t_rdwr + cas, + tBL)
    t_rdwr = jnp.maximum(t_rdwr, st.data_bus_free[ch] - cas)
    # legacy tier: the RD/WR command *and* its burst must clear the
    # blackout window too, like PRE/ACT above (satellite 1 — the burst
    # used to be issued straight through the tRFC blackout)
    clamp_rw = lambda tt: jnp.where(
        legacy, dram_lib.refresh_clamp_span(T, tt, cas + T.tBL, row), tt)
    if bg_path:
        # column-to-column spacing: tCCD_S on the channel, tCCD_L within
        # the (rank, bank group); the wait is what it adds past every
        # other rule (the legacy clamp is monotone, so it is >= 0)
        g_slot = dram_lib.bank_group_slot(geom, bank)
        t_free = clamp_rw(t_rdwr)
        t_rdwr = jnp.maximum(t_rdwr, jnp.maximum(
            st.last_cas[ch] + T.tCCD_S, st.last_cas_bg[g_slot] + T.tCCD_L))
        t_rdwr = clamp_rw(t_rdwr)
        _acc(stats, "ccd_wait_cycles",
             measure.astype(jnp.int32) * (t_rdwr - t_free))
    else:
        t_rdwr = clamp_rw(t_rdwr)
    done = t_rdwr + cas + T.tBL

    # --- bank state updates -------------------------------------------------
    new_ready_rdwr = jnp.where(needs_act, t_act + rcd, r_rdwr_b)
    after_rw = jnp.where(is_write, done + T.tWR, t_rdwr + T.tRTP)
    new_ready_pre = jnp.maximum(
        jnp.where(needs_act, t_act + ras, r_pre_b), after_rw)

    # closed-row policy: auto-precharge unless the next queued request from
    # this core hits the same row (queue-hit lookahead).
    auto_pre = p.closed_policy & ~next_same
    t_autopre = new_ready_pre
    hc = hcrac_lib.insert(hshape, hc, gid, t_autopre,
                          enable=auto_pre & hc_gate & enable,
                          params=p.hcrac)
    new_open = jnp.where(auto_pre, NO_ROW, row)
    new_ready_act = jnp.where(
        auto_pre, t_autopre + T.tRP,
        jnp.where(is_conflict, t_pre + T.tRP, r_act_b))

    n_cmds = (1 + needs_act.astype(jnp.int32) + is_conflict.astype(jnp.int32)
              + auto_pre.astype(jnp.int32))
    new_cmd_free = jnp.maximum(st.cmd_bus_free[ch], t_arr) + n_cmds
    new_data_free = done

    # last-PRE registers: the auto-PRE (if any) postdates the conflict-PRE,
    # which postdates the REF's implied precharge
    lp_gid0 = jnp.where(ref_pre, gid_ref, st.last_pre_gid[bank])
    lp_t0 = jnp.where(ref_pre, ref_t, st.last_pre_t[bank])
    new_lp_gid = jnp.where(auto_pre, gid,
                           jnp.where(is_conflict, gid_old, lp_gid0))
    new_lp_t = jnp.where(auto_pre, t_autopre,
                         jnp.where(is_conflict, t_pre, lp_t0))

    # --- stats ---------------------------------------------------------------
    m = measure.astype(jnp.int32)
    _acc(stats, "n_req", m)
    _acc(stats, "lat_sum", m * (done - t_arr))
    _acc(stats, "acts", m * needs_act)
    _acc(stats, "acts_lowered", m * lowered_used)
    _acc(stats, "hcrac_lookups", m * (needs_act & hc_gate))
    _acc(stats, "hcrac_hits", m * cc_hit)
    _acc(stats, "row_hits", m * is_hit)
    _acc(stats, "row_closed", m * is_closed)
    _acc(stats, "row_conflicts", m * is_conflict)
    _acc(stats, "reads", m * ~is_write)
    _acc(stats, "writes", m * is_write)
    _acc(stats, "pres", m * (is_conflict.astype(jnp.int32)
                             + auto_pre.astype(jnp.int32)))
    _acc(stats, "act_ras_sum", m * needs_act * ras)
    ref8 = needs_act & measure & (tsr < T.cycles_8ms)
    _acc(stats, "refresh8ms_acts", ref8)
    # stateful-tier refresh stats: REFs observed at command arrivals, and
    # the blackout cycles a REF imposed beyond the bank's prior business
    # (legacy-tier blocking shows up in latency, not here — DESIGN.md §14)
    _acc(stats, "refs_issued", m * stateful.astype(jnp.int32) * n_pend)
    _acc(stats, "ref_blocked_cycles",
         jnp.where(do_ref & measure,
                   jnp.maximum(ref_done - jnp.maximum(t0, busy0), 0), 0))
    # per-bank scatter-adds: a masked (m=0) or padded step adds zero, and
    # ``bank`` is always < the active banks_total, so envelope-padded
    # entries stay exactly zero (the §8/§9 masking invariant, tested)
    stats["bank_acts"] = stats["bank_acts"].at[bank].add(m * needs_act)
    stats["bank_act_ras_sum"] = stats["bank_act_ras_sum"].at[bank].add(
        m * needs_act * ras)

    # ACT/PRE events for the RLTL post-pass (see Events docstring).
    # pre3 is the REF-implied precharge of the stateful refresh tier:
    # the post-pass sees refresh-driven PREs, not just request-driven
    # ones (the former DESIGN.md §14 caveat).  ``ref_pre`` already folds
    # ``enable`` in (via do_ref), and a REF-closed row can't also be a
    # conflict-PRE this step (openr is NO_ROW after the REF), so the two
    # streams never double-count one precharge.
    events = Events(
        act_gid=jnp.where(needs_act & measure, gid, -1),
        act_t=t_act,
        act_ref8=ref8,
        pre1_gid=jnp.where(is_conflict & enable, gid_old, -1),
        pre1_t=t_pre,
        pre2_gid=jnp.where(auto_pre & enable, gid, -1),
        pre2_t=t_autopre,
        pre3_gid=jnp.where(ref_pre, gid_ref, -1),
        pre3_t=ref_t,
    )

    # masked writes: a disabled (padded no-op) step must leave every state
    # word untouched.  Masking at the written element keeps the cost O(1)
    # per step — a whole-carry select would copy the HCRAC arrays each
    # step, which dominates the scan on the CPU backend (measured).
    w = lambda new, old: jnp.where(enable, new, old)
    new_st = st._replace(
        open_row=st.open_row.at[bank].set(w(new_open, openr)),
        ready_act=st.ready_act.at[bank].set(
            w(new_ready_act, st.ready_act[bank])),
        ready_rdwr=st.ready_rdwr.at[bank].set(
            w(new_ready_rdwr, st.ready_rdwr[bank])),
        ready_pre=st.ready_pre.at[bank].set(
            w(new_ready_pre, st.ready_pre[bank])),
        last_pre_gid=st.last_pre_gid.at[bank].set(
            w(new_lp_gid, st.last_pre_gid[bank])),
        last_pre_t=st.last_pre_t.at[bank].set(
            w(new_lp_t, st.last_pre_t[bank])),
        # do_ref already folds ``enable`` (and the stateful gate) in
        ref_k=st.ref_k.at[bank].set(
            jnp.where(do_ref, ref_due, st.ref_k[bank])),
        last_ref_t=st.last_ref_t.at[bank].set(new_last_ref_t),
        cmd_bus_free=st.cmd_bus_free.at[ch].set(
            w(new_cmd_free, st.cmd_bus_free[ch])),
        data_bus_free=st.data_bus_free.at[ch].set(
            w(new_data_free, st.data_bus_free[ch])),
        hcrac=hc,
        stats=stats,
    )
    if bg_path:
        new_st = new_st._replace(
            last_cas=st.last_cas.at[ch].set(w(t_rdwr, st.last_cas[ch])),
            last_cas_bg=st.last_cas_bg.at[g_slot].set(
                w(t_rdwr, st.last_cas_bg[g_slot])))
    if act_floor is not None:
        return new_st, done, events, (t_act, needs_act)
    return new_st, done, events


def _make_step(shape: SimShape, p: MechParams, trace: dict, warmup_steps,
               collect_events: bool = True):
    gap = trace["gap"]
    bank = trace["bank"]
    row = trace["row"]
    is_write = trace["is_write"]
    dep = trace["dep"]
    next_same = trace["next_same"]
    length = trace["length"]
    n_cores, L = gap.shape

    def step(st: SimState, step_idx):
        # 1. earliest-issue core selection
        ptr_c = jnp.clip(st.ptr, 0, L - 1)
        take = lambda a: jnp.take_along_axis(a, ptr_c[:, None], axis=1)[:, 0]
        g = take(gap)
        d = take(dep)
        issue = jnp.maximum(st.last_issue + g,
                            st.mshr_ring[jnp.arange(n_cores), st.ring_idx])
        issue = jnp.maximum(issue, jnp.where(d, st.last_complete, 0))
        issue = jnp.where(st.ptr >= length, INF, issue)
        c = jnp.argmin(issue).astype(jnp.int32)
        t_arr = issue[c]

        # a step with every core exhausted is a padded no-op (see _run):
        # it still traces through _service, but all its state writes are
        # discarded below and its events are masked out.
        alive = t_arr < INF
        measure = (step_idx >= warmup_steps) & alive
        # data-driven address mapping: fold the trace's (bank, row) into
        # the active geometry (identity for a trace generated against it)
        b_act, r_act = fold_address(p.geom, bank[c, ptr_c[c]],
                                    row[c, ptr_c[c]])
        st2, done, events = _service(shape, p, st, t_arr, b_act,
                                     r_act, is_write[c, ptr_c[c]],
                                     next_same[c, ptr_c[c]], measure, alive)

        # 2. core bookkeeping (masked: a dead step must not advance cores)
        w = lambda new, old: jnp.where(alive, new, old)
        st3 = st2._replace(
            ptr=st2.ptr.at[c].add(alive.astype(jnp.int32)),
            last_issue=st2.last_issue.at[c].set(w(t_arr, st2.last_issue[c])),
            last_complete=st2.last_complete.at[c].set(
                w(done, st2.last_complete[c])),
            mshr_ring=st2.mshr_ring.at[c, st2.ring_idx[c]].set(
                w(done, st2.mshr_ring[c, st2.ring_idx[c]])),
            ring_idx=st2.ring_idx.at[c].set(
                w((st2.ring_idx[c] + 1) % shape.mshr, st2.ring_idx[c])),
            core_end=st2.core_end.at[c].set(
                w(jnp.maximum(st2.core_end[c], done), st2.core_end[c])),
        )
        return st3, (events if collect_events else None)

    return step


def _next_same_folded(nb: int, bank, row, length):
    """Closed-row queue-hit lookahead, recomputed on device over *folded*
    addresses: ``out[c, i]`` is True iff core ``c``'s next request to the
    same (folded) bank targets the same (folded) row.

    This is the exact per-geometry lookahead (DESIGN.md §8, §10.2): the
    pre-PR-5 host precompute ran over the unfolded stream, so under a
    non-identity geometry fold the hint ignored cross-bank collisions
    (the DESIGN §8 caveat, now closed — regression in
    tests/test_geometry.py).  A reverse scan with one ``[nb]`` last-row
    register file per core; ``nb`` is the static envelope bank count, so
    the carry is tiny (the §2.1 perf rule: small carry, masked writes).
    Entries at or past ``length`` neither match nor update — identical
    to the host ``traces._next_same`` over the unpadded stream, which is
    the identity-fold parity case (bitwise, tested).
    """
    L = bank.shape[-1]
    idx = jnp.arange(L, dtype=jnp.int32)

    def per_core(bk, rw, ln):
        def rstep(last_row, x):
            b, r, live = x
            out = live & (last_row[b] == r)
            new = last_row.at[b].set(jnp.where(live, r, last_row[b]))
            return new, out
        init = jnp.full((nb,), NO_ROW, jnp.int32)
        _, out = jax.lax.scan(rstep, init, (bk, rw, idx < ln),
                              reverse=True)
        return out

    return jax.vmap(per_core)(bank, row, length)


def _retire_trailing_refs(stats: dict, core_end, p: MechParams) -> dict:
    """Retire trailing REF windows at stream end (stateful tier only).

    The in-scan ``refs_issued`` accumulation counts REF windows *observed
    at request arrivals* — on a sparse tail the count stops at the last
    arrival even though the controller's rolling schedule keeps issuing
    REFs until wall-clock end.  Overwrite it with the closed-form rolling
    schedule over ``[0, total_cycles]``: one REF per bank per elapsed
    tREFI window, including the window opening at t=0 (``ref_due`` starts
    at ``t0 // tREFI + 1``, i.e. the schedule has a REF at every multiple
    of tREFI *including* 0 once any request lands).  The serving engine
    keeps the observed-at-arrival semantics (its latency feedback loop is
    defined on arrival-visible state; DESIGN.md §14).
    """
    stats = dict(stats)
    total = jnp.max(core_end)
    sched = (total // p.timing.tREFI + 1) * p.geom.banks_total
    stats["refs_issued"] = jnp.where(
        p.refresh_stateful, sched.astype(jnp.int32), stats["refs_issued"])
    return stats


def _run_impl(shape: SimShape, params: MechParams, trace: dict,
              warmup_steps, n_steps: int, collect_events: bool = True):
    n_cores, L = trace["gap"].shape
    trace = dict(trace)
    if "next_same" not in trace:
        # queue-hit lookahead over the *folded* stream — exact for
        # identity and non-identity geometry folds alike (see
        # _next_same_folded).  Grid engines that know each point's
        # geometry host-side hoist this to one lookahead per *distinct*
        # geometry (``_ns_tables``) and pass the per-point view in.
        fb, fr = fold_address(params.geom, trace["bank"], trace["row"])
        trace["next_same"] = _next_same_folded(
            shape.envelope.max_banks_total, fb, fr, trace["length"])
    st = _init_state(shape, n_cores, L)
    step = _make_step(shape, params, trace, warmup_steps, collect_events)
    st, events = jax.lax.scan(step, st, jnp.arange(n_steps, dtype=jnp.int32))
    stats = _retire_trailing_refs(st.stats, st.core_end, params)
    return stats, st.core_end, events


def _ns_tables(shape: SimShape, trace: dict, ns_geoms: GeomParams):
    """One folded queue-hit lookahead per *distinct* grid geometry.

    ``ns_geoms`` stacks one ``GeomParams`` per distinct fold key
    (``banks_total``, ``n_rows``) of the launch's ``shape_grid`` (the
    full grid, so every chunk shares one table shape → one compile).
    The fold only reads those two counts, so any representative config
    per key yields the bitwise-identical lookahead.  Cuts the
    per-*point* ``9·n_steps`` fold/lookahead term of ``bytes_per_point``
    to a per-*geometry* one (the ROADMAP cross-host perf item)."""
    def per_geom(gp):
        fb, fr = fold_address(gp, trace["bank"], trace["row"])
        return _next_same_folded(shape.envelope.max_banks_total, fb, fr,
                                 trace["length"])
    return jax.vmap(per_geom)(ns_geoms)


def _hoist_geoms(grid: Sequence[SimConfig],
                 shape_grid: Sequence[SimConfig]):
    """Host-side hoist prep for trace-driven sweeps: the stacked
    distinct-geometry params (keyed over ``shape_grid`` so chunked
    launches share one table shape) and each launched point's index
    into them."""
    keys: list[tuple] = []
    reps: list[DRAMConfig] = []
    # shape_grid first so every chunk of one experiment shares the same
    # (ordered) distinct set; launched-only keys can only appear when a
    # caller passes an incomplete shape_grid directly
    for cfg in list(shape_grid) + list(grid):
        k = (cfg.dram.banks_total, cfg.dram.n_rows)
        if k not in keys:
            keys.append(k)
            reps.append(cfg.dram)
    idx = [keys.index((cfg.dram.banks_total, cfg.dram.n_rows))
           for cfg in grid]
    ns_geoms = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *[geom_params(d) for d in reps])
    return ns_geoms, jnp.asarray(idx, jnp.int32)


@functools.partial(jax.jit, static_argnums=(0, 4, 5))
def _run(shape: SimShape, params: MechParams, trace: dict, warmup_steps,
         n_steps: int, collect_events: bool = True):
    """Returns (stats, core_end, events) for one configuration.

    Perf note: the scan carry must stay small and must never be gathered
    from with data-dependent indices — a dynamic read of a large in-place
    carry buffer forces a full-array copy per step on the CPU backend
    (~300x slowdown, measured).  Row-history state (for RLTL) is therefore
    emitted as per-step *events* (scan ys, written with affine indices)
    and matched in a post-pass; ``collect_events=False`` drops the event
    stream entirely for consumers that don't need RLTL.

    ``n_steps`` (static) may exceed the trace's request count: once every
    core is exhausted the remaining steps are no-ops (`alive` masking in
    ``_make_step``), which lets callers pad to a common step count so
    differently-sized workload mixes share one compilation.
    """
    return _run_impl(shape, params, trace, warmup_steps, n_steps,
                     collect_events)


@functools.partial(jax.jit, static_argnums=(0, 4, 5, 8))
def _run_batched(shape: SimShape, params: MechParams, trace: dict,
                 warmup_steps, n_steps: int, collect_events: bool = True,
                 ns_geoms: GeomParams | None = None, ns_idx=None,
                 reduce_keys: tuple | None = None):
    """The vmapped grid engine: ``params`` leaves carry a leading [grid]
    axis; one compilation of the (single) scan body serves every grid
    point.

    ``ns_geoms``/``ns_idx`` (from ``_hoist_geoms``) hoist the folded
    ``next_same`` recompute to one lookahead per distinct geometry: each
    point gathers its geometry's row of the shared table instead of
    re-running the reverse scan — bitwise-identical (same function, same
    folded inputs).  ``None`` falls back to the per-point recompute.

    ``reduce_keys`` (static) switches the launch to the on-device
    reduction contract (DESIGN.md §13): the return value is the
    ``[grid, n_deps]`` int32 column array of ``_reduce_device`` instead
    of the ``(stats, core_end, events)`` triple."""
    if ns_geoms is None:
        out = jax.vmap(
            lambda p: _run_impl(shape, p, trace, warmup_steps, n_steps,
                                collect_events))(params)
    else:
        ns = _ns_tables(shape, trace, ns_geoms)

        def one(p, gi):
            return _run_impl(shape, p, {**trace, "next_same": ns[gi]},
                             warmup_steps, n_steps, collect_events)
        out = jax.vmap(one)(params, ns_idx)
    if reduce_keys is not None:
        return _reduce_device(out[0], out[1], reduce_keys)
    return out


@functools.partial(jax.jit, static_argnums=(0, 4, 5, 8))
def _run_grid(shape: SimShape, params: MechParams, traces: dict,
              warmups, n_steps: int, collect_events: bool = False,
              ns_geoms: GeomParams | None = None, ns_idx=None,
              reduce_keys: tuple | None = None):
    """The full grid engine: nested vmap over [traces] x [params].

    ``traces`` leaves carry a leading [batch] axis, ``warmups`` is [batch],
    ``params`` leaves carry a leading [grid] axis; the single compiled
    scan body serves every (trace, config) pair.  ``ns_geoms``/``ns_idx``
    hoist the ``next_same`` recompute per (trace, distinct geometry)
    instead of per (trace, point) — see ``_run_batched``.  ``reduce_keys``
    (static) returns the ``[batch, grid, n_deps]`` int32 reduction
    instead of the stats triple (DESIGN.md §13)."""
    def per_trace(trace, warmup):
        if ns_geoms is None:
            return jax.vmap(
                lambda p: _run_impl(shape, p, trace, warmup, n_steps,
                                    collect_events))(params)
        ns = _ns_tables(shape, trace, ns_geoms)

        def one(p, gi):
            return _run_impl(shape, p, {**trace, "next_same": ns[gi]},
                             warmup, n_steps, collect_events)
        return jax.vmap(one)(params, ns_idx)
    out = jax.vmap(per_trace)(traces, warmups)
    if reduce_keys is not None:
        return _reduce_device(out[0], out[1], reduce_keys)
    return out


def _rltl_edges_cycles(tck_ns: float = timing_lib.CYCLE_NS) -> np.ndarray:
    """``RLTL_EDGES_MS`` in cycles of ``tck_ns``."""
    return np.array([ms_to_cycles(e, tck_ns) for e in RLTL_EDGES_MS],
                    np.int32)


def _rltl_edges(grid: Sequence[SimConfig], events):
    """Per-lane RLTL bucket edges ``[G, B]`` in each point's own clock
    for a launch's ``events`` (whose grid axis a sharded launch pads past
    ``len(grid)`` by repeating its last point), or None where every point
    runs the default clock — the constant edges the passes fall back to,
    so a DDR3 launch compiles as it always has — or nothing was
    collected."""
    if events is None or all(cfg.timing.tCK_ns == timing_lib.CYCLE_NS
                             for cfg in grid):
        return None
    lanes = events.act_gid.shape[-2]
    return np.stack([_rltl_edges_cycles(cfg.timing.tCK_ns) for cfg in grid]
                    + [_rltl_edges_cycles(grid[-1].timing.tCK_ns)]
                    * (lanes - len(grid)))


def _rltl_post_pass(events: Events, edges=None):
    """Match each measured ACT to the most recent PRE of the same row.

    Exact reconstruction of the per-row "last PRE" history: all PRE and ACT
    events are sorted by (row id, time, kind); within a row, events strictly
    alternate ACT ... PRE, ACT ... PRE (a row must be precharged between
    activations), so an ACT's predecessor in the sorted order is its row's
    latest preceding PRE (or another event meaning "cold/open history").
    Returns the RLTL interval histogram (thesis Fig 3.2 buckets, upper
    ``edges`` in cycles; the default clock's when None) and the number
    of ACTs with a valid preceding PRE.
    """
    act_gid = np.asarray(events.act_gid)
    act_t = np.asarray(events.act_t)
    pre_gid = np.concatenate([np.asarray(events.pre1_gid),
                              np.asarray(events.pre2_gid),
                              np.asarray(events.pre3_gid)])
    pre_t = np.concatenate([np.asarray(events.pre1_t),
                            np.asarray(events.pre2_t),
                            np.asarray(events.pre3_t)])
    am = act_gid >= 0
    pm = pre_gid >= 0
    gid = np.concatenate([act_gid[am], pre_gid[pm]])
    t = np.concatenate([act_t[am], pre_t[pm]])
    kind = np.concatenate([np.ones(am.sum(), np.int8),
                           np.zeros(pm.sum(), np.int8)])  # PRE=0 < ACT=1
    order = np.lexsort((kind, t, gid))
    gid, t, kind = gid[order], t[order], kind[order]
    prev_same = np.zeros(len(gid), bool)
    prev_same[1:] = gid[1:] == gid[:-1]
    is_act = kind == 1
    prev_is_pre = np.zeros(len(gid), bool)
    prev_is_pre[1:] = kind[:-1] == 0
    valid = is_act & prev_same & prev_is_pre
    intervals = np.where(valid, t - np.roll(t, 1), 0)[valid]
    edges = _rltl_edges_cycles() if edges is None else np.asarray(edges)
    bucket = np.searchsorted(edges, intervals, side="left")
    hist = np.bincount(bucket, minlength=len(RLTL_EDGES_MS) + 1).astype(np.int64)
    return hist, int(valid.sum())


def _rltl_device(events: Events, edges=None):
    """On-device mirror of ``_rltl_post_pass``: a sorted-segment (per
    row id) reduction over the event stream, pure JAX — bitwise the host
    pass (tests/test_simulator.py).

    Instead of host-filtering the empty event slots, they are rewritten
    to a sentinel row id (maximal, kind=ACT) so the stable lexsort parks
    them after every live row segment: they can never validate (the
    sentinel gid is excluded) nor split a live segment.  The grid
    engines vmap this over their batch axes, so only the
    ``[len(RLTL_EDGES_MS)+1]`` histogram and a scalar total ever leave
    the accelerator — the per-step event stream itself (7 int32 arrays
    × n_steps × grid) stays on device however long the trace is."""
    gid = jnp.concatenate([events.act_gid, events.pre1_gid,
                           events.pre2_gid, events.pre3_gid])
    t = jnp.concatenate([events.act_t, events.pre1_t, events.pre2_t,
                         events.pre3_t])
    n = events.act_gid.shape[0]
    kind = jnp.concatenate([jnp.ones(n, jnp.int8),
                            jnp.zeros(3 * n, jnp.int8)])  # PRE=0 < ACT=1
    sent = jnp.int32(2**31 - 1)
    live = gid >= 0
    gid = jnp.where(live, gid, sent)
    kind = jnp.where(live, kind, jnp.int8(1))
    order = jnp.lexsort((kind, t, gid))
    gid, t, kind = gid[order], t[order], kind[order]
    prev_same = jnp.concatenate([jnp.zeros(1, bool), gid[1:] == gid[:-1]])
    prev_is_pre = jnp.concatenate([jnp.zeros(1, bool), kind[:-1] == 0])
    valid = (kind == 1) & prev_same & prev_is_pre & (gid != sent)
    prev_t = jnp.concatenate([t[:1], t[:-1]])
    intervals = jnp.where(valid, t - prev_t, 0)
    edges = jnp.asarray(_rltl_edges_cycles() if edges is None else edges,
                        jnp.int32)
    bucket = jnp.searchsorted(edges, intervals, side="left").astype(
        jnp.int32)
    hist = jnp.zeros(len(RLTL_EDGES_MS) + 1, jnp.int32).at[bucket].add(
        valid.astype(jnp.int32))
    return hist, jnp.sum(valid.astype(jnp.int32))


@jax.jit
def _rltl_hist_device(events: Events, edges=None):
    """``_rltl_device`` vmapped over however many leading batch axes the
    engine emitted ([grid] for sweeps, [batch, grid] for sweep_traces);
    ``edges`` is None (the default clock's) or carries the same leading
    axes."""
    fn = _rltl_device
    for _ in range(events.act_gid.ndim - 1):
        fn = jax.vmap(fn)
    return fn(events, edges)


def _rltl_np(events: Events | None, edges=None,
             on_device: bool | None = None):
    """The RLTL post-pass, dispatched per backend; returns host views
    ``(hist [..., B+1] int64, total [...] int64)``.  ``edges`` are the
    per-point bucket edges of ``_rltl_edges`` (``[G, B]``, broadcast over
    a leading batch axis), or None for the default clock's.

    On accelerators the segmented pass runs on device
    (``_rltl_hist_device``) and only the histograms cross to the host —
    the per-step event streams (7 int32 arrays × n_steps × grid) never
    leave HBM however long the trace is.  On CPU the host *is* the
    device, there is no transfer to avoid, and numpy's stable lexsort
    beats XLA's comparator sort ~8x (measured, BENCH_simstep.json), so
    the original host pass runs instead.  Both are bitwise-identical
    (tests/test_simulator.py); ``on_device`` forces one side for
    tests/benchmarks."""
    if events is None:
        return None, None
    if on_device is None:
        on_device = jax.default_backend() != "cpu"
    lead = events.act_gid.shape[:-1]
    if edges is not None:
        edges = np.broadcast_to(np.asarray(edges, np.int32),
                                lead + (len(RLTL_EDGES_MS),))
    with span("rltl"):
        if on_device:
            hist, total = _rltl_hist_device(events, edges)
            return np.asarray(hist).astype(np.int64), \
                np.asarray(total).astype(np.int64)
        ev = Events(*(np.asarray(e) for e in events))
        hist = np.zeros(lead + (len(RLTL_EDGES_MS) + 1,), np.int64)
        total = np.zeros(lead, np.int64)
        for idx in np.ndindex(*lead):
            hist[idx], total[idx] = _rltl_post_pass(
                Events(*(x[idx] for x in ev)),
                None if edges is None else edges[idx])
        return hist, total


def _device_trace(batch: TraceBatch) -> dict:
    # note: the host-precomputed ``batch.next_same`` is NOT shipped —
    # the engine recomputes the lookahead post-fold (_next_same_folded),
    # which is bitwise-identical for identity folds and *correct* (not
    # merely stale-consistent) for non-identity geometry folds
    return {
        "gap": jnp.asarray(batch.gap, jnp.int32),
        "bank": jnp.asarray(batch.bank, jnp.int32),
        "row": jnp.asarray(batch.row, jnp.int32),
        "is_write": jnp.asarray(batch.is_write),
        "dep": jnp.asarray(batch.dep),
        "length": jnp.asarray(batch.length, jnp.int32),
    }


def _finalize(raw_stats: dict, core_end, rltl: tuple,
              lengths: np.ndarray, cfg: SimConfig | None = None) -> dict:
    """Host-side post-processing shared by ``simulate``/``sweep`` (which
    pass the batch's per-core lengths) and the streamed-generation path
    (which knows them from the ``WorkloadSpec`` — no ``TraceBatch``
    exists there).  ``rltl`` is this point's ``(hist, total)`` from the
    on-device post-pass (``_rltl_np``), or ``(None, None)`` when the run
    was collected without events."""
    stats = {k: np.asarray(v) for k, v in raw_stats.items()}
    for k in BG_STAT_KEYS:  # carried only on the bank-group path
        stats.setdefault(k, np.int32(0))
    hist, rltl_total = rltl
    stats["rltl_hist"] = None if hist is None else np.asarray(hist)
    stats["rltl_total"] = None if rltl_total is None else int(rltl_total)
    stats["core_end"] = np.asarray(core_end)
    stats["total_cycles"] = int(stats["core_end"].max())
    # int32 cycle-horizon backstop (satellite 4): a stream whose clock
    # wrapped past INF (the dead-step sentinel) silently corrupts every
    # time-derived stat — fail loudly with the split-the-stream remedy
    assert 0 <= stats["total_cycles"] < int(INF), (
        f"cycle clock overflowed the int32 horizon "
        f"(total_cycles={stats['total_cycles']}, limit={int(INF)}); "
        f"split the stream into shorter chunks or reduce mean_gap")
    stats["n_cores"] = int(np.asarray(lengths).shape[0])
    stats["lengths"] = np.asarray(lengths)
    if cfg is not None:
        # active geometry of this point (geometry-aware consumers:
        # energy_nj, the geometry benchmark's labels)
        stats["n_channels"] = cfg.dram.n_channels
        stats["n_ranks"] = cfg.dram.n_ranks
        stats["n_banks"] = cfg.dram.n_banks
        stats["banks_total"] = cfg.dram.banks_total
    # derived scalars come from the one metric registry (DESIGN.md §13):
    # the same formulas serve this full-stats path and the on-device
    # reduce path, so the two are bitwise-equal by construction
    return metrics_lib.finalize_scalars(stats)


def simulate(batch: TraceBatch, cfg: SimConfig = SimConfig()) -> dict:
    """Run the simulator on a trace batch; returns a python stats dict.

    All numeric configuration is passed as traced data (``mech_params``),
    so configs sharing a ``SimShape`` — any mix of mechanism kinds, timing
    values or caching durations — reuse one compilation.
    """
    trace = _device_trace(batch)
    n_steps = int(batch.length.sum())
    # horizon guard: int32 cycle arithmetic
    assert n_steps < 2**24, "trace too long for the int32 cycle horizon"
    # a-priori overflow guard (satellite 4): the arrival clock alone —
    # the per-core gap sum — must stay below the int32 sentinel before
    # any service time is added (``_finalize`` backstops the total)
    arrival = int(np.asarray(batch.gap, np.int64).sum(axis=1).max())
    assert arrival < int(INF), (
        f"trace arrival clock ({arrival} cycles) overflows the int32 "
        f"horizon ({int(INF)}); split the stream into shorter chunks")
    warmup = jnp.int32(int(cfg.warmup_frac * n_steps))
    shape = sim_shape(cfg)
    if cfg.controller == "frfcfs":
        from repro.controller import engine as ctrl_engine
        _record_launch("_run_window", shape, n_steps)
        raw_stats, core_end, events, attempts = ctrl_engine._run_window(
            shape, cfg.window, mech_params(cfg), trace, warmup, n_steps)
        _drain_admits([_AdmitTally(attempts, "_run_window", shape,
                                   n_steps)])
    else:
        _record_launch("_run", shape, n_steps)
        raw_stats, core_end, events = _run(shape, mech_params(cfg), trace,
                                           warmup, n_steps)
    edges = None if cfg.timing.tCK_ns == timing_lib.CYCLE_NS \
        else _rltl_edges_cycles(cfg.timing.tCK_ns)
    return _finalize(raw_stats, core_end, _rltl_np(events, edges),
                     batch.length, cfg)


def _shard_grid(stacked: MechParams, n_grid: int):
    """Lay the stacked grid axis out across the available devices.

    Pads the axis to a device multiple (replicating the last entry) and
    device_puts each leaf with a grid-axis ``NamedSharding`` so the jitted
    vmapped run executes one shard per device.  A no-op on one device.
    Returns ``(stacked, padded_n)``.
    """
    devs = jax.devices()
    if len(devs) <= 1:
        return stacked, n_grid
    pad = (-n_grid) % len(devs)
    if pad:
        stacked = jax.tree_util.tree_map(
            lambda x: jnp.concatenate(
                [x, jnp.repeat(x[-1:], pad, axis=0)]), stacked)
    mesh = jax.sharding.Mesh(np.asarray(devs), ("grid",))
    sharding = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("grid"))
    stacked = jax.tree_util.tree_map(
        lambda x: jax.device_put(x, sharding), stacked)
    return stacked, n_grid + pad


def _record_launch(engine: str, shape: SimShape, n_steps: int) -> None:
    """Count a dispatched launch's scan steps (``repro.obs.scan_steps``)."""
    obs.record_launch(engine, shape.envelope.max_bank_groups > 1, n_steps)


class _AdmitTally(NamedTuple):
    """A window-engine launch's per-lane admission attempts (device
    array, not yet copied back) and what its drain files them under in
    ``repro.obs.admit_attempts``."""
    attempts: jax.Array
    engine: str
    shape: SimShape
    steps: int


def _tally_admits(out, engine: str, shape: SimShape, n_steps: int,
                  reduce_keys: tuple | None):
    """A window launch's output with its last element, the lanes'
    admission attempts, wrapped for the drain; a reduced launch returns
    its metric columns alone and passes through."""
    if reduce_keys is not None:
        return out
    *head, attempts = out
    return (*head, _AdmitTally(attempts, engine, shape, n_steps))


def _drain_admits(tallies) -> None:
    """File the admission attempts of a drained window launch (none for
    the in-order engines, whose output has no tally)."""
    for t in tallies:
        a = np.asarray(t.attempts)
        obs.record_admits(t.engine, t.shape.envelope.max_bank_groups > 1,
                          a.sum(), a.size * t.steps)


def _uniform_backend(grid: Sequence[SimConfig]) -> str:
    """The engine tier of a launch.  A single vmapped/kernelized launch
    runs every point through one engine, so mixing tiers inside one grid
    is a caller error, not something to silently split."""
    backend = grid[0].backend
    assert all(cfg.backend == backend for cfg in grid), (
        "a sweep grid must share one backend (split the grid to compare "
        "engine tiers)")
    return backend


def _launch_controller(grid: Sequence[SimConfig],
                       shape_grid: Sequence[SimConfig] | None = None):
    """The controller tier of a launch and its shared static window size.

    Returns ``("inorder", 1)`` when every point is in-order — the
    existing engines then run completely unmodified (the tier-1 bitwise
    guarantee).  If ANY point opts into ``controller="frfcfs"``, the
    whole launch routes through the window engine
    (``repro.controller.engine``) with ONE static window depth ``W`` =
    the max ``cfg.window`` over grid *and* shape_grid, so every chunk of
    one experiment shares one compile; in-order points ride along with
    traced ``win_cap=1``, which the window engine serves
    bitwise-identically to the in-order engine (DESIGN.md §15,
    tests/test_controller.py)."""
    pts = list(grid) + (list(shape_grid) if shape_grid is not None else [])
    if all(cfg.controller == "inorder" for cfg in pts):
        return "inorder", 1
    return "frfcfs", max(cfg.window for cfg in pts
                         if cfg.controller == "frfcfs")


def _freeze_hints(hints: dict) -> tuple:
    """Hashable view of the registry pad hints (cache key component)."""
    return tuple(sorted((n, tuple(sorted(h.items())))
                        for n, h in hints.items()))


@functools.lru_cache(maxsize=16384)
def _point_params_np(timing: TimingParams, dram: DRAMConfig, policy: str,
                     mech: MechanismConfig, refresh_mode: str,
                     controller: str, window: int,
                     hints_key: tuple, env: DRAMEnvelope):
    """One grid point's ``mech_params`` pytree as flat *numpy* leaves.

    ``mech_params`` only reads (timing, dram, policy, mech,
    refresh_mode, controller, window), so points differing elsewhere (a
    workload-seed axis, serving knobs, ...) share one cache entry — and
    a 10⁵-point grid stages from a handful of distinct entries by
    fancy-indexing numpy columns instead of building 10⁵ × ~80 device
    scalars (``_grid_shape_and_params``).  The hints key covers the
    registered-policy set, so a temporarily registered mechanism
    (tests' ``registry.temporary``) never aliases an entry."""
    cfg = SimConfig(dram=dram, timing=timing, mech=mech, policy=policy,
                    refresh_mode=refresh_mode, controller=controller,
                    window=window)
    hints = {n: dict(h) for n, h in hints_key}
    p = mech_params(cfg, hints=hints, envelope=env)
    leaves, treedef = jax.tree_util.tree_flatten(p)
    return tuple(np.asarray(x) for x in leaves), treedef


def _stack_cached(grid, point_key, point_leaves):
    """Stack per-point cached numpy leaf tuples into ``[grid, ...]``
    columns: dedup points by ``point_key``, stack the few distinct leaf
    sets, fan out with one fancy-index per leaf."""
    uniq_of: dict = {}
    uniq: list = []
    kidx = np.empty(len(grid), np.intp)
    for i, cfg in enumerate(grid):
        k = point_key(cfg)
        j = uniq_of.get(k)
        if j is None:
            j = uniq_of[k] = len(uniq)
            uniq.append(point_leaves(cfg))
        kidx[i] = j
    leaves0, treedef = uniq[0]
    for lv, td in uniq[1:]:
        assert td == treedef, "grid points disagree on params structure"
    cols = []
    for li in range(len(leaves0)):
        u = np.stack([lv[li] for lv, _ in uniq])
        cols.append(u[kidx])
    return jax.tree_util.tree_unflatten(treedef, cols)


def _grid_shape_and_params(grid: Sequence[SimConfig],
                           shape_grid: Sequence[SimConfig] | None = None):
    """Validate grid shape compatibility; return the unified static shape
    and the stacked traced params.

    ``shape_grid`` (a superset of ``grid``, defaulting to ``grid``) is
    what determines the padded DRAM envelope, the padded HCRAC capacity,
    and the registry pad hints: the experiment runner passes the *full*
    grid here while launching a chunk, so every chunk shares one
    ``SimShape`` — and therefore one compilation.  Extra padding is
    behaviour-neutral (DESIGN.md §4, §8).

    The stacked leaves are *numpy* arrays assembled from the per-point
    ``_point_params_np`` cache — same dtypes/values as the former
    ``jnp.stack`` of per-point device scalars (the jit consumes either),
    but staging cost scales with *distinct* (timing, dram, policy, mech)
    combinations, not grid size, and the arrays slice cheaply per chunk
    (the §13 streaming runner's staged-once contract).
    """
    shape_grid = list(shape_grid) if shape_grid is not None else list(grid)
    c0 = grid[0]
    for cfg in list(grid) + shape_grid:
        assert cfg.mshr == c0.mshr, "sweep grid must share MSHR depth"
        assert cfg.warmup_frac == c0.warmup_frac
        assert cfg.mech.hcrac.n_ways == c0.mech.hcrac.n_ways
        assert cfg.mech.hcrac.exact_expiry == c0.mech.hcrac.exact_expiry
    n_sets_max = max(cfg.mech.hcrac.n_sets for cfg in shape_grid)
    assert n_sets_max >= max(cfg.mech.hcrac.n_sets for cfg in grid), \
        "shape_grid must cover every launched config's HCRAC capacity"
    env = envelope_of([cfg.dram for cfg in list(grid) + shape_grid])
    hints = registry.pad_hints([cfg.mech for cfg in shape_grid])
    shape = sim_shape(c0, n_sets_max=n_sets_max, envelope=env)
    hkey = _freeze_hints(hints)
    stacked = _stack_cached(
        grid,
        point_key=lambda cfg: (cfg.timing, cfg.dram, cfg.policy, cfg.mech,
                               cfg.refresh_mode, cfg.controller,
                               cfg.window),
        point_leaves=lambda cfg: _point_params_np(
            cfg.timing, cfg.dram, cfg.policy, cfg.mech, cfg.refresh_mode,
            cfg.controller, cfg.window, hkey, env))
    return shape, stacked


def _launch_batch(shape, stacked, trace, warmup, n_steps: int,
                  collect_events: bool, ns_geoms, ns_idx, n_grid: int,
                  backend: str = "ref",
                  reduce_keys: tuple | None = None,
                  controller: str = "inorder", window: int = 1):
    """Dispatch one (possibly chunk-sliced) stacked-params trace launch
    and return the *unblocked* device output — the async half of
    ``sweep()``.  The §13 pipeline calls this for chunk k+1 while chunk
    k's output is still in flight; nothing blocks until ``_drain_batch``
    touches the arrays."""
    if reduce_keys is not None:
        collect_events = False
    if controller == "frfcfs":
        assert backend == "ref", (
            "the frfcfs controller tier runs the ref engine only")
        from repro.controller import engine as ctrl_engine
        (stacked, ns_idx), _ = _shard_grid((stacked, ns_idx), n_grid)
        _record_launch("_run_window_batched", shape, n_steps)
        return _tally_admits(ctrl_engine._run_window_batched(
            shape, window, stacked, trace, warmup, n_steps,
            collect_events, ns_geoms, ns_idx, reduce_keys),
            "_run_window_batched", shape, n_steps, reduce_keys)
    if backend == "pallas":
        from repro.kernels.sim_step import ops as sim_step_ops
        _record_launch("sim_step.run_sweep", shape, n_steps)
        out = sim_step_ops.run_sweep(shape, stacked, trace, warmup,
                                     n_steps, collect_events, ns_geoms,
                                     ns_idx)
        if reduce_keys is not None:
            return _reduce_jit(out[0], out[1], reduce_keys)
        return out
    (stacked, ns_idx), _ = _shard_grid((stacked, ns_idx), n_grid)
    _record_launch("_run_batched", shape, n_steps)
    return _run_batched(shape, stacked, trace, warmup, n_steps,
                        collect_events, ns_geoms, ns_idx, reduce_keys)


def _drain_batch(out, grid, lengths, n_grid: int,
                 reduce_keys: tuple | None = None):
    """Block on a ``_launch_batch`` output and convert: the reduced
    ``[grid, n_deps]`` int columns, or the full per-point stats dicts
    (``_finalize``)."""
    if reduce_keys is not None:
        with span("d2h"):
            return np.asarray(out)[:n_grid]
    raw_stats, core_end, events, *tally = out
    with span("d2h"):
        stats_np = {k: np.asarray(v) for k, v in raw_stats.items()}
        core_np = np.asarray(core_end)
        _drain_admits(tally)
    hist_np, total_np = _rltl_np(events, _rltl_edges(grid, events))
    with span("finalize"):
        return [
            _finalize({k: v[g] for k, v in stats_np.items()}, core_np[g],
                      (None, None) if hist_np is None
                      else (hist_np[g], total_np[g]), lengths, grid[g])
            for g in range(n_grid)
        ]


def sweep(batch: TraceBatch, grid: Sequence[SimConfig],
          pad_steps: bool = False, rltl: bool = True,
          shape_grid: Sequence[SimConfig] | None = None,
          reduce_keys: tuple | None = None):
    """Evaluate every configuration in ``grid`` on ``batch`` in one call.

    The whole grid — any mix of the registered mechanism kinds, HCRAC
    capacities, caching durations, timing sets, and DRAM geometries
    (channel/bank counts pad to a shared envelope, DESIGN.md §8) — is
    flattened to stacked ``MechParams`` and evaluated by one ``vmap``-ed,
    jit-compiled scan (sharded across devices when several are
    available).  Results are bitwise identical to per-config
    ``simulate()`` calls.

    ``pad_steps=True`` pads the scan length to the trace *capacity*
    (cores x padded length) instead of the exact request count; padded
    steps are no-ops, so stats are unchanged, but every same-shape trace
    set then shares a single compilation — the compile-once/run-many mode
    the benchmarks use.  ``rltl=False`` skips event collection (the
    stats dicts then carry ``rltl_hist=None``) — substantially faster and
    smaller when the RLTL histogram isn't needed.  ``shape_grid`` lets a
    caller pad shapes for a larger grid than it launches (the experiment
    runner's chunking mode; see ``_grid_shape_and_params``).

    ``reduce_keys`` (a tuple of ``REDUCE_KEYS`` entries) switches to the
    on-device reduction contract (DESIGN.md §13): the return value is a
    ``[grid, n_deps]`` int numpy array instead of per-point stats dicts
    (RLTL events are never collected in this mode).
    """
    grid = list(grid)
    assert grid, "empty sweep grid"
    shape, stacked = _grid_shape_and_params(grid, shape_grid)

    trace = _device_trace(batch)
    n_req = int(batch.length.sum())
    assert n_req < 2**24, "trace too long for the int32 cycle horizon"
    n_cores, max_len = batch.gap.shape
    n_steps = n_cores * max_len if pad_steps else n_req
    warmup = jnp.int32(int(grid[0].warmup_frac * n_req))

    # one lookahead per *distinct* geometry (host-known here), gathered
    # per point inside the engines — see _hoist_geoms/_ns_tables
    ns_geoms, ns_idx = _hoist_geoms(
        grid, shape_grid if shape_grid is not None else grid)

    n_grid = len(grid)
    ctrl, win = _launch_controller(grid, shape_grid)
    out = _launch_batch(shape, stacked, trace, warmup, n_steps, rltl,
                        ns_geoms, ns_idx, n_grid,
                        backend=_uniform_backend(grid),
                        reduce_keys=reduce_keys,
                        controller=ctrl, window=win)
    # one device->host transfer for the whole grid, then per-point views
    return _drain_batch(out, grid, batch.length, n_grid, reduce_keys)


def _launch_grid(shape, stacked, traces, warmups, n_steps: int,
                 collect_events: bool, ns_geoms, ns_idx, n_batch: int,
                 reduce_keys: tuple | None = None,
                 controller: str = "inorder", window: int = 1):
    """Async dispatch of the nested [batch, grid] engine (ref tier only
    — see ``sweep_traces``); returns the unblocked device output."""
    if reduce_keys is not None:
        collect_events = False
    (traces, warmups), _ = _shard_grid((traces, warmups), n_batch)
    if controller == "frfcfs":
        from repro.controller import engine as ctrl_engine
        _record_launch("_run_window_grid", shape, n_steps)
        return _tally_admits(ctrl_engine._run_window_grid(
            shape, window, stacked, traces, warmups, n_steps,
            collect_events, ns_geoms, ns_idx, reduce_keys),
            "_run_window_grid", shape, n_steps, reduce_keys)
    _record_launch("_run_grid", shape, n_steps)
    return _run_grid(shape, stacked, traces, warmups, n_steps,
                     collect_events, ns_geoms, ns_idx, reduce_keys)


def _drain_grid(out, grid, batches, n_batch: int,
                reduce_keys: tuple | None = None):
    if reduce_keys is not None:
        with span("d2h"):
            return np.asarray(out)[:n_batch]
    raw_stats, core_end, events, *tally = out
    with span("d2h"):
        stats_np = {k: np.asarray(v)
                    for k, v in raw_stats.items()}  # [B, G]
        core_np = np.asarray(core_end)
        _drain_admits(tally)
    hist_np, total_np = _rltl_np(events, _rltl_edges(grid, events))
    rows = []
    for b in range(n_batch):
        with span("finalize"):
            row = []
            for g in range(len(grid)):
                rl = ((None, None) if hist_np is None
                      else (hist_np[b, g], total_np[b, g]))
                row.append(_finalize(
                    {k: v[b, g] for k, v in stats_np.items()},
                    core_np[b, g], rl, batches[b].length, grid[g]))
        rows.append(row)
    return rows


def sweep_traces(batches: Sequence[TraceBatch], grid: Sequence[SimConfig],
                 rltl: bool = False,
                 shape_grid: Sequence[SimConfig] | None = None,
                 reduce_keys: tuple | None = None):
    """Evaluate a config grid over *several* trace batches in one call.

    The full evaluation matrix — every (workload batch, configuration)
    pair — runs through one nested-vmap compilation of the scan body:
    the outer axis batches the traces, the inner axis the mechanism
    params.  All batches must share array shapes (cores x padded length);
    the scan length is padded to the trace capacity, so differing request
    counts are handled by no-op steps and per-batch traced warm-up.

    Returns ``out[b][g]``: stats for batch ``b`` under config ``g``,
    bitwise identical to ``simulate(batches[b], grid[g])`` (modulo the
    RLTL histogram, which is only collected when ``rltl=True``).
    ``reduce_keys`` returns the ``[batch, grid, n_deps]`` int array of
    the on-device reduction contract instead (DESIGN.md §13).
    """
    batches = list(batches)
    grid = list(grid)
    assert batches and grid, "empty sweep"
    tshape = batches[0].gap.shape
    for b in batches:
        assert b.gap.shape == tshape, \
            "sweep_traces requires same-shape trace batches"
    shape, stacked = _grid_shape_and_params(grid, shape_grid)

    traces = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs),
        *[_device_trace(b) for b in batches])
    n_cores, max_len = tshape
    n_steps = n_cores * max_len
    assert n_steps < 2**24, "trace too long for the int32 cycle horizon"
    warmups = jnp.asarray(
        [int(grid[0].warmup_frac * int(b.length.sum())) for b in batches],
        jnp.int32)

    # trace batches are the outer vmap axis here, which the sim_step
    # kernel's sweep-batch grid doesn't model — the nested-matrix entry
    # stays on the authoritative ref engine (DESIGN.md §11)
    assert _uniform_backend(grid) == "ref", (
        "sweep_traces runs the ref engine only; use sweep() per batch "
        "for the pallas tier")
    ns_geoms, ns_idx = _hoist_geoms(
        grid, shape_grid if shape_grid is not None else grid)

    n_batch = len(batches)
    ctrl, win = _launch_controller(grid, shape_grid)
    out = _launch_grid(shape, stacked, traces, warmups, n_steps, rltl,
                       ns_geoms, ns_idx, n_batch, reduce_keys,
                       controller=ctrl, window=win)
    return _drain_grid(out, grid, batches, n_batch, reduce_keys)


# --------------------------------------------------------------------------
# Streamed generation: the synthetic-workload path (DESIGN.md §10).
# The workload itself is traced data (WorkloadParams / InterleaveParams
# stacked along the grid axis next to MechParams), the stream is
# generated on device inside the same jit as the scan, and no host
# trace is ever materialized or transferred.  The generator lives in
# ``repro.workloads`` (which imports this core layer); the entry points
# import it lazily at call time, so the module import graph stays
# acyclic while the engine keeps both paths side by side.
# --------------------------------------------------------------------------

def _run_synth_impl(shape: SimShape, n_cores: int, max_len: int,
                    p: MechParams, w, il, warmup,
                    n_steps: int, collect_events: bool):
    from repro.workloads.generator import generate
    trace = generate(n_cores, max_len, w, p.geom, il)
    return _run_impl(shape, p, trace, warmup, n_steps, collect_events)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 7, 8, 9))
def _run_synth_batched(shape: SimShape, n_cores: int, max_len: int,
                       params: MechParams, wparams, ilparams,
                       warmups, n_steps: int,
                       collect_events: bool = True,
                       reduce_keys: tuple | None = None):
    """The synthetic grid engine: generation + scan vmapped together —
    ``params`` / ``wparams`` / ``ilparams`` leaves and the per-point
    ``warmups`` carry a leading [grid] axis and one compilation serves
    every (workload, interleave, geometry, mechanism) point.
    ``reduce_keys`` (static) returns the ``[grid, n_deps]`` int32
    reduction instead of the stats triple (DESIGN.md §13)."""
    out = jax.vmap(
        lambda p, w, il, wu: _run_synth_impl(shape, n_cores, max_len, p,
                                             w, il, wu, n_steps,
                                             collect_events))(
        params, wparams, ilparams, warmups)
    if reduce_keys is not None:
        return _reduce_device(out[0], out[1], reduce_keys)
    return out


@functools.lru_cache(maxsize=4096)
def _wparams_np(names: tuple, n_req: int, phases: tuple, n_segs: int):
    """One spec's traced ``WorkloadParams`` as flat numpy leaves, cached
    by the (names, n_req, phases, n_segs) tuple that determines every
    leaf *except* the stream seed (staged as seed=0; the caller
    overwrites the seed column from the configs) — a 10⁵-point seed axis
    stages from ONE entry.  ``n_segs`` is the grid-wide phase-segment
    count the spec pads to (profiles.n_segs_of)."""
    from repro.workloads.profiles import spec_params
    p = spec_params(WorkloadSpec(names=names, n_req=n_req, seed=0,
                                 phases=phases), n_segs=n_segs)
    leaves, treedef = jax.tree_util.tree_flatten(p)
    return tuple(np.asarray(x) for x in leaves), treedef


@functools.lru_cache(maxsize=4096)
def _check_synth_horizon(names: tuple, n_req: int, phases: tuple):
    """A-priori int32 overflow guard for synthetic streams (satellite
    4): the expected arrival clock per core — ``length * mean_gap``,
    maximized over the phase schedule — must sit well below the int32
    sentinel (4x expectation covers the geometric gap tail; the
    ``_finalize`` runtime assert backstops the actual clock)."""
    spec = WorkloadSpec(names=names, n_req=n_req, phases=phases)
    lengths = spec.lengths()
    for c, n in enumerate(names):
        gaps = [WORKLOAD_BY_NAME[n].mean_gap] + [
            WORKLOAD_BY_NAME[nm[c]].mean_gap for _, nm in phases]
        worst = 4.0 * float(lengths[c]) * max(max(gaps), 1.0)
        assert worst < float(INF), (
            f"core {c} ({n!r}, n_req={n_req}) risks int32 cycle "
            f"overflow (~{worst:.3g} expected arrival cycles vs the "
            f"{int(INF)} horizon); split the stream into shorter chunks")


@functools.lru_cache(maxsize=512)
def _ilparams_np(il: InterleaveConfig):
    leaves, treedef = jax.tree_util.tree_flatten(interleave_params(il))
    return tuple(np.asarray(x) for x in leaves), treedef


@functools.lru_cache(maxsize=4096)
def _spec_total_len(names: tuple, n_req: int) -> int:
    return int(WorkloadSpec(names=names, n_req=n_req).lengths().sum())


def _stage_synth(grid: Sequence[SimConfig],
                 shape_grid: Sequence[SimConfig] | None = None):
    """Host staging of a synthetic launch: static facts + numpy-stacked
    params (``MechParams`` / ``WorkloadParams`` / ``InterleaveParams`` /
    warmups).  The §13 runner stages the full unique grid ONCE and
    slices numpy views per chunk."""
    from repro.workloads.profiles import max_len_of, n_segs_of
    grid = list(grid)
    assert grid, "empty synthetic sweep grid"
    shape_grid_l = (list(shape_grid) if shape_grid is not None
                    else list(grid))
    for cfg in grid + shape_grid_l:
        assert cfg.workload is not None and cfg.workload.names, (
            "sweep_synth needs cfg.workload set on every grid point")
    n_cores = grid[0].workload.n_cores
    for cfg in grid + shape_grid_l:
        assert cfg.workload.n_cores == n_cores, (
            "synthetic grids must share the core count")
    shape, stacked = _grid_shape_and_params(grid, shape_grid)

    max_len = max_len_of([cfg.workload for cfg in grid + shape_grid_l])
    n_steps = n_cores * max_len
    assert n_steps < 2**24, "workload too long for the int32 cycle horizon"

    n_segs = n_segs_of([cfg.workload for cfg in grid + shape_grid_l])
    for cfg in grid:
        _check_synth_horizon(cfg.workload.names, cfg.workload.n_req,
                             cfg.workload.phases)
    wstack = _stack_cached(
        grid,
        point_key=lambda cfg: (cfg.workload.names, cfg.workload.n_req,
                               cfg.workload.phases, n_segs),
        point_leaves=lambda cfg: _wparams_np(cfg.workload.names,
                                             cfg.workload.n_req,
                                             cfg.workload.phases, n_segs))
    seeds = np.asarray([cfg.workload.seed for cfg in grid], np.int32)
    wstack = wstack._replace(
        seed=np.ascontiguousarray(
            np.broadcast_to(seeds[:, None], wstack.seed.shape)))
    ilstack = _stack_cached(
        grid,
        point_key=lambda cfg: cfg.interleave,
        point_leaves=lambda cfg: _ilparams_np(cfg.interleave))
    # per-point warm-up, computed host-side from the spec's known
    # request counts with the SAME ``int(frac * total)`` float
    # arithmetic the materialized path uses — bitwise parity for any
    # warmup_frac (the ``sweep_traces`` warmups pattern)
    warmups = np.asarray(
        [int(cfg.warmup_frac * _spec_total_len(cfg.workload.names,
                                               cfg.workload.n_req))
         for cfg in grid], np.int32)
    return shape, n_cores, max_len, n_steps, stacked, wstack, ilstack, \
        warmups


def _launch_synth(shape, n_cores: int, max_len: int, stacked, wstack,
                  ilstack, warmups, n_steps: int, collect_events: bool,
                  n_grid: int, backend: str = "ref",
                  reduce_keys: tuple | None = None,
                  controller: str = "inorder", window: int = 1):
    """Async dispatch of one synthetic launch (unblocked device out)."""
    if reduce_keys is not None:
        collect_events = False
    if controller == "frfcfs":
        assert backend == "ref", (
            "the frfcfs controller tier runs the ref engine only")
        from repro.controller import engine as ctrl_engine
        (stacked, wstack, ilstack, warmups), _ = _shard_grid(
            (stacked, wstack, ilstack, warmups), n_grid)
        _record_launch("_run_window_synth_batched", shape, n_steps)
        return _tally_admits(ctrl_engine._run_window_synth_batched(
            shape, window, n_cores, max_len, stacked, wstack, ilstack,
            warmups, n_steps, collect_events, reduce_keys),
            "_run_window_synth_batched", shape, n_steps, reduce_keys)
    if backend == "pallas":
        from repro.kernels.sim_step import ops as sim_step_ops
        _record_launch("sim_step.run_synth", shape, n_steps)
        out = sim_step_ops.run_synth(
            shape, n_cores, max_len, stacked, wstack, ilstack, warmups,
            n_steps, collect_events)
        if reduce_keys is not None:
            return _reduce_jit(out[0], out[1], reduce_keys)
        return out
    (stacked, wstack, ilstack, warmups), _ = _shard_grid(
        (stacked, wstack, ilstack, warmups), n_grid)
    _record_launch("_run_synth_batched", shape, n_steps)
    return _run_synth_batched(shape, n_cores, max_len, stacked, wstack,
                              ilstack, warmups, n_steps, collect_events,
                              reduce_keys)


def _drain_synth(out, grid, n_grid: int,
                 reduce_keys: tuple | None = None):
    if reduce_keys is not None:
        with span("d2h"):
            return np.asarray(out)[:n_grid]
    raw_stats, core_end, events, *tally = out
    with span("d2h"):
        stats_np = {k: np.asarray(v) for k, v in raw_stats.items()}
        core_np = np.asarray(core_end)
        _drain_admits(tally)
    hist_np, total_np = _rltl_np(events, _rltl_edges(grid, events))
    with span("finalize"):
        return [
            _finalize({k: v[g] for k, v in stats_np.items()}, core_np[g],
                      (None, None) if hist_np is None
                      else (hist_np[g], total_np[g]),
                      grid[g].workload.lengths(), grid[g])
            for g in range(n_grid)
        ]


def sweep_synth(grid: Sequence[SimConfig], rltl: bool = True,
                shape_grid: Sequence[SimConfig] | None = None,
                reduce_keys: tuple | None = None):
    """Evaluate a *synthetic* config grid — every ``cfg.workload`` set —
    with per-point on-device stream generation (DESIGN.md §10).

    The mechanics mirror ``sweep()``: one static ``SimShape`` (padded
    over ``shape_grid``), stacked traced params, one vmapped jitted
    launch sharded across devices.  On top of ``MechParams``, each grid
    point stacks its ``WorkloadParams`` ([grid, C] leaves) and
    ``InterleaveParams``, and the scan consumes a stream generated *for*
    its active geometry through the interleave layer — ``fold_address``
    is the identity and the recomputed ``next_same`` lookahead is exact
    by construction.  Results are bitwise-identical to simulating the
    host-materialized view of the same stream
    (``repro.workloads.materialize``; tests/test_workloads.py).

    All specs must share the core count; per-core array length pads to
    the longest (traffic-scaled) spec across ``shape_grid``, padded
    steps being no-ops as usual.

    With ``reduce_keys`` set (DESIGN.md §13) the launch reduces on
    device and returns a ``[grid, len(reduce_keys)]`` int32 array.
    """
    grid = list(grid)
    (shape, n_cores, max_len, n_steps, stacked, wstack, ilstack,
     warmups) = _stage_synth(grid, shape_grid)
    n_grid = len(grid)
    ctrl, win = _launch_controller(grid, shape_grid)
    out = _launch_synth(shape, n_cores, max_len, stacked, wstack,
                        ilstack, warmups, n_steps, rltl, n_grid,
                        backend=_uniform_backend(grid),
                        reduce_keys=reduce_keys,
                        controller=ctrl, window=win)
    return _drain_synth(out, grid, n_grid, reduce_keys)


def simulate_synth(cfg: SimConfig) -> dict:
    """One synthetic grid point, streamed end to end (``cfg.workload``
    selects the profiles; ``cfg.interleave`` the channel map).  The
    single-point view of ``sweep_synth`` — bitwise-identical to
    ``simulate(materialize(cfg.workload, cfg.dram, cfg.interleave),
    cfg)``, the materialized-trace path.  Always runs the authoritative
    ref engine (the single-point *oracle*; ``cfg.backend`` only routes
    the batched entries)."""
    assert cfg.workload is not None, "simulate_synth needs cfg.workload"
    return sweep_synth([dataclasses.replace(cfg, backend="ref")],
                       rltl=True)[0]


def sweep_serving(grid: Sequence[SimConfig],
                  shape_grid: Sequence[SimConfig] | None = None,
                  counts=None, collect_steps: bool = False,
                  reduce_keys: tuple | None = None):
    """Evaluate a *serving* config grid — every ``cfg.serving`` set —
    as one fused continuous-batching scan per point, vmapped across the
    grid (DESIGN.md §12).  The serving sibling of ``sweep_synth``; the
    engine lives in ``repro.serving.loop`` (which imports this core
    layer), imported lazily to keep the module graph acyclic.

    With ``reduce_keys`` set (keys from ``engine.SERVE_REDUCE_KEYS``)
    the launch reduces on device and returns ``[grid, n_keys]`` int32.
    """
    from repro.serving.loop import engine
    return engine.run_sweep(grid, shape_grid=shape_grid, counts=counts,
                            collect_steps=collect_steps,
                            reduce_keys=reduce_keys)


def simulate_serving(cfg: SimConfig, counts=None,
                     collect_steps: bool = True) -> dict:
    """One serving grid point, fused end to end (single-point view of
    ``sweep_serving``; per-step occupancy/queue arrays collected by
    default)."""
    from repro.serving.loop import engine
    return engine.simulate_serving(cfg, counts=counts,
                                   collect_steps=collect_steps)


def weighted_speedup(core_end_base: np.ndarray, core_end_mech: np.ndarray,
                     alone_end: np.ndarray | None = None) -> float:
    """Thesis metric: WS = sum_i IPC_shared_i / IPC_alone_i; with fixed
    per-core instruction counts this reduces to cycle ratios.  The speedup
    of a mechanism is WS_mech / WS_base."""
    if alone_end is None:
        alone_end = core_end_base
    ws_base = float(np.sum(alone_end / np.maximum(core_end_base, 1)))
    ws_mech = float(np.sum(alone_end / np.maximum(core_end_mech, 1)))
    return ws_mech / max(ws_base, 1e-9)
