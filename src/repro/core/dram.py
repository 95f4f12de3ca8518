"""DRAM geometry, address mapping, and refresh-phase arithmetic.

Matches Table 5.1 of the thesis: DDR3-1600, 1-2 channels, 1 rank/channel,
8 banks/rank, 64 K rows/bank, 8 KB row buffer.  Banks are indexed globally
(``channel * banks_per_channel + bank``) throughout the simulator.

Static envelope vs traced geometry (DESIGN.md §8): a concrete system is
described by ``DRAMConfig`` (host-side, hashable).  For the batched
experiment engine the configuration splits into

* ``DRAMEnvelope`` — the *static* padded layout: the maximum channel /
  global-bank / row counts across a grid.  It is the only geometry fact
  that determines array shapes, so every geometry in a sweep shares one
  XLA compilation.
* ``GeomParams``  — the *traced* active counts (channels, ranks, banks,
  rows, row-buffer bytes).  Channel-of / bank-of / row-id address mapping
  is modular arithmetic over these traced values, so banks and channels
  beyond the active counts are simply never addressed — the same
  padded-prefix trick the HCRAC uses for capacity sweeps (DESIGN.md §4).

Refresh is modelled as the standard rolling all-bank auto-refresh: every
``tREFI`` one of ``n_refresh_groups`` row groups is refreshed, so row ``r``
of any bank is recharged at absolute cycles
``(r mod G) * tREFI + k * retention``.  This gives a *closed form* for
time-since-last-refresh, which is what NUAT [Shin+ HPCA'14] keys on — no
per-row refresh state is needed.  The refresh-group arithmetic lives in
``TimingParams``/``TimingVec`` (already traced), so it sweeps with the
timing axis rather than the geometry axis.
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, NamedTuple

import jax.numpy as jnp
import numpy as np

# np (not jnp) scalar: strongly-typed int32 with identical promotion,
# but literalable — Pallas kernel bodies may close over it (DESIGN.md §11)
NO_ROW = np.int32(-1)


@dataclasses.dataclass(frozen=True)
class DRAMConfig:
    n_channels: int = 2
    n_ranks: int = 1
    n_banks: int = 8          # per rank
    n_rows: int = 65536       # per bank
    row_buffer_bytes: int = 8192
    #: bank groups per rank (DDR4, DESIGN.md §16): bank ``b`` of a rank
    #: is in group ``b mod n_bank_groups``, so consecutive bank ids fall
    #: in different groups (an assumed mapping).  1 = no bank groups,
    #: the DDR3 model.
    n_bank_groups: int = 1

    def __post_init__(self):
        assert self.n_bank_groups >= 1 and \
            self.n_banks % self.n_bank_groups == 0, (
                f"n_banks ({self.n_banks}) must split evenly into "
                f"n_bank_groups ({self.n_bank_groups})")

    @property
    def banks_total(self) -> int:
        return self.n_channels * self.n_ranks * self.n_banks

    @property
    def banks_per_channel(self) -> int:
        return self.n_ranks * self.n_banks

    def channel_of(self, global_bank):
        return global_bank // (self.n_ranks * self.n_banks)

    def global_row_id(self, global_bank, row):
        """Unique id for (bank, row) — the HCRAC tag (thesis Eq. 6.2)."""
        return global_bank * jnp.int32(self.n_rows) + row


#: Default two-channel system of Table 5.1.
DDR3_SYSTEM = DRAMConfig()


@dataclasses.dataclass(frozen=True)
class DRAMEnvelope:
    """The static half of the geometry: the padded layout every grid point
    shares.  Only ``max_channels`` / ``max_banks_total`` size arrays; the
    row count rides along for memory-budget accounting and documentation.
    Equal envelopes ⇒ one XLA compilation (DESIGN.md §8)."""
    max_channels: int = 2
    max_banks_total: int = 16
    max_rows: int = 65536
    #: the most bank groups of any point: > 1 is the only thing that
    #: compiles the bank-group path (its registers, rules and counters);
    #: at 1 the step is the DDR3 step, leaf for leaf (DESIGN.md §16)
    max_bank_groups: int = 1

    def covers(self, cfg: DRAMConfig) -> bool:
        return (self.max_channels >= cfg.n_channels
                and self.max_banks_total >= cfg.banks_total
                and self.max_rows >= cfg.n_rows
                and self.max_bank_groups >= cfg.n_bank_groups)


def envelope_of(cfgs: Iterable[DRAMConfig]) -> DRAMEnvelope:
    """The smallest ``DRAMEnvelope`` covering every config in ``cfgs``."""
    cfgs = list(cfgs)
    assert cfgs, "envelope of an empty geometry set"
    return DRAMEnvelope(
        max_channels=max(c.n_channels for c in cfgs),
        max_banks_total=max(c.banks_total for c in cfgs),
        max_rows=max(c.n_rows for c in cfgs),
        max_bank_groups=max(c.n_bank_groups for c in cfgs),
    )


class GeomParams(NamedTuple):
    """Traced (vmappable) DRAM geometry: every leaf an int32 scalar array,
    stacked along the grid axis by ``sweep()`` so 1-vs-2-channel and
    bank-count sweeps ride one compilation.  Address mapping over these is
    modular arithmetic: a trace's (bank, row) folds into the active
    geometry as ``bank mod banks_total`` / ``row mod n_rows`` — identity
    whenever the trace was generated for this geometry, and the
    contention-preserving remap for geometry sensitivity studies."""
    n_channels: jnp.ndarray
    n_ranks: jnp.ndarray
    n_banks: jnp.ndarray            # per rank
    n_rows: jnp.ndarray             # per bank
    banks_total: jnp.ndarray        # n_channels * n_ranks * n_banks
    banks_per_channel: jnp.ndarray  # n_ranks * n_banks
    row_buffer_bytes: jnp.ndarray
    n_bank_groups: jnp.ndarray      # per rank (read on the bank-group
                                    # path only, DESIGN.md §16)


def geom_params(cfg: DRAMConfig) -> GeomParams:
    """The traced-params view of a concrete ``DRAMConfig``."""
    return GeomParams(
        n_channels=jnp.int32(cfg.n_channels),
        n_ranks=jnp.int32(cfg.n_ranks),
        n_banks=jnp.int32(cfg.n_banks),
        n_rows=jnp.int32(cfg.n_rows),
        banks_total=jnp.int32(cfg.banks_total),
        banks_per_channel=jnp.int32(cfg.banks_per_channel),
        row_buffer_bytes=jnp.int32(cfg.row_buffer_bytes),
        n_bank_groups=jnp.int32(cfg.n_bank_groups),
    )


def channel_of(geom: GeomParams, global_bank):
    """Channel owning a global bank id — data-driven (traced) division."""
    return global_bank // geom.banks_per_channel


def global_row_id(geom: GeomParams, global_bank, row):
    """Unique id for (bank, row) — the HCRAC tag (thesis Eq. 6.2), over
    the traced geometry."""
    return global_bank * geom.n_rows + row


def bank_group_of(geom: GeomParams, global_bank):
    """Bank group of a global bank within its rank: ``(bank within rank)
    mod n_bank_groups``, so consecutive bank ids fall in different groups
    (the assumed DDR4 mapping, DESIGN.md §16)."""
    return jnp.mod(jnp.mod(global_bank, geom.n_banks), geom.n_bank_groups)


def bank_group_slot(geom: GeomParams, global_bank):
    """Register slot of (rank, bank group) in a ``[max_banks_total]``
    array: the rank's first global bank id plus the group.  A group index
    is below ``n_banks``, so slots of different (rank, group) pairs never
    collide and always lie inside the envelope's bank count."""
    return (global_bank - jnp.mod(global_bank, geom.n_banks)
            + bank_group_of(geom, global_bank))


def in_active_geometry(geom: GeomParams, bank, row):
    """Traced bool: (bank, row) directly addresses the active geometry —
    exactly the domain on which ``fold_address`` is the identity (the
    padded-parity case; property-tested in tests/test_geometry.py)."""
    bank = jnp.asarray(bank)
    row = jnp.asarray(row)
    return ((bank >= 0) & (bank < geom.banks_total)
            & (row >= 0) & (row < geom.n_rows))


def fold_address(geom: GeomParams, bank, row):
    """Map a trace's (bank, row) into the active geometry.

    Modular folding over the traced counts: for a trace generated against
    this geometry the mapping is the identity (bitwise-neutral, verified
    in tests/test_geometry.py); for a smaller active geometry the request
    stream folds onto fewer banks/channels, preserving total traffic while
    increasing contention — exactly the channel-sensitivity comparison of
    the thesis (Table 5.1 variants).

    The closed-row policy's queue-hit lookahead (``next_same``) is
    recomputed *post-fold* on device (``simulator._next_same_folded``),
    so cross-bank fold collisions are reflected in the controller hint —
    exact for identity and non-identity folds alike (DESIGN.md §8, §10;
    the pre-PR-5 host precompute was stale under non-identity folds).
    """
    return jnp.mod(bank, geom.banks_total), jnp.mod(row, geom.n_rows)


# --------------------------------------------------------------------------
# Channel interleaving (DESIGN.md §10.2): how the on-device workload
# generator composes a logical (bank, row) pair into a physical global
# bank id — i.e. which *channel* owns a request.  Host-materialized
# traces address global banks directly (the "bank" identity policy);
# the synthetic-generation path makes the policy a traced experiment
# axis (``register_axis("interleave")``) in the spirit of the
# parallelism/interleaving characterization of Chang's thesis
# (arXiv:1712.08304).
# --------------------------------------------------------------------------

#: registered interleave policies, index = the traced ``kind_id``
INTERLEAVE_KINDS = ("bank", "row", "block", "xor")


@dataclasses.dataclass(frozen=True)
class InterleaveConfig:
    """Host-side (hashable) channel-interleave policy selection.

    * ``bank`` — identity: the logical bank id carries the channel bits
      (``channel = lb // banks_per_channel``), exactly how materialized
      traces address banks.  The parity baseline.
    * ``row`` — fine-grained: consecutive rows round-robin the channels
      (``channel = row mod n_channels``); streaming spreads across
      channels, hot rows pin to one.
    * ``block`` — coarse-grained: ``block_rows``-row blocks stay
      channel-contiguous (``channel = (row // block_rows) mod n_ch``);
      locality stays within a channel, conflicts concentrate.
    * ``xor`` — permutation-based skew (``channel = (row XOR lb) mod
      n_ch``): the classic conflict-dispersing XOR map.
    """
    kind: str = "bank"
    block_rows: int = 32

    def __post_init__(self):
        assert self.kind in INTERLEAVE_KINDS, (
            f"unknown interleave kind {self.kind!r}; "
            f"known: {INTERLEAVE_KINDS}")
        assert self.block_rows >= 1


class InterleaveParams(NamedTuple):
    """Traced (vmappable) interleave policy: the kind as data, so an
    interleave sweep rides the same single compilation as every other
    axis (the same split as ``GeomParams``)."""
    kind_id: jnp.ndarray     # int32 index into INTERLEAVE_KINDS
    block_rows: jnp.ndarray  # int32


def interleave_params(cfg: InterleaveConfig) -> InterleaveParams:
    """The traced-params view of a concrete ``InterleaveConfig``."""
    return InterleaveParams(
        kind_id=jnp.int32(INTERLEAVE_KINDS.index(cfg.kind)),
        block_rows=jnp.int32(cfg.block_rows),
    )


def compose_address(geom: GeomParams, il: InterleaveParams, lb, row):
    """Compose a logical (bank, row) into a physical global bank id.

    ``lb`` is a *logical* bank in ``[0, banks_total)`` (the generator's
    conflict-target choice); the interleave policy decides only which
    channel serves it.  All four policies are evaluated data-driven and
    selected by the traced ``kind_id``, so mixed-policy grids share one
    compilation.  For ``kind_id == 0`` ("bank") the map is the identity
    ``lb`` — bitwise the materialized-trace addressing (tested).  With
    one active channel every policy degenerates to the identity (all
    channel terms are mod-1 zero), which the experiment runner's dedup
    exploits.
    """
    lb = jnp.asarray(lb, jnp.int32)
    row = jnp.asarray(row, jnp.int32)
    bpc = geom.banks_per_channel
    nch = geom.n_channels
    ch_home = lb // bpc
    ch_row = jnp.mod(row, nch)
    ch_blk = jnp.mod(row // jnp.maximum(il.block_rows, 1), nch)
    ch_xor = jnp.mod(row ^ lb, nch)
    ch = jnp.where(il.kind_id == 1, ch_row,
                   jnp.where(il.kind_id == 2, ch_blk,
                             jnp.where(il.kind_id == 3, ch_xor, ch_home)))
    return ch * bpc + jnp.mod(lb, bpc)


def time_since_refresh(geom, timing, row, t):
    """Cycles since row ``row``'s group was last refreshed, at cycle ``t``.

    Closed form from the rolling-refresh schedule; always in
    ``[0, retention)``.  ``timing`` may be a static ``TimingParams`` or a
    traced params pytree with the same field names (DESIGN.md §4);
    ``geom`` (a ``GeomParams`` or ``DRAMConfig``) rides along for API
    symmetry — the refresh-group arithmetic is timing data.
    """
    groups = jnp.asarray(timing.n_refresh_groups, jnp.int32)
    phase = jnp.mod(row, groups) * jnp.asarray(timing.tREFI, jnp.int32)
    return jnp.mod(t - phase, jnp.asarray(timing.retention_cycles, jnp.int32))


def refresh_adjust(timing, t, row=None):
    """Earliest cycle >= t at which a bank command may issue, accounting for
    the refresh that occupies the first ``tRFC`` cycles of every ``tREFI``
    window (the legacy closed-form tier; DESIGN.md §14).

    With ``row`` given, only commands to the refresh *group* being
    restored in the current window stall — window ``k`` refreshes group
    ``k mod n_refresh_groups``, matching ``time_since_refresh``'s rolling
    schedule.  ``row=None`` keeps the pre-PR-9 all-bank blackout.
    """
    tREFI = jnp.asarray(timing.tREFI, jnp.int32)
    r = jnp.mod(t, tREFI)
    busy = r < timing.tRFC
    if row is not None:
        groups = jnp.asarray(timing.n_refresh_groups, jnp.int32)
        busy = busy & (jnp.mod(row, groups) == jnp.mod(t // tREFI, groups))
    return jnp.where(busy, t + (jnp.asarray(timing.tRFC, jnp.int32) - r), t)


def refresh_clamp_span(timing, t, span, row=None):
    """Earliest start >= ``t`` such that ``[start, start + span)`` avoids
    the refresh blackout — the burst-window form of ``refresh_adjust``
    (an RD/WR command plus its data burst must not overlap
    ``[k·tREFI, k·tREFI + tRFC)``).  Requires ``span <= tREFI - tRFC``
    so one push always clears the window.  With ``row`` given, only the
    window whose refresh group matches the row stalls the burst.
    """
    tREFI = jnp.asarray(timing.tREFI, jnp.int32)
    tRFC = jnp.asarray(timing.tRFC, jnp.int32)
    r = jnp.mod(t, tREFI)
    base = t - r
    in_this = r < tRFC                 # start inside window k's blackout
    into_next = r + span > tREFI       # burst straddles window k+1's
    if row is not None:
        groups = jnp.asarray(timing.n_refresh_groups, jnp.int32)
        k = t // tREFI
        g = jnp.mod(row, groups)
        in_this = in_this & (g == jnp.mod(k, groups))
        into_next = into_next & (g == jnp.mod(k + 1, groups))
    fixed = jnp.where(in_this, base + tRFC, base + tREFI + tRFC)
    return jnp.where(in_this | into_next, fixed, t)
