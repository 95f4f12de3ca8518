"""HCRAC — the Highly-Charged Row Address Cache (thesis §4.2).

A tag-only, set-associative cache of *global row ids* kept by the memory
controller.  Three operations (thesis §4.2.1-4.2.3):

* ``insert``  — on every PRE, the just-closed row's address is inserted.
* ``lookup``  — on every ACT, a hit means the row is still highly charged
  and the lowered tRCD/tRAS may be used.
* invalidate — the thesis uses two counters (IIC, EC) that sweep the k
  entries once per caching duration ``C`` cycles, so no entry older than
  ``C`` survives (entries may be invalidated *prematurely*, with lifetime
  uniform in (0, C] depending on their slot's sweep phase).

Instead of stepping IIC every cycle (impossible to vectorize efficiently),
we emulate the counter pair **exactly** with timestamps: physical slot
``s`` (``s = set * ways + way``) is swept at absolute cycles
``t ≡ (s+1) * C/k  (mod C)``.  An entry inserted at ``t_i`` is alive at
lookup time ``t`` iff no sweep of its slot occurred in ``(t_i, t]``::

    alive  <=>  floor((t - phase_s) / C) == floor((t_i - phase_s) / C)

which is bit-exact with the hardware scheme described in the thesis.
Setting ``exact_expiry=True`` switches to the idealised per-entry timer
(``t - t_i <= C``) the thesis mentions as the costlier alternative — the
performance difference between the two is one of our reproduced claims
("the loss due to premature invalidation is negligible").

All state lives in small arrays, so the structure ``vmap``s across
channels / configurations and runs inside ``lax.scan`` simulator steps.
The tables are stored way-major, ``[ways, sets]``: under ``vmap`` the
lanes lead and the set axis is the minor one, so it fills the TPU's
128-lane tile.  Set-major ``[sets, ways]`` tables would put the 2 ways
on the lane axis, padded 64x, and the compiler copies a table into that
padded form for every row read of every scan step.  Reads and writes
address single ``(way, set)`` elements, one per way (``_row``,
``.at[way, set]``), so both want the one layout.

Static shape vs traced params (DESIGN.md §4): every operation takes an
``HCRACConfig`` — the *static* part, fixing array shapes (``n_sets`` /
``n_ways``) and the expiry flavour — plus an optional ``HCRACParams``
pytree of *traced* values (active set count, caching duration, sweep
period).  When ``params`` is given, ``cfg.n_sets`` only bounds the array
shape and ``params.n_sets`` does the addressing, so HCRACs of different
capacities share one compiled program: a capacity-``k`` table lives in the
first ``k / n_ways`` sets of the padded array (sets beyond the active
count are never addressed — modular indexing is the active-entry mask)
and a whole capacity sweep ``vmap``s over stacked params.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

# np scalar so Pallas kernel bodies may close over it (see dram.NO_ROW)
NO_TAG = np.int32(-1)


@dataclasses.dataclass(frozen=True)
class HCRACConfig:
    n_entries: int = 128          # total entries (thesis default, per core)
    n_ways: int = 2               # 2-way set associative, LRU (Table 5.1)
    caching_cycles: int = 800_000  # 1 ms at the 800 MHz bus clock
    exact_expiry: bool = False    # idealised timer instead of IIC/EC sweep

    @property
    def n_sets(self) -> int:
        assert self.n_entries % self.n_ways == 0
        return self.n_entries // self.n_ways

    @property
    def sweep_period(self) -> int:
        """IIC period: C / k cycles between successive slot invalidations."""
        return max(1, self.caching_cycles // self.n_entries)


class HCRACState(NamedTuple):
    tags: jnp.ndarray     # [ways, sets] int32 global row id (NO_TAG = empty)
    itime: jnp.ndarray    # [ways, sets] int32 insertion cycle
    lru: jnp.ndarray      # [ways, sets] int32 last-touch cycle (LRU policy)


class HCRACParams(NamedTuple):
    """Traced (vmappable) HCRAC parameters; see module docstring.

    ``n_sets`` is the *active* set count — it must not exceed the static
    ``cfg.n_sets`` that sized the state arrays.
    """
    n_sets: jnp.ndarray          # int32 active sets (capacity / n_ways)
    caching_cycles: jnp.ndarray  # int32 caching duration C
    sweep_period: jnp.ndarray    # int32 C / n_entries (IIC step)


def params_of(cfg: HCRACConfig) -> HCRACParams:
    """The traced-params view of a concrete config."""
    return HCRACParams(
        n_sets=jnp.int32(cfg.n_sets),
        caching_cycles=jnp.int32(cfg.caching_cycles),
        sweep_period=jnp.int32(cfg.sweep_period),
    )


def init(cfg: HCRACConfig) -> HCRACState:
    shape = (cfg.n_ways, cfg.n_sets)
    return HCRACState(
        tags=jnp.full(shape, NO_TAG, jnp.int32),
        itime=jnp.zeros(shape, jnp.int32),
        lru=jnp.full(shape, -1, jnp.int32),
    )


def _slot_phase(cfg: HCRACConfig, p: HCRACParams, set_idx, way_idx):
    """Absolute-cycle phase of the IIC/EC sweep for each physical slot."""
    slot = set_idx * cfg.n_ways + way_idx
    return (slot + 1) * p.sweep_period


def _alive(cfg: HCRACConfig, set_idx, itime, t, params: HCRACParams = None):
    """Whether entries inserted at ``itime`` are still valid at cycle ``t``."""
    p = params if params is not None else params_of(cfg)
    ways = jnp.arange(cfg.n_ways, dtype=jnp.int32)
    if cfg.exact_expiry:
        return (t - itime) <= p.caching_cycles
    phase = _slot_phase(cfg, p, set_idx, ways)
    c = p.caching_cycles
    # Same sweep window <=> no invalidation of this slot in (itime, t].
    return (t - phase) // c == (itime - phase) // c


def _row(cfg: HCRACConfig, table, set_idx):
    """The entries of set(s) ``set_idx`` in every way, ``[ways,
    *set_idx.shape]``: one read of single elements per way, which leaves
    the table in the layout its writes want.  Slicing the whole ``[ways]``
    column (``table[:, set_idx]``) makes the compiler put the way axis
    minor for the read and relayout the table to get there."""
    return jnp.stack([table[w, set_idx] for w in range(cfg.n_ways)])


def lookup(cfg: HCRACConfig, st: HCRACState, gid, t, enable=True,
           params: HCRACParams = None):
    """Look up global row id ``gid`` at cycle ``t``.

    Returns ``(hit, new_state)``; a hit refreshes the matching entry's LRU
    stamp.  ``enable`` masks the LRU side effect (the returned ``hit`` is
    unmasked — callers combine it with their own predicates).
    """
    p = params if params is not None else params_of(cfg)
    set_idx = jnp.mod(gid, p.n_sets).astype(jnp.int32)
    row_tags = _row(cfg, st.tags, set_idx)  # [ways]
    row_itime = _row(cfg, st.itime, set_idx)
    valid = (row_tags != NO_TAG) & _alive(cfg, set_idx, row_itime, t, p)
    match = valid & (row_tags == gid)
    hit = jnp.any(match)
    new_lru = jnp.where(match & jnp.asarray(enable), t,
                        _row(cfg, st.lru, set_idx))
    lru = st.lru
    for w in range(cfg.n_ways):
        lru = lru.at[w, set_idx].set(new_lru[w])
    st = st._replace(lru=lru)
    return hit, st


def insert(cfg: HCRACConfig, st: HCRACState, gid, t, enable=True,
           params: HCRACParams = None):
    """Insert global row id ``gid`` at cycle ``t`` (called on PRE).

    Victim selection: an already-matching way (refresh in place), else an
    invalid/expired way, else the LRU way.  ``enable`` masks the update
    (so the call is safe inside ``lax.scan`` branches).
    """
    p = params if params is not None else params_of(cfg)
    set_idx = jnp.mod(gid, p.n_sets).astype(jnp.int32)
    row_tags = _row(cfg, st.tags, set_idx)
    row_itime = _row(cfg, st.itime, set_idx)
    row_lru = _row(cfg, st.lru, set_idx)
    valid = (row_tags != NO_TAG) & _alive(cfg, set_idx, row_itime, t, p)
    match = valid & (row_tags == gid)

    # Priority: match > first invalid > LRU.
    inv_way = jnp.argmin(valid)                  # first False if any
    any_inv = jnp.any(~valid)
    lru_way = jnp.argmin(jnp.where(valid, row_lru, jnp.iinfo(jnp.int32).max))
    way = jnp.where(jnp.any(match), jnp.argmax(match),
                    jnp.where(any_inv, inv_way, lru_way)).astype(jnp.int32)

    en = jnp.asarray(enable)
    new_tags = st.tags.at[way, set_idx].set(jnp.where(en, gid, row_tags[way]))
    new_itime = st.itime.at[way, set_idx].set(
        jnp.where(en, t, row_itime[way]))
    new_lru = st.lru.at[way, set_idx].set(jnp.where(en, t, row_lru[way]))
    return HCRACState(tags=new_tags, itime=new_itime, lru=new_lru)


def padded_shape(cfg: HCRACConfig, n_sets_max: int) -> HCRACConfig:
    """The static shape carrier for a capacity sweep: same ways / expiry,
    arrays sized for ``n_sets_max`` sets.  Traced fields are zeroed so that
    configs differing only in capacity / duration hash to one shape (and
    therefore one XLA compilation)."""
    assert n_sets_max >= cfg.n_sets
    return dataclasses.replace(cfg, n_entries=n_sets_max * cfg.n_ways,
                               caching_cycles=0)


def storage_bits(cfg: HCRACConfig, n_ranks=1, n_banks=8, n_rows=65536) -> int:
    """Thesis Eq. 6.1/6.2 storage cost (bits) for one HCRAC instance."""
    entry = (int(jnp.ceil(jnp.log2(n_ranks))) if n_ranks > 1 else 0)
    entry += int(jnp.ceil(jnp.log2(n_banks))) + int(jnp.ceil(jnp.log2(n_rows))) + 1
    lru_bits = 1 if cfg.n_ways == 2 else max(1, cfg.n_ways.bit_length())
    return cfg.n_entries * (entry + lru_bits)
