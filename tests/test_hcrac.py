"""HCRAC invariants: unit tests + hypothesis property tests.

Key invariant (thesis §4.2.3): with the IIC/EC counter invalidation, *no
lookup may hit on an entry older than the caching duration* — the
mechanism's safety property (a stale hit would under-time a leaky row).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypo import given, settings, st  # hypothesis, or deterministic fallback

from repro.controller.oracle import _HostHCRAC
from repro.core import hcrac as H

CFG = H.HCRACConfig(n_entries=32, n_ways=2, caching_cycles=1000)


def test_insert_then_hit():
    st_ = H.init(CFG)
    st_ = H.insert(CFG, st_, jnp.int32(42), jnp.int32(10))
    hit, _ = H.lookup(CFG, st_, jnp.int32(42), jnp.int32(20))
    assert bool(hit)


def test_miss_on_other_row():
    st_ = H.init(CFG)
    st_ = H.insert(CFG, st_, jnp.int32(42), jnp.int32(10))
    hit, _ = H.lookup(CFG, st_, jnp.int32(43), jnp.int32(20))
    assert not bool(hit)


def test_expiry_after_caching_duration():
    st_ = H.init(CFG)
    st_ = H.insert(CFG, st_, jnp.int32(42), jnp.int32(10))
    hit, _ = H.lookup(CFG, st_, jnp.int32(42),
                      jnp.int32(10 + CFG.caching_cycles + 1))
    assert not bool(hit)


def test_lru_eviction():
    """Third distinct row in a 2-way set evicts the least recently used."""
    cfg = H.HCRACConfig(n_entries=2, n_ways=2, caching_cycles=10**6)
    st_ = H.init(cfg)
    st_ = H.insert(cfg, st_, jnp.int32(1), jnp.int32(1))
    st_ = H.insert(cfg, st_, jnp.int32(2), jnp.int32(2))
    _, st_ = H.lookup(cfg, st_, jnp.int32(1), jnp.int32(3))  # touch 1
    st_ = H.insert(cfg, st_, jnp.int32(3), jnp.int32(4))     # evicts 2
    assert bool(H.lookup(cfg, st_, jnp.int32(1), jnp.int32(5))[0])
    assert not bool(H.lookup(cfg, st_, jnp.int32(2), jnp.int32(5))[0])
    assert bool(H.lookup(cfg, st_, jnp.int32(3), jnp.int32(5))[0])


@pytest.mark.slow
@settings(max_examples=40, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 200), st.integers(1, 50)),
                min_size=1, max_size=60),
       st.integers(0, 200),
       st.booleans())
def test_no_stale_hits(ops, probe_gid, exact):
    """PROPERTY: a hit implies the row was inserted within the caching
    duration (for both the IIC/EC emulation and the exact-timer variant);
    and with the exact timer, an insert within the window + no eviction
    pressure implies a hit (no false negatives beyond premature sweep)."""
    cfg = H.HCRACConfig(n_entries=64, n_ways=2, caching_cycles=500,
                        exact_expiry=exact)
    st_ = H.init(cfg)
    t = 0
    last_insert: dict[int, int] = {}
    for gid, dt in ops:
        t += dt
        st_ = H.insert(cfg, st_, jnp.int32(gid), jnp.int32(t))
        last_insert[gid] = t
    probe_t = t + 1
    hit, _ = H.lookup(cfg, st_, jnp.int32(probe_gid), jnp.int32(probe_t))
    if bool(hit):
        assert probe_gid in last_insert
        assert probe_t - last_insert[probe_gid] <= cfg.caching_cycles


@pytest.mark.slow
@settings(max_examples=60, deadline=None)
@given(st.integers(0, 10_000), st.integers(0, 2_000), st.integers(0, 31))
def test_sweep_alive_implies_within_duration(itime, dt, set_idx):
    """LEMMA behind the IIC/EC emulation: an entry its slot's sweep has
    not yet crossed is necessarily younger than the caching duration
    (sweep-aliveness is *strictly stronger* than the exact timer) — i.e.
    premature invalidation may only shorten lifetimes, never extend."""
    cfg = H.HCRACConfig(n_entries=64, n_ways=2, caching_cycles=400)
    t = itime + dt
    alive = bool(np.asarray(
        H._alive(cfg, jnp.int32(set_idx), jnp.full((2,), itime, jnp.int32),
                 jnp.int32(t))).any())
    if alive:
        assert t - itime <= cfg.caching_cycles


@pytest.mark.parametrize("exact", [False, True], ids=["sweep", "exact"])
@pytest.mark.parametrize("n_sets,n_ways", [(4, 1), (8, 2), (16, 4)])
def test_matches_numpy_oracle_step_by_step(n_sets, n_ways, exact):
    """A random insert/lookup sequence against ``controller/oracle.py``'s
    numpy HCRAC: every hit, and after every operation the whole table
    read back through the way-major ``[ways, sets]`` layout.  Few row ids
    and jumps past the duration give expired entries in several ways of a
    set (first-invalid ties); inserts at the same cycle give equal LRU
    stamps (lowest-way ties); masked inserts must change nothing."""
    cfg = H.HCRACConfig(n_entries=n_sets * n_ways, n_ways=n_ways,
                        caching_cycles=400, exact_expiry=exact)
    insert = jax.jit(H.insert, static_argnums=0)
    lookup = jax.jit(H.lookup, static_argnums=0)
    rng = np.random.default_rng(n_sets * 10 + n_ways + 100 * exact)
    st_, ref = H.init(cfg), _HostHCRAC(cfg)
    t = s = 0
    kinds = {"first_invalid_tie": 0, "lru_tie": 0}
    for _ in range(400):
        t += 450 if rng.random() < 0.02 else int(rng.choice([0, 0, 1, 2]))
        gid = int(rng.integers(0, (n_ways + 1) * n_sets))
        if rng.random() < 0.5:                   # stay in the last set
            gid = s + n_sets * int(rng.integers(0, n_ways + 1))
        s = gid % n_sets
        if rng.random() < 0.6:
            en = bool(rng.random() < 0.9)
            valid = ref._valid(s, t)
            if en and not (valid & (ref.tags[s] == gid)).any():
                if (~valid).sum() > 1:
                    kinds["first_invalid_tie"] += 1
                elif valid.all() and (ref.lru[s] == ref.lru[s].min()).sum() > 1:
                    kinds["lru_tie"] += 1
            st_ = insert(cfg, st_, jnp.int32(gid), jnp.int32(t),
                         enable=jnp.bool_(en))
            ref.insert(gid, t, enable=en)
        else:
            hit, st_ = lookup(cfg, st_, jnp.int32(gid), jnp.int32(t))
            assert bool(hit) == ref.lookup(gid, t)
        assert st_.tags.shape == (n_ways, n_sets)
        for got, want in ((st_.tags, ref.tags), (st_.itime, ref.itime),
                          (st_.lru, ref.lru)):
            np.testing.assert_array_equal(np.asarray(got), want.T)
        np.testing.assert_array_equal(
            np.asarray(H._row(cfg, st_.tags, jnp.int32(s))), ref.tags[s])
    if n_ways > 1:
        assert min(kinds.values()) > 0, kinds


def test_storage_cost_matches_thesis():
    """Thesis §6.3: 128 entries, 2 channels, 8 cores -> 5376 bytes total;
    672 bytes per core per channel... 128 entries/core across 2 channels."""
    cfg = H.HCRACConfig(n_entries=128, n_ways=2)
    bits = H.storage_bits(cfg, n_ranks=1, n_banks=8, n_rows=65536)
    per_core_bytes = bits / 8
    # eq 6.2: 3 + 16 + 1 valid = 20 bits + 1 LRU = 21 bits -> 336 B;
    # x2 channels = 672 B/core; x8 cores = 5376 B
    assert per_core_bytes == 336
    assert per_core_bytes * 2 == 672
    assert per_core_bytes * 2 * 8 == 5376
