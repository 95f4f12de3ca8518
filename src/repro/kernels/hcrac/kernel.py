"""Batched HCRAC-lookup Pallas kernel (the paper's table as a kernel).

The serving scheduler probes the hot-row table for whole batches of
candidate pages at once (millions of probes/s at fleet rates); this kernel
tiles the probe stream while the *entire* tag array stays VMEM-resident —
at the thesis's 128-entry default the table is ~1 KB, and even the
1024-entry 8-core table is 8 KB per operand.  There is no gather: each
probe block compares against every set at once (a one-hot set mask over
lane-dense ``[W, S]`` rows), which is what Mosaic lowers for a table this
small.

Exact IIC/EC sweep semantics (same arithmetic as repro.core.hcrac._alive):
entry in physical slot ``s`` is alive at ``t`` iff no sweep of ``s``
occurred in ``(itime, t]``.  The wrapper turns that into a per-entry live
interval ``[lo, hi)`` of lookup times (``floor((t - phase) / C) == e``
iff ``phase + e*C <= t < phase + (e+1)*C``), so the kernel body is
compares and one lane reduction — no integer division on the vector unit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.hcrac import NO_TAG, HCRACConfig

_I32_MIN = np.int32(np.iinfo(np.int32).min)


def _hcrac_kernel(set_ref, gid_ref, t_ref, tags_ref, lo_ref, hi_ref,
                  hit_ref, *, n_ways):
    set_idx = set_ref[...]                           # [bq, 1]
    gids = gid_ref[...]
    ts = t_ref[...]
    n_sets = tags_ref.shape[1]
    onehot = jax.lax.broadcasted_iota(
        jnp.int32, (set_idx.shape[0], n_sets), 1) == set_idx   # [bq, S]
    hit = jnp.zeros(onehot.shape, jnp.int32)
    for w in range(n_ways):
        tag = tags_ref[w:w + 1, :]                   # [1, S]
        match = (onehot & (tag != NO_TAG) & (tag == gids)
                 & (lo_ref[w:w + 1, :] <= ts) & (ts < hi_ref[w:w + 1, :]))
        hit = jnp.maximum(hit, match.astype(jnp.int32))
    hit_ref[...] = jnp.max(hit, axis=1, keepdims=True)


def _live_interval(cfg: HCRACConfig, tags, itime):
    """Per-entry ``[lo, hi)`` interval of lookup times at which the entry
    is alive, ``[W, S]`` each (transposed: lanes run over sets)."""
    c = jnp.int32(cfg.caching_cycles)
    if cfg.exact_expiry:
        # (t - itime) <= C
        lo = jnp.full(itime.shape, _I32_MIN, jnp.int32)
        hi = itime + c + 1
    else:
        S, W = tags.shape
        slot = (jnp.arange(S, dtype=jnp.int32)[:, None] * W
                + jnp.arange(W, dtype=jnp.int32)[None, :])
        phase = (slot + 1) * jnp.int32(cfg.sweep_period)
        lo = phase + ((itime - phase) // c) * c
        hi = lo + c
    return lo.T, hi.T


def hcrac_lookup_kernel(cfg: HCRACConfig, tags, itime, gids, times, *,
                        block_q: int = 256, interpret: bool = False):
    """tags/itime: [S, W]; gids/times: [Q] -> hits [Q] int32."""
    Q = gids.shape[0]
    block_q = min(block_q, Q)
    assert Q % block_q == 0
    S, W = tags.shape
    lo, hi = _live_interval(cfg, tags, itime)
    col = lambda x: x.astype(jnp.int32).reshape(Q, 1)
    probe = pl.BlockSpec((block_q, 1), lambda i: (i, 0))
    table = pl.BlockSpec((W, S), lambda i: (0, 0))
    hits = pl.pallas_call(
        functools.partial(_hcrac_kernel, n_ways=W),
        grid=(Q // block_q,),
        in_specs=[probe, probe, probe, table, table, table],
        out_specs=probe,
        out_shape=jax.ShapeDtypeStruct((Q, 1), jnp.int32),
        interpret=interpret,
    )(col(jnp.mod(gids, cfg.n_sets)), col(gids), col(times), tags.T, lo, hi)
    return hits[:, 0]
