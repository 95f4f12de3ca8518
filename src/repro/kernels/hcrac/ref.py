"""Pure-jnp oracle for the batched HCRAC lookup kernel."""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.hcrac import HCRACConfig, HCRACState, NO_TAG, _alive, _row


def hcrac_lookup_ref(cfg: HCRACConfig, st: HCRACState, gids, times):
    """Vector lookup: gids/times [Q] -> hits [Q] (no LRU side effects,
    matching the serving scheduler's read-only probe)."""
    set_idx = jnp.mod(gids, cfg.n_sets).astype(jnp.int32)     # [Q]
    tags = _row(cfg, st.tags, set_idx).T                       # [Q, W]
    itime = _row(cfg, st.itime, set_idx).T
    alive = _alive(cfg, set_idx[:, None], itime, times[:, None])
    match = (tags != NO_TAG) & alive & (tags == gids[:, None])
    return jnp.any(match, axis=-1)
