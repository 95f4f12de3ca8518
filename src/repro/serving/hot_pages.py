"""ChargeCache for serving: hot KV-page tracking (DESIGN.md §2.2).

The thesis's HCRAC is reused verbatim as a *hot-page table* over KV-cache
pages in HBM: a page that was just streamed through the sense amps /
row buffers is cheap to re-open within the caching window, so the batch
scheduler prefers to co-schedule requests whose pages are hot.  The table
is the same set-associative, IIC/EC-invalidated structure as the memory-
controller version (repro.core.hcrac); batched probes go through the
Pallas kernel (repro.kernels.hcrac).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hcrac as hcl
from repro.core.timing import ms_to_cycles

#: ``touch`` inserts page by page: one compiled insert per table shape
#: instead of an eager dispatch of every op of every insert
_insert = jax.jit(hcl.insert, static_argnums=0)


@dataclasses.dataclass
class HotPageConfig:
    n_entries: int = 1024
    n_ways: int = 2
    caching_ms: float = 1.0
    page_tokens: int = 2048          # tokens of KV per HBM page granule
    #: page id -> DRAM (bank, row) mapping for the closed-loop simulator
    n_banks: int = 16
    n_rows: int = 65536
    #: idealised per-entry expiry timer instead of the IIC/EC sweep —
    #: makes aliveness slot-phase-independent, which the host-vs-traced
    #: serving parity tests rely on (repro.serving.loop)
    exact_expiry: bool = False

    def hcrac(self) -> hcl.HCRACConfig:
        return hcl.HCRACConfig(
            n_entries=self.n_entries, n_ways=self.n_ways,
            caching_cycles=ms_to_cycles(self.caching_ms),
            exact_expiry=self.exact_expiry)


class HotPageTracker:
    """Stateful wrapper used by the batch scheduler."""

    def __init__(self, cfg: HotPageConfig):
        self.cfg = cfg
        self.hc_cfg = cfg.hcrac()
        self.state = hcl.init(self.hc_cfg)

    def probe(self, page_ids: np.ndarray, now_cycles: int) -> np.ndarray:
        """Batched read-only lookup (Pallas kernel path)."""
        if len(page_ids) == 0:
            return np.zeros(0, bool)
        from repro.kernels.hcrac import ops as hc_ops
        t = jnp.full((len(page_ids),), np.int32(now_cycles), jnp.int32)
        hits = hc_ops.hcrac_lookup(self.hc_cfg, self.state,
                                   jnp.asarray(page_ids, jnp.int32), t)
        return np.asarray(hits)

    def touch(self, page_ids: np.ndarray, now_cycles: int) -> None:
        """Record accesses (insert/refresh entries)."""
        st = self.state
        for g in np.asarray(page_ids, np.int32):
            st = _insert(self.hc_cfg, st, jnp.int32(g),
                         jnp.int32(now_cycles))
        self.state = st

    def page_to_dram(self, page_ids: np.ndarray):
        """Hash page ids onto (bank, row) for the closed-loop DRAM sim.

        Full-avalanche mixing (splitmix64 finalizer): a plain
        multiplicative hash preserved the page-id stride structure, which
        aliased every row of a bank into HCRAC set 0 and collapsed the hit
        rate to ~5 % despite 99 % RLTL — the memory-system analogue of a
        cache index pathology (cf. pseudo-random interleaving, Rau ISCA'91,
        thesis ref [75])."""
        h = np.asarray(page_ids, np.uint64)
        h = (h + np.uint64(0x9E3779B97F4A7C15))
        h ^= h >> np.uint64(30)
        h *= np.uint64(0xBF58476D1CE4E5B9)
        h ^= h >> np.uint64(27)
        h *= np.uint64(0x94D049BB133111EB)
        h ^= h >> np.uint64(31)
        bank = (h % np.uint64(self.cfg.n_banks)).astype(np.int32)
        row = ((h >> np.uint64(8)) % np.uint64(self.cfg.n_rows)).astype(
            np.int32)
        return bank, row
