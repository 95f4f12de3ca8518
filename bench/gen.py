"""The benchmark's own host trace generator (the traffic yardstick).

A copy of the statistical trace model the simulator's studies are driven
by: the 22 ``*_like`` profiles (SPEC CPU2006 / TPC / STREAM stand-ins),
one core's stream (``generate_trace``), the padded multi-core batch with
its closed-row queue-hit lookahead (``multicore_batch``) and the thesis's
random eight-core mixes (``random_mixes``).  The bank count and the rows
per bank are the configuration's (``study.build_study`` passes them), so
a memory system of another geometry gets streams that span all of its
banks.  It is kept here, apart from the program, so a change to the
program's generator cannot move the benchmark's traffic;
``bench/tests/test_bench.py`` checks that the copy still reproduces the
program's generator bitwise.

Numpy only: worker processes import this module to build studies in
parallel, and must never load JAX.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import numpy as np

@dataclasses.dataclass(frozen=True)
class Profile:
    name: str
    mean_gap: float
    p_rowhit: float
    hot_rows: int
    p_hot: float
    stack_geo: float
    p_seq: float
    p_dep: float
    p_write: float = 0.3
    traffic: float = 1.0
    n_hot_banks: int = 2
    stack_zipf: float = 1.25


_RAW = [
    Profile("mcf_like", 28, 0.20, 16384, 0.90, 0.3, 0.00, 0.45,
            n_hot_banks=3, stack_zipf=1.08),
    Profile("lbm_like", 28, 0.62, 2048, 0.70, 0.3, 0.30, 0.10, 0.45,
            n_hot_banks=2, stack_zipf=1.3),
    Profile("milc_like", 36, 0.45, 8192, 0.88, 0.3, 0.10, 0.20,
            n_hot_banks=2, stack_zipf=1.25),
    Profile("libquantum_like", 30, 0.72, 1024, 0.75, 0.3, 0.40, 0.05,
            n_hot_banks=2, stack_zipf=1.35),
    Profile("omnetpp_like", 40, 0.15, 16384, 0.92, 0.3, 0.00, 0.60,
            n_hot_banks=3, stack_zipf=1.1),
    Profile("soplex_like", 36, 0.35, 8192, 0.90, 0.3, 0.05, 0.30,
            n_hot_banks=2, stack_zipf=1.2),
    Profile("GemsFDTD_like", 34, 0.55, 4096, 0.85, 0.3, 0.20, 0.15,
            n_hot_banks=2, stack_zipf=1.3),
    Profile("leslie3d_like", 38, 0.60, 4096, 0.85, 0.3, 0.25, 0.15,
            n_hot_banks=2, stack_zipf=1.3),
    Profile("sphinx3_like", 45, 0.40, 8192, 0.88, 0.3, 0.05, 0.25,
            n_hot_banks=2, stack_zipf=1.25),
    Profile("bwaves_like", 36, 0.60, 2048, 0.80, 0.3, 0.30, 0.10,
            n_hot_banks=2, stack_zipf=1.3),
    Profile("astar_like", 90, 0.25, 8192, 0.88, 0.3, 0.00, 0.50,
            n_hot_banks=2, stack_zipf=1.2),
    Profile("gcc_like", 110, 0.35, 8192, 0.88, 0.3, 0.05, 0.35,
            n_hot_banks=2, stack_zipf=1.25),
    Profile("zeusmp_like", 80, 0.55, 4096, 0.85, 0.3, 0.20, 0.15,
            n_hot_banks=2, stack_zipf=1.3),
    Profile("cactusADM_like", 95, 0.50, 4096, 0.85, 0.3, 0.15, 0.20,
            n_hot_banks=2, stack_zipf=1.3),
    Profile("wrf_like", 100, 0.55, 4096, 0.85, 0.3, 0.20, 0.15,
            n_hot_banks=2, stack_zipf=1.3),
    Profile("dealII_like", 140, 0.40, 8192, 0.88, 0.3, 0.05, 0.30,
            n_hot_banks=2, stack_zipf=1.25),
    Profile("gobmk_like", 220, 0.30, 8192, 0.85, 0.3, 0.02, 0.40,
            n_hot_banks=2, stack_zipf=1.2),
    Profile("hmmer_like", 4000, 0.30, 64, 0.50, 0.3, 0.00, 0.30,
            traffic=0.01, n_hot_banks=2, stack_zipf=1.4),
    Profile("tpcc64_like", 48, 0.25, 16384, 0.90, 0.3, 0.00, 0.50,
            n_hot_banks=3, stack_zipf=1.12),
    Profile("tpch2_like", 42, 0.45, 8192, 0.88, 0.3, 0.10, 0.30,
            n_hot_banks=2, stack_zipf=1.2),
    Profile("stream_copy_like", 26, 0.75, 1024, 0.70, 0.3, 0.55,
            0.05, 0.5, n_hot_banks=2, stack_zipf=1.35),
    Profile("stream_triad_like", 26, 0.72, 1024, 0.70, 0.3, 0.50,
            0.05, 0.4, n_hot_banks=2, stack_zipf=1.35),
]

#: the calibrated table: tighter issue gaps, more address dependencies
WORKLOADS = [dataclasses.replace(w, mean_gap=max(6, w.mean_gap * 0.55),
                                 p_dep=min(0.9, w.p_dep + 0.25))
             for w in _RAW]
BY_NAME = {w.name: w for w in WORKLOADS}


class Batch(NamedTuple):
    """A padded multi-core stream, field for field the program's
    ``TraceBatch``: ``[C, L]`` arrays and the per-core ``length [C]``."""
    gap: np.ndarray
    bank: np.ndarray
    row: np.ndarray
    is_write: np.ndarray
    dep: np.ndarray
    next_same: np.ndarray
    length: np.ndarray


def generate_trace(profile: Profile, n_req: int, seed: int, n_banks: int,
                   n_rows: int, row_base: int = 0,
                   row_span: int | None = None):
    """One core's stream over ``n_banks`` banks in all and ``n_rows``
    rows per bank: ``(gap, bank, row, is_write, dep)`` arrays."""
    n_req = max(8, int(n_req * profile.traffic))
    rng = np.random.default_rng(seed)
    span = row_span or n_rows
    nb = n_banks

    gap = rng.geometric(1.0 / max(profile.mean_gap, 1.001),
                        n_req).astype(np.int32)
    is_write = rng.random(n_req) < profile.p_write
    dep = rng.random(n_req) < profile.p_dep

    bank = np.zeros(n_req, np.int32)
    row = np.zeros(n_req, np.int32)
    hot_banks = rng.choice(nb, size=min(profile.n_hot_banks, nb),
                           replace=False)
    stack_b = hot_banks[rng.integers(0, len(hot_banks),
                                     profile.hot_rows)].astype(np.int32)
    stack_r = (row_base + rng.integers(0, span, profile.hot_rows)
               ).astype(np.int32)
    cur_b, cur_r = int(stack_b[0]), int(stack_r[0])

    u = rng.random((n_req, 3))
    if profile.stack_zipf > 0:
        stack_pick = np.minimum(rng.zipf(profile.stack_zipf, n_req) - 1,
                                profile.hot_rows - 1)
    else:
        stack_pick = np.minimum(rng.geometric(profile.stack_geo, n_req) - 1,
                                profile.hot_rows - 1)
    rand_b = hot_banks[rng.integers(0, len(hot_banks), n_req)]
    rand_r = row_base + rng.integers(0, span, n_req)

    for i in range(n_req):
        if u[i, 0] < profile.p_rowhit:
            pass  # row-buffer hit run: same (bank, row)
        elif u[i, 1] < profile.p_seq:
            cur_r = row_base + (cur_r - row_base + 1) % span  # streaming
        elif u[i, 2] < profile.p_hot:
            j = stack_pick[i]
            cur_b, cur_r = int(stack_b[j]), int(stack_r[j])
            stack_b[1:j + 1] = stack_b[:j]  # move to front
            stack_r[1:j + 1] = stack_r[:j]
            stack_b[0], stack_r[0] = cur_b, cur_r
        else:
            cur_b, cur_r = int(rand_b[i]), int(rand_r[i])
            stack_b[1:] = stack_b[:-1]
            stack_r[1:] = stack_r[:-1]
            stack_b[0], stack_r[0] = cur_b, cur_r
        bank[i] = cur_b
        row[i] = cur_r
    return gap, bank, row, is_write.astype(bool), dep.astype(bool)


def _next_same(bank, row) -> np.ndarray:
    """True where this core's next request to the same bank hits the
    same row (the closed-row policy's queue-hit lookahead)."""
    n = len(bank)
    out = np.zeros(n, bool)
    last: dict[int, int] = {}
    for i in range(n - 1, -1, -1):
        b = int(bank[i])
        j = last.get(b)
        out[i] = j is not None and row[j] == row[i]
        last[b] = i
    return out


def multicore_batch(names, n_req: int, seed: int, n_banks: int,
                    n_rows: int) -> Batch:
    """A multiprogrammed mix over ``n_banks`` banks in all and ``n_rows``
    rows per bank: core ``i`` draws from seed ``seed * 1000 + i`` inside
    its own slice of the row space."""
    span = n_rows // max(len(names), 1)
    cores = [generate_trace(BY_NAME[n], n_req, seed * 1000 + i, n_banks,
                            n_rows, row_base=i * span, row_span=span)
             for i, n in enumerate(names)]
    lengths = np.array([len(c[0]) for c in cores], np.int32)
    c, L = len(cores), int(lengths.max())

    def pad(k, dtype):
        out = np.zeros((c, L), dtype)
        for i, core in enumerate(cores):
            out[i, :len(core[k])] = core[k]
        return out

    return Batch(gap=pad(0, np.int32), bank=pad(1, np.int32),
                 row=pad(2, np.int32), is_write=pad(3, bool),
                 dep=pad(4, bool),
                 next_same=_pad_rows([_next_same(cr[1], cr[2])
                                     for cr in cores], L),
                 length=lengths)


def _pad_rows(rows, L: int) -> np.ndarray:
    out = np.zeros((len(rows), L), bool)
    for i, r in enumerate(rows):
        out[i, :len(r)] = r
    return out


def random_mixes(n_mixes: int, n_cores: int, seed: int = 42):
    """``n_mixes`` multiprogrammed mixes of ``n_cores`` profile names,
    drawn uniformly with replacement from the 22 profiles."""
    rng = np.random.default_rng(seed)
    names = [w.name for w in WORKLOADS]
    return [[names[j] for j in rng.integers(0, len(names), n_cores)]
            for _ in range(n_mixes)]
