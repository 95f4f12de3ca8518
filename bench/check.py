"""The comparison that decides ``correct``.

Every answer a study produces is one grid point's statistics, which the
simulator computes in exact integers.  A run re-computes a seeded sample
of the points its window produced — one per mechanism of the grid that
the reference models, at least, or every such point where the traffic
sets ``"check_every_point"`` — with the plain reference on the same
host streams, and counts the values that differ.

The reference is the module ``bench/<name>.py`` that the configuration's
``"reference"`` key names (``reference`` where it names none), so a
memory system with rules of its own brings its own reference as a new
file.  A full-stats cell compares every stat counter, the cycle counts,
the per-bank counts and the RLTL buckets.  A cell whose traffic streams
``"reduce"`` metrics compares each of them with the same metric worked
out from the reference's counters (``formulas.py``).  The comparison is
exact, so its limit is 0: one differing number makes the run incorrect.
"""

from __future__ import annotations

import functools
import importlib.util
import os

import numpy as np

import formulas

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
#: the one number compared, and its limit (an exact comparison)
LIMIT = 0
#: the reference of a configuration that names none
DEFAULT_REFERENCE = "reference"


@functools.lru_cache(maxsize=None)
def _reference_module(name: str):
    parts = name.split(".")
    if not all(p.isidentifier() for p in parts):
        raise ValueError(f"reference {name!r} is not a module name")
    path = os.path.join(BENCH_DIR, *parts) + ".py"
    spec = importlib.util.spec_from_file_location(
        "bench_reference_" + "_".join(parts), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reference(cfg: dict):
    """The configuration's plain reference: the module under ``bench/``
    its ``"reference"`` key names (dotted for a subdirectory), with
    ``run``, ``STAT_KEYS`` and ``MECHANISMS``."""
    return _reference_module(cfg.get("reference", DEFAULT_REFERENCE))


def _labels(p: dict, mix: int) -> dict:
    out = {"trace": mix}
    for name, v in p.items():
        out["capacity" if name == "capacity_per_core" else name] = v
    return out


def differing(got: dict, ref: dict, traffic: dict, n_banks: int,
              stat_keys) -> list:
    """The names of the values on which a full-stats program point and
    its reference differ (each element of an array counts once)."""
    bad = []
    for k in tuple(stat_keys) + ("total_cycles",):
        if int(np.asarray(got[k])) != ref[k]:
            bad.append(k)
    arrays = [("core_end", None), ("bank_acts", n_banks),
              ("bank_act_ras_sum", n_banks)]
    if traffic["rltl"]:
        arrays.append(("rltl_hist", None))
        if got["rltl_total"] is None or int(got["rltl_total"]) \
                != ref["rltl_total"]:
            bad.append("rltl_total")
    for k, n in arrays:
        g = got[k]
        g = [] if g is None else np.asarray(g).ravel().tolist()
        g = g[:n] if n is not None else g
        r = list(ref[k])
        if len(g) != len(r):
            bad.append(k)
            continue
        bad.extend(f"{k}[{i}]" for i, (a, b) in enumerate(zip(g, r))
                   if a != b)
    return bad


def differing_reduced(got: dict, ref: dict, metrics) -> list:
    """The streamed metrics of a point that differ from the same metric
    of its reference counters, bit for bit."""
    return [m for m in metrics if got.get(m) != formulas.value(m, ref)]


def compare(results, inputs, sample, points, cfg, traffic) -> dict:
    """Check the sampled points of a window.  ``results[i]`` is study
    ``i + 1``'s ``Results``; ``inputs[i + 1]`` its host streams."""
    ref_mod = load_reference(cfg)
    reduced = traffic.get("reduce")
    n_banks = (cfg["geometry"]["n_channels"] * cfg["geometry"]["n_ranks"]
               * cfg["geometry"]["n_banks"])
    total, replayed, notes = 0, 0, []
    for s_idx, mix, p_idx in sample:
        p, q = points[p_idx]
        batch = inputs[s_idx + 1][mix]
        ref = ref_mod.run(batch, cfg, q, rltl=bool(traffic["rltl"]))
        got = results[s_idx].point(**_labels(p, mix))
        bad = (differing_reduced(got, ref, reduced) if reduced else
               differing(got, ref, traffic, n_banks, ref_mod.STAT_KEYS))
        total += len(bad)
        replayed += int(np.asarray(batch.length).sum())
        tag = "/".join(str(v) for v in p.values())
        notes.append(f"{tag}@study{s_idx + 1}.mix{mix}:"
                     f"{'ok' if not bad else ','.join(bad[:6])}")
    checks = {"mismatches": {"value": total, "limit": LIMIT}}
    lines = [f"checked {len(sample)} points: " + " ".join(notes),
             f"check mismatches {total} limit {LIMIT}"]
    return {"correct": total <= LIMIT, "checks": checks, "lines": lines,
            "requests": replayed}
