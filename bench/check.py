"""The comparison that decides ``correct``.

Every answer a study produces is one grid point's statistics, which the
simulator computes in exact integers.  A run re-computes a seeded sample
of the points its window produced — one per mechanism of the grid that
the reference models, at least — with the plain reference
(``reference.py``) on the same host streams, and counts the values that
differ.  The comparison is exact, so its limit
is 0: one differing counter, cycle or histogram bucket makes the run
incorrect.
"""

from __future__ import annotations

import numpy as np

import reference

#: the one number compared, and its limit (an exact comparison)
LIMIT = 0


def _labels(p: dict, mix: int) -> dict:
    out = {"trace": mix}
    for name, v in p.items():
        out["capacity" if name == "capacity_per_core" else name] = v
    return out


def differing(got: dict, ref: dict, traffic: dict, n_banks: int) -> list:
    """The names of the values on which a program point and its reference
    differ (each element of an array counts once)."""
    bad = []
    for k in reference.STAT_KEYS + ("total_cycles",):
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


def compare(results, inputs, sample, points, cfg, traffic) -> dict:
    """Check the sampled points of a window.  ``results[i]`` is study
    ``i + 1``'s ``Results``; ``inputs[i + 1]`` its host streams."""
    n_banks = (cfg["geometry"]["n_channels"] * cfg["geometry"]["n_ranks"]
               * cfg["geometry"]["n_banks"])
    total, replayed, notes = 0, 0, []
    for s_idx, mix, p_idx in sample:
        p, q = points[p_idx]
        batch = inputs[s_idx + 1][mix]
        ref = reference.run(batch, cfg, q, rltl=bool(traffic["rltl"]))
        got = results[s_idx].point(**_labels(p, mix))
        bad = differing(got, ref, traffic, n_banks)
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
