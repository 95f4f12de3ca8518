"""Studies: what one timed unit of work is, built from a traffic file.

A study is one ``Experiment`` over the cell's grid with the cell's
traffic.  Its inputs come from ``(run seed, study index)`` alone:

* the traffic file fixes the *multiset* of per-core profiles a study
  simulates (drawn once, from ``profile_draw_seed``, as the thesis's
  ``random_mixes`` draws its eight-core mixes), so every study of every
  seed does the same amount of work;
* the run seed and the study index shuffle those profiles into mixes and
  draw each mix's stream seed, so no two studies of a run share a stream.

Numpy only: a pool of worker processes imports this module to build the
studies while the parent brings up the chip.
"""

from __future__ import annotations

import numpy as np

import check
import gen


def study_rng(seed: int, k: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed % 2 ** 64, k]))


def profile_multiset(traffic: dict, cores: int) -> list[str]:
    """The per-core profile names every study of this traffic simulates."""
    mixes = gen.random_mixes(traffic["mixes_per_study"], cores,
                             seed=traffic["profile_draw_seed"])
    return [n for mix in mixes for n in mix]


def study_mixes(traffic: dict, cores: int, seed: int, k: int):
    """Study ``k`` of run ``seed``: its mixes (profile name lists) and
    each mix's stream seed."""
    rng = study_rng(seed, k)
    names = profile_multiset(traffic, cores)
    order = rng.permutation(len(names))
    names = [names[i] for i in order]
    mixes = [names[i * cores:(i + 1) * cores]
             for i in range(traffic["mixes_per_study"])]
    seeds = [int(s) for s in rng.integers(0, 2 ** 31,
                                          traffic["mixes_per_study"])]
    return mixes, seeds


def stream_geometry(cfg: dict) -> tuple[int, int]:
    """The banks in all (channels x ranks x banks per rank) and the rows
    per bank that the configuration's streams address."""
    g = cfg["geometry"]
    return g["n_channels"] * g["n_ranks"] * g["n_banks"], g["n_rows"]


def build_study(traffic: dict, cfg: dict, seed: int, k: int):
    """The host streams of study ``k``: one padded batch per mix."""
    mixes, seeds = study_mixes(traffic, cfg["cores"], seed, k)
    n_banks, n_rows = stream_geometry(cfg)
    return [gen.multicore_batch(m, cfg["requests_per_core"], s, n_banks,
                                n_rows)
            for m, s in zip(mixes, seeds)]


def grid_points(traffic: dict, cfg: dict) -> list[tuple[dict, dict]]:
    """Every requested grid point of a study, in the C order of the
    traffic file's axes: its axis labels, and the point as the reference
    takes it (``mechanism``, ``entries`` in all, ``duration_ms``)."""
    axes = traffic["axes"]
    pts = [{}]
    for name, values in axes.items():
        pts = [{**p, name: v} for p in pts for v in values]
    out = []
    for p in pts:
        q = {"mechanism": p["mechanism"]}
        if "capacity_per_core" in p:
            q["entries"] = int(p["capacity_per_core"]) * cfg["cores"]
        if "duration_ms" in p:
            q["duration_ms"] = float(p["duration_ms"])
        out.append((p, q))
    return out


def work_of(batches, n_points: int) -> int:
    """A study's work: the real requests of every mix, once per
    requested grid point (whatever the program deduplicates or pads)."""
    return n_points * sum(int(np.asarray(b.length).sum()) for b in batches)


def check_sample(traffic: dict, cfg: dict, seed: int, n_studies: int):
    """The (study, mix, grid point) triples a run compares with the
    configuration's reference (``check.load_reference``), drawn from the
    run seed: ``check_points`` of them (one per compared mechanism at
    least), cycling through the grid's mechanisms that the reference
    models and through the mixes in a shuffled order, so the sample
    spans every such mechanism and as many distinct mixes as it has
    points; the study and the value of every other axis are drawn per
    point.  Where the traffic sets ``"check_every_point"``, the sample is
    instead every grid point of a modelled mechanism once, in a shuffled
    order, each on the next mix of the shuffled mixes and in a drawn
    study, so a fault confined to any of the grid's lanes is seen."""
    rng = np.random.default_rng(np.random.SeedSequence([seed % 2 ** 64,
                                                        2 ** 32 + 7]))
    pts = grid_points(traffic, cfg)
    modelled = check.load_reference(cfg).MECHANISMS
    mechs = [m for m in traffic["axes"]["mechanism"] if m in modelled]
    n_mixes = traffic["mixes_per_study"]
    mix_order = rng.permutation(n_mixes)
    if traffic.get("check_every_point"):
        compared = [j for j, (p, _) in enumerate(pts)
                    if p["mechanism"] in modelled]
        return [(int(rng.integers(0, n_studies)), int(mix_order[i % n_mixes]),
                 int(j)) for i, j in enumerate(rng.permutation(compared))]
    out = []
    for i in range(max(traffic.get("check_points", 0), len(mechs))):
        cand = [j for j, (p, _) in enumerate(pts)
                if p["mechanism"] == mechs[i % len(mechs)]]
        out.append((int(rng.integers(0, n_studies)),
                    int(mix_order[i % n_mixes]),
                    cand[int(rng.integers(0, len(cand)))]))
    return out
