"""A stand-in reference that a test configuration names by its
``"reference"`` key: the plain reference with its latency sum one too
high, modelling ``base`` alone, and recording the points it ran."""

import reference

STAT_KEYS = reference.STAT_KEYS
MECHANISMS = ("base",)
RAN = []


def run(batch, cfg, point, rltl=False):
    RAN.append(point["mechanism"])
    out = reference.run(batch, cfg, point, rltl=rltl)
    out["lat_sum"] += 1
    return out
