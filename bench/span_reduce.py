"""Device idle time of the measured window, put down to the program's
own host phases.

The window and the busy time are ``trace_reduce``'s: the span of the
``study/<k>`` host annotations, and on each used device the union of its
XLA module events inside it.  Every idle instant (window minus busy) is
given to the innermost ``repro/<phase>`` host span that covers it (the
spans ``repro.obs.span`` records), and each phase to one of four groups:

* ``stage``: ``repro/expand``, ``repro/stage``, ``repro/launch``;
* ``drain``: ``repro/drain`` (its own time), ``repro/d2h``, ``repro/rltl``;
* ``finalize``: ``repro/finalize``, ``repro/fan_out``, ``repro/assemble``;
* ``unattributed``: no ``repro/`` span, or only ``repro/run``.

Each group's seconds over the window, mean over devices, is its idle
share; the four add up to ``device_idle_share``.  A program without the
spans puts all idle time in ``unattributed`` and reports no spans.

A profile of ``Experiment.run()`` taken outside the harness has no
``study/<k>`` annotation; its window is then the span of its
``repro/run`` spans.  From the root of the repository:

    python3 bench/span_reduce.py <trace_dir> [<device id> ...]

prints the reduction as one JSON object.
"""

from __future__ import annotations

import json
import sys

from trace_reduce import (_DEVICE, _STUDY, MODULE_LINES, _clip, _events,
                          _merge, find_xplane)

GROUPS = ("stage", "drain", "finalize", "unattributed")
PHASE_GROUP = {
    "repro/expand": "stage", "repro/stage": "stage",
    "repro/launch": "stage",
    "repro/drain": "drain", "repro/d2h": "drain", "repro/rltl": "drain",
    "repro/finalize": "finalize", "repro/fan_out": "finalize",
    "repro/assemble": "finalize",
}
PREFIX = "repro/"
RUN = "repro/run"


def idle_intervals(window, busy):
    """The parts of ``window = (lo, hi)`` no interval of ``busy`` covers."""
    lo, hi = window
    clipped = [_clip(s, e, lo, hi) for s, e in busy]
    out, edge = [], lo
    for s, e in _merge((s, e) for s, e in clipped if e > s):
        if s > edge:
            out.append((edge, s))
        edge = max(edge, e)
    if hi > edge:
        out.append((edge, hi))
    return out


def attribute(window, busy, spans) -> dict:
    """Idle time of ``window`` per group: ``{group: ns}``.

    ``busy`` holds the device's busy intervals and ``spans`` the host's
    ``(start, end, name)`` spans, all on one clock.  An idle instant
    goes to the innermost span covering it (the latest to start; of
    two that start together, the shorter), and through it to its group.
    """
    spans = sorted((s, e, n) for s, e, n in spans if e > s)
    out = dict.fromkeys(GROUPS, 0)
    for g0, g1 in idle_intervals(window, busy):
        cuts = sorted({g0, g1} | {t for s, e, _ in spans for t in (s, e)
                                  if g0 < t < g1})
        for a, b in zip(cuts, cuts[1:]):
            cover = [(s, a - e, n) for s, e, n in spans if s <= a and b <= e]
            name = max(cover)[2] if cover else None
            out[PHASE_GROUP.get(name, "unattributed")] += b - a
    return out


def reduce_profile(pd, device_ids=None) -> dict:
    """Idle share per group over the ``study/<k>`` window (else the
    ``repro/run`` spans'), mean over the devices used, and the number of
    ``repro/`` spans inside the window."""
    spans, studies, devices = [], [], {}
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        if m:
            if device_ids is None or int(m.group(1)) in device_ids:
                devices[int(m.group(1))] = plane
        elif plane.name.startswith("/host:"):
            for _, ev in _events(plane):
                iv = (ev.start_ns, ev.start_ns + ev.duration_ns)
                if _STUDY.match(ev.name):
                    studies.append(iv)
                elif ev.name.startswith(PREFIX):
                    spans.append(iv + (ev.name,))
    studies = studies or [(s, e) for s, e, n in spans if n == RUN]
    if not studies or not devices:
        raise ValueError(f"trace has {len(studies)} study or run spans "
                         f"and {len(devices)} device planes")
    window = (min(s for s, _ in studies), max(e for _, e in studies))
    width = window[1] - window[0]
    shares = dict.fromkeys(GROUPS, 0.0)
    for plane in devices.values():
        busy = [(ev.start_ns, ev.start_ns + ev.duration_ns)
                for _, ev in _events(plane, MODULE_LINES)]
        for group, ns in attribute(window, busy, spans).items():
            shares[group] += ns / width / len(devices)
    return {"window_s": width * 1e-9, "idle_share": shares,
            "n_spans": sum(1 for s, e, _ in spans
                           if window[0] <= s and e <= window[1])}


def reduce_dir(trace_dir: str, device_ids=None) -> dict:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(find_xplane(trace_dir)),
                          device_ids)


if __name__ == "__main__":
    ids = [int(a) for a in sys.argv[2:]] or None
    print(json.dumps(reduce_dir(sys.argv[1], ids)))
