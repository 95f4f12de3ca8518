"""From a profiler trace of the measured window to per-layer numbers.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
``jax.profiler.ProfileData``:

* the window is the span from the first ``study/<k>`` host annotation's
  start to the last one's end, on the trace's own clock;
* a device plane (``/device:TPU:<id>``) is busy wherever one of its XLA
  module events runs; busy time is the union of those intervals inside
  the window, idle share is one minus busy over the window;
* each module's device time is the sum of its events inside the window,
  keyed by the jit's name with the trailing ``(<id>)`` stripped, so
  ``jit__run_grid(123)`` reads as ``jit__run_grid``.

The per-layer metric readers (``metrics/*.py``) take their numbers from
the dict ``reduce_profile`` returns.
"""

from __future__ import annotations

import glob
import os
import re

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_STUDY = re.compile(r"^study/\d+$")
_SUFFIX = re.compile(r"\(\d+\)$")
MODULE_LINES = ("XLA Modules",)
#: the marker a device plane carries where its trace buffers overflowed
DROPPED = "Trace Buffers Dropped"


def module_name(event_name: str) -> str:
    return _SUFFIX.sub("", event_name).strip()


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def reduce_dir(trace_dir: str, device_ids=None) -> dict:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(find_xplane(trace_dir)),
                          device_ids)


def _events(plane, line_names=None):
    for line in plane.lines:
        if line_names is None or line.name in line_names:
            for ev in line.events:
                yield line.name, ev


def reduce_profile(pd, device_ids=None) -> dict:
    """Busy time, module time and idle gaps per device, over the window
    the ``study/<k>`` host annotations span."""
    host_spans = []
    devices = {}
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        if m:
            if device_ids is None or int(m.group(1)) in device_ids:
                devices[int(m.group(1))] = plane
        elif plane.name.startswith("/host:"):
            for _, ev in _events(plane):
                host_spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                   ev.name))
    for plane in devices.values():
        for _, ev in _events(plane):
            if ev.name == DROPPED:
                raise ValueError(f"{plane.name}: the profiler dropped trace "
                                 f"buffers; the trace is incomplete")
    studies = [(s, e) for s, e, n in host_spans if _STUDY.match(n)]
    if not studies or not devices:
        raise ValueError(f"trace has {len(studies)} study spans and "
                         f"{len(devices)} device planes")
    lo = min(s for s, _ in studies)
    hi = max(e for _, e in studies)
    window_ns = hi - lo

    per_dev = {}
    for dev_id, plane in sorted(devices.items()):
        busy, modules = [], {}
        for _, ev in _events(plane, MODULE_LINES):
            s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
            if e <= s:
                continue
            busy.append((s, e))
            name = module_name(ev.name)
            modules[name] = modules.get(name, 0.0) + (e - s) * 1e-9
        merged = _merge(busy)
        per_dev[dev_id] = {
            "busy_s": sum(e - s for s, e in merged) * 1e-9,
            "modules": modules, "merged": merged}

    n = len(per_dev)
    busy_s = sum(d["busy_s"] for d in per_dev.values()) / n
    names = {k for d in per_dev.values() for k in d["modules"]}
    module_s = {k: sum(d["modules"].get(k, 0.0) for d in per_dev.values())
                / n for k in names}
    first = per_dev[min(per_dev)]
    gaps = []
    edge = lo
    for s, e in first["merged"] + [[hi, hi]]:
        if s > edge:
            gaps.append((edge, s))
        edge = max(edge, e)
    inner = [(s, e, nm) for s, e, nm in host_spans if not _STUDY.match(nm)]

    def what(g0, g1):
        mid = (g0 + g1) / 2
        cover = [(e - s, nm) for s, e, nm in inner if s <= mid <= e]
        if cover:
            return min(cover)[1]
        cover = [nm for s, e, nm in host_spans if s <= mid <= e]
        return cover[0] if cover else "between studies"

    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": busy_s,
        "busy_by_device": {k: d["busy_s"] for k, d in per_dev.items()},
        "module_s": module_s,
        "module_s_by_device": {k: d["modules"] for k, d in per_dev.items()},
        "breakdown": {
            "device_ops": sorted(([k, v] for k, v in module_s.items()),
                                 key=lambda kv: -kv[1])[:10],
            "idle_gaps": [[what(g0, g1), (g1 - g0) * 1e-9]
                          for g0, g1 in gaps[:10]],
        },
    }

