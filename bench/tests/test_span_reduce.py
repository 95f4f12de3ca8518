"""CPU self-check of what the harness reads of the program's own
observability (run with ``python -m pytest bench/tests``):

* ``span_reduce`` puts each idle instant of the window down to the
  innermost ``repro/`` span, and its shares add up to the idle share;
* the set-up readers sum the program's jit-cache counters, and are
  silent for a program without them or where the window compiled.
"""

from __future__ import annotations

import gzip
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

import harness  # noqa: E402
import program_counters  # noqa: E402
import span_reduce  # noqa: E402
import trace_reduce  # noqa: E402

#: a profile of one tiny study recorded on one TPU v5e, before the
#: program had spans
FIXTURE = os.path.join(BENCH, "tests", "fixtures",
                       "trace_small.xplane.pb.gz")
SETUP_READERS = ("setup_compile_s", "setup_executables")


class Ev:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur


class Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class Profile:
    def __init__(self, planes):
        self.planes = planes


def test_idle_attribution_takes_the_innermost_span():
    busy = [(10, 20), (40, 60), (-5, 2), (98, 130)]
    spans = [(0, 100, "repro/run"), (0, 15, "repro/expand"),
             (15, 30, "repro/stage"), (30, 40, "repro/launch"),
             (60, 90, "repro/drain"), (60, 70, "repro/d2h"),
             (70, 80, "repro/finalize"), (90, 95, "repro/assemble"),
             (90, 93, "repro/fan_out"), (-50, -10, "repro/launch")]
    got = span_reduce.attribute((0, 100), busy, spans)
    # idle: [2, 10] expand, [20, 30] stage, [30, 40] launch, [60, 70]
    # d2h, [70, 80] finalize, [80, 90] drain itself, [90, 93] fan_out
    # (starts with assemble, shorter), [93, 95] assemble, [95, 98] run
    assert got == {"stage": 8 + 10 + 10, "drain": 10 + 10,
                   "finalize": 10 + 3 + 2, "unattributed": 3}
    idle = sum(e - s for s, e in span_reduce.idle_intervals((0, 100), busy))
    assert sum(got.values()) == idle == 100 - (10 + 20 + 2 + 2)


def test_idle_without_spans_is_unattributed():
    got = span_reduce.attribute((0, 50), [(5, 10)], [(0, 50, "study/1")])
    assert got == {"stage": 0, "drain": 0, "finalize": 0,
                   "unattributed": 45}


def test_span_reduction_on_recorded_chip_trace():
    """The fixture predates the program's spans: all of its idle time is
    unattributed, and equals what ``device_idle_share`` reads."""
    from jax.profiler import ProfileData
    with gzip.open(FIXTURE) as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    red = trace_reduce.reduce_profile(pd, [0])
    spans = span_reduce.reduce_profile(pd, [0])
    assert spans["n_spans"] == 0
    assert spans["window_s"] == pytest.approx(red["window_s"], abs=1e-12)
    idle = harness.load_reader("device_idle_share")({"trace": red})
    assert spans["idle_share"]["unattributed"] == pytest.approx(idle,
                                                                abs=1e-9)
    for g in ("stage", "drain", "finalize"):
        assert spans["idle_share"][g] == 0


def _profile(host_events):
    dev = Plane("/device:TPU:0", [Line("XLA Modules", [
        Ev("jit__run_grid(1)", 100, 400),
        Ev("jit__rltl_hist_device(2)", 600, 100)])])
    return Profile([dev, Plane("/host:CPU", [Line("main/1", host_events)])])


SPANS = [Ev("repro/run", 0, 990), Ev("repro/expand", 0, 50),
         Ev("repro/stage", 50, 40), Ev("repro/launch", 90, 20),
         Ev("repro/drain", 110, 800), Ev("repro/rltl", 550, 200),
         Ev("repro/finalize", 750, 100), Ev("repro/assemble", 910, 60)]


def test_shares_sum_to_the_idle_share_with_spans():
    """A profile with ``repro/`` spans made by hand: the four shares add
    up to ``device_idle_share``."""
    pd = _profile([Ev("study/1", 0, 1000)] + SPANS)
    red = trace_reduce.reduce_profile(pd, [0])
    spans = span_reduce.reduce_profile(pd, [0])
    # idle [0, 100], [500, 600], [700, 1000]; drain's own time is
    # [500, 550] and [850, 910], rltl [550, 600] and [700, 750]
    assert spans["idle_share"] == pytest.approx(
        {"stage": 0.1, "drain": 0.21, "finalize": 0.1 + 0.06,
         "unattributed": 0.03}, abs=1e-12)
    assert sum(spans["idle_share"].values()) == pytest.approx(
        harness.load_reader("device_idle_share")({"trace": red}), abs=1e-12)
    assert spans["n_spans"] == 8


def test_window_without_study_spans_is_the_run():
    """A profile of ``Experiment.run()`` alone: the window is
    ``repro/run``'s, [0, 990]; only ``repro/run`` covers [970, 990]."""
    spans = span_reduce.reduce_profile(_profile(SPANS), [0])
    assert spans["window_s"] == pytest.approx(990e-9)
    assert spans["idle_share"]["unattributed"] == pytest.approx(20 / 990)
    assert sum(spans["idle_share"].values()) == pytest.approx(490 / 990)


def test_setup_readers_sum_the_program_counters(monkeypatch):
    jit = {"trace": {"_run_grid": (1, 2.0), "add": (3, 0.5)},
           "lower": {"jit(_run_grid)": (1, 1.0)},
           "compile": {"jit(_run_grid)": (1, 4.0), "jit(add)": (2, 0.25)},
           "cache_load": {"": (3, 3.5)}}
    monkeypatch.setattr(program_counters, "jit_cache", lambda: jit)
    ctx = {"window_compiles": 0}
    assert harness.load_reader("setup_compile_s")(ctx) == 7.75
    assert harness.load_reader("setup_executables")(ctx) == 3


def test_setup_readers_read_this_process():
    """The harness's process holds the program's live counters: a jit
    compiled here shows in them."""
    import jax
    import numpy as np

    before = harness.load_reader("setup_executables")({"window_compiles": 0})
    jax.jit(lambda x: x * 3 + 1)(np.arange(7.0)).block_until_ready()
    after = harness.load_reader("setup_executables")({"window_compiles": 0})
    assert after == before + 1
    assert harness.load_reader("setup_compile_s")({"window_compiles": 0}) > 0


@pytest.mark.parametrize("metric", SETUP_READERS)
def test_setup_reader_is_silent_where_the_window_compiled(metric):
    assert harness.load_reader(metric)({"window_compiles": 1}) is None


@pytest.mark.parametrize("metric", SETUP_READERS)
def test_setup_reader_is_silent_without_the_counters(metric, monkeypatch):
    """A program without ``repro.obs`` (an import of it fails)."""
    import repro
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert program_counters.jit_cache() is None
    assert harness.load_reader(metric)({"window_compiles": 0}) is None
