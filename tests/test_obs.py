"""The program's own observability (``repro.obs``), on the CPU at tiny
sizes.

* A traced ``Experiment.run()`` records the ``repro/*`` span tree on the
  profiler's host plane, with the nesting the runner documents: one
  ``repro/run`` holding expand, stage, a launch and a drain per chunk and
  assemble; each drain holding the engine's d2h, rltl and one finalize
  per drained row, and one fan_out per row.
* ``reduce=`` runs fan out without a per-point finalize; synthetic runs
  launch and drain like trace-driven ones.
* ``jit_cache()`` counts a compile of the engine jit once, and an
  identical second run adds no compile.
* ``repro/obs.py`` is the only program file that touches the profiler or
  ``jax.monitoring``.
"""

from __future__ import annotations

import collections
import glob
import os

import jax
import pytest
from jax.profiler import ProfileData

from repro import obs
from repro.core import SimConfig
from repro.core.traces import WorkloadSpec, multicore_batch
from repro.experiment import Experiment

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src", "repro")
#: three mechanisms that never dedup, so chunk_size=2 makes two chunks
MECHS = ["base", "chargecache", "lldram"]

Span = collections.namedtuple("Span", "name start end args")


def _traces(n_req: int = 64):
    return {"a": multicore_batch(["stream_copy_like", "tpcc64_like"],
                                 n_req=n_req, seed=0),
            "b": multicore_batch(["stream_triad_like", "hmmer_like"],
                                 n_req=n_req, seed=1)}


def recorded(exp: Experiment, log_dir) -> list[Span]:
    """Run ``exp`` under the profiler (Python tracer off) and return the
    ``repro/`` spans of the host plane, names without the prefix."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(log_dir), profiler_options=opts):
        exp.run()
    path, = glob.glob(os.path.join(str(log_dir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro/"):
                    spans.append(Span(ev.name[len("repro/"):], ev.start_ns,
                                      ev.start_ns + ev.duration_ns,
                                      {k: v for k, v in ev.stats}))
    return sorted(spans, key=lambda s: (s.start, -s.end))


def parent(span: Span, spans: list[Span]) -> str | None:
    """Name of the innermost other span that encloses ``span``."""
    outer = [s for s in spans if s is not span
             and s.start <= span.start and span.end <= s.end]
    return min(outer, key=lambda s: s.end - s.start).name if outer else None


def counts(spans):
    return collections.Counter(s.name for s in spans)


def test_trace_mode_records_the_span_tree(tmp_path):
    exp = Experiment(traces=_traces(), axes={"mechanism": MECHS},
                     rltl=True, chunk_size=2)
    spans = recorded(exp, tmp_path)
    assert counts(spans) == {"run": 1, "expand": 1, "stage": 1,
                             "launch": 2, "drain": 2, "d2h": 2, "rltl": 2,
                             "finalize": 4, "fan_out": 4, "assemble": 1}
    tree = {s.name: set() for s in spans}
    for s in spans:
        tree[s.name].add(parent(s, spans))
    assert tree["run"] == {None}
    for name in ("expand", "stage", "launch", "drain", "assemble"):
        assert tree[name] == {"run"}, name
    for name in ("d2h", "rltl", "finalize", "fan_out"):
        assert tree[name] == {"drain"}, name
    for name in ("launch", "drain"):
        assert [int(s.args["chunk"]) for s in spans if s.name == name] \
            == [0, 1]
    order = [s.name for s in spans if parent(s, spans) == "run"]
    assert order == ["expand", "stage", "launch", "launch", "drain",
                     "drain", "assemble"]


def test_reduce_mode_fans_out_without_finalize(tmp_path):
    exp = Experiment(traces=_traces(), axes={"mechanism": MECHS},
                     reduce=("row_hit_rate",), chunk_size=2)
    spans = recorded(exp, tmp_path)
    c = counts(spans)
    assert c["fan_out"] == 4 and c["d2h"] == 2
    assert "finalize" not in c and "rltl" not in c
    assert {parent(s, spans) for s in spans if s.name == "fan_out"} \
        == {"drain"}


def test_synth_mode_launches_and_drains(tmp_path):
    base = SimConfig(workload=WorkloadSpec(names=("stream_copy_like",),
                                           n_req=64, seed=0))
    exp = Experiment(traces=None, base=base, axes={"mechanism": MECHS},
                     chunk_size=2)
    spans = recorded(exp, tmp_path)
    c = counts(spans)
    assert c["launch"] == 2 and c["drain"] == 2 and c["run"] == 1
    assert {parent(s, spans) for s in spans
            if s.name in ("launch", "drain")} == {"run"}


def _engine_compiles() -> int:
    return sum(n for fun, (n, _) in obs.jit_cache().get("compile", {}).items()
               if "_run_grid" in fun)


def test_jit_cache_counts_one_compile_per_shape():
    # a stream length no other test uses, so the first run compiles here
    exp = Experiment(traces=_traces(n_req=77), axes={"mechanism": MECHS})
    before = _engine_compiles()
    exp.run()
    assert _engine_compiles() == before + 1
    snap = obs.jit_cache()
    assert snap["trace"] and snap["lower"]
    assert all(n >= 1 and s >= 0 for phase in snap.values()
               for n, s in phase.values())
    exp.run()
    assert obs.jit_cache()["compile"] == snap["compile"]


@pytest.mark.parametrize("needle", ["jax.profiler", "jax.monitoring"])
def test_obs_is_the_only_tracing_code(needle):
    users = sorted(os.path.relpath(p, SRC)
                   for p in glob.glob(os.path.join(SRC, "**", "*.py"),
                                      recursive=True)
                   if needle in open(p).read())
    assert users == ["obs.py"]
