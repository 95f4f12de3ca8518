"""The benchmark harness: one run of one cell.

``run_cell`` brings up the chip, builds the cell's studies from the run
seed, warms up the cell's own shapes with a study no timed study shares,
runs studies back to back through ``Experiment.run()`` for the measured
window, and checks a seeded sample of what the window produced against
the configuration's plain reference (``check.py``).  Everything a cell is
made of is found by name: its configuration (``configs/<config>.json``,
whose ``geometry`` also shapes the streams and whose optional
``"reference"`` names its reference module), its traffic
(``traffic/<traffic>.json``, with an optional ``"reduce"`` list of
streamed metrics) and each per-layer metric (``metrics/<metric>.py``),
all listed in ``BENCHMARK.json``.

JAX is imported only inside ``run_cell``: the study-building worker
processes import this package too and must never touch the chip.
"""

from __future__ import annotations

import glob
import importlib.util
import json
import math
import multiprocessing
import os
import shutil
import sys
import time

import check
import study

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
#: JAX's persistent compilation cache: one fixed directory in the checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache", "bench")
#: where a traced run's profile goes while it is read (removed after)
TRACE_DIR = os.path.join(ROOT, ".jax_cache", "bench_trace")

_COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/jaxpr_trace_duration")
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_parts(spec: dict, name: str):
    """The cell's workload entry, configuration and traffic dicts, and
    the per-layer metrics it reports."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH_DIR, "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    e2e = {m["name"] for m in spec["end_to_end"]
           if name in m.get("workloads", [name])}
    layer = [(m["name"], m["unit"]) for m in spec["per_layer"]
             if name in m.get("workloads", [name] if m["moves"] in e2e
                              else [])]
    return cell, cfg, traffic, layer


def load_reader(metric: str):
    """The reader of one per-layer metric: ``metrics/<name>.py``."""
    path = os.path.join(BENCH_DIR, "metrics", metric + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


#: chips a cell asks for -> (visible chips, chips-per-process bounds)
_VISIBLE = {1: ("0", "1,1,1"), 4: ("0,1,2,3", "2,2,1")}


def limit_visible_chips(chips: int) -> None:
    """On a host with more chips than the cell asks for, show JAX only
    the first ``chips`` (set before JAX starts): the program shards a
    grid over every device it sees."""
    present = len(glob.glob("/dev/accel*")) or len(glob.glob("/dev/vfio/[0-9]*"))
    if (chips in _VISIBLE and present > chips
            and "TPU_VISIBLE_CHIPS" not in os.environ):
        visible, bounds = _VISIBLE[chips]
        os.environ.update(TPU_VISIBLE_CHIPS=visible,
                          TPU_CHIPS_PER_PROCESS_BOUNDS=bounds,
                          TPU_PROCESS_BOUNDS="1,1,1")


def pin_compile_cache() -> None:
    """Keep every compile in the checkout's own cache directory."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"


def module_level_tracing() -> None:
    """Compile without per-HLO trace marks, so the profiler records one
    event per XLA module launch and not one per op per scan step (a
    40,000-step scan emits ~10^7 op events, overflows the trace buffers
    and takes minutes to collect).  Set for every run, traced or not, so
    both compile and time the same programs."""
    args = os.environ.get("LIBTPU_INIT_ARGS", "")
    if "xla_enable_hlo_trace" not in args:
        os.environ["LIBTPU_INIT_ARGS"] = (
            args + " --xla_enable_hlo_trace=false").strip()


class StudyPool:
    """Builds studies in worker processes (numpy only, spawned, so they
    never load JAX) while the parent brings up the chip."""

    def __init__(self, traffic, cfg, seed: int, workers: int):
        ctx = multiprocessing.get_context("spawn")
        self._pool = ctx.Pool(workers) if workers > 0 else None
        self._args = (traffic, cfg, seed)
        self._jobs: dict[int, object] = {}

    def submit(self, ks) -> None:
        for k in ks:
            if k not in self._jobs:
                self._jobs[k] = (
                    self._pool.apply_async(study.build_study,
                                           self._args + (k,))
                    if self._pool is not None else None)

    def get(self, k: int):
        self.submit([k])
        job = self._jobs[k]
        if job is None:
            return study.build_study(*self._args, k)
        return job.get()

    def close(self) -> None:
        if self._pool is not None:
            self._pool.terminate()
            self._pool.join()
            self._pool = None


def program_experiment_kwargs(cfg: dict, traffic: dict):
    """The cell as the program's user writes it: the base ``SimConfig``
    and the ``Experiment`` axes and options, ``reduce`` among them where
    the traffic streams metrics (program API only)."""
    from repro.core import HCRACConfig, MechanismConfig, SimConfig
    from repro.core.dram import DRAMConfig
    from repro.core.timing import TimingParams
    from repro.experiment.spec import AXIS_BUILDERS

    t = {k: v for k, v in cfg["timing"].items() if k != "cycle_ns"}
    g = {k: v for k, v in cfg["geometry"].items()}
    h = cfg["hcrac"]
    caching = int(round(h["duration_ms"] * 1e6 / cfg["timing"]["cycle_ns"]))
    base = SimConfig(
        dram=DRAMConfig(**g), timing=TimingParams(**t),
        mech=MechanismConfig(kind="base", hcrac=HCRACConfig(
            n_entries=h["entries"], n_ways=h["ways"],
            caching_cycles=caching, exact_expiry=h["exact_expiry"])),
        policy=cfg["row_policy"], mshr=cfg["mshr"],
        warmup_frac=cfg["warmup_frac"], refresh_mode=cfg["refresh"],
        controller=cfg["controller"],
        window=max(1, cfg["window"]) if cfg["controller"] == "frfcfs"
        else SimConfig.__dataclass_fields__["window"].default)
    base = AXIS_BUILDERS["duration_ms"](base, h["duration_ms"])
    axes = {}
    for name, values in traffic["axes"].items():
        if name == "capacity_per_core":
            axes["capacity"] = [(v, v * cfg["cores"]) for v in values]
        else:
            axes[name] = list(values)
    kw = {"axes": axes, "base": base, "rltl": bool(traffic["rltl"])}
    if traffic.get("reduce"):
        kw["reduce"] = tuple(traffic["reduce"])
    return kw


def run_study(batches, kw):
    from repro.experiment import Experiment
    return Experiment(traces=list(batches), **kw).run()


def launch_steps(res, batches) -> int:
    """Scan steps one study's launches ran on each device: every launch
    scans ``cores x longest stream`` steps."""
    n_cores, max_len = max((b.gap.shape for b in batches),
                           key=lambda s: s[1])
    return int(res.meta["n_launches"]) * int(n_cores) * int(max_len)


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, cfg_override: dict | None = None,
             traffic_override: dict | None = None, workers: int | None = None,
             t_start: float | None = None, log=None) -> dict:
    """One run of one cell; returns the result line's object."""
    t_start = time.perf_counter() if t_start is None else t_start
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    cell, cfg, traffic, layer = cell_parts(load_spec(), name)
    cfg = {**cfg, **(cfg_override or {})}
    traffic = {**traffic, **(traffic_override or {})}
    chips = int(cell["chips"])
    if require_tpu:
        limit_visible_chips(chips)
        pin_compile_cache()
        module_level_tracing()
    if workers is None:
        workers = max(1, min(3, (os.cpu_count() or 2) // 2))
    pool = StudyPool(traffic, cfg, seed, workers)
    try:
        pool.submit(range(0, 3))   # the warm-up study and two timed ones
        return _run(name, cell, cfg, traffic, layer, seed, seconds, trace,
                    require_tpu, chips, pool, t_start, log)
    finally:
        pool.close()


def _run(name, cell, cfg, traffic, layer, seed, seconds, trace,
         require_tpu, chips, pool, t_start, log):
    import jax

    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < chips):
        raise NoChip(f"{name} needs {chips} TPU chip(s); JAX sees "
                     f"{len(devices)} {devices[0].platform} device(s)")
    used = devices[:chips] if require_tpu else devices

    compile_log: list[tuple[str, float, float]] = []

    def on_duration(event, duration, **_):
        if event in _COMPILE_EVENTS:
            compile_log.append((event, time.perf_counter(), duration))
    jax.monitoring.register_event_duration_secs_listener(on_duration)

    kw = program_experiment_kwargs(cfg, traffic)
    points = study.grid_points(traffic, cfg)

    # ---- set-up: warm the cell's shapes on a study no timed one uses ----
    with jax.profiler.TraceAnnotation("setup/warmup_inputs"):
        warm_batches = pool.get(0)
    t_w = time.perf_counter()
    n_c0 = len(compile_log)
    with jax.profiler.TraceAnnotation("setup/warmup_study"):
        run_study(warm_batches, kw)
    warm_s = time.perf_counter() - t_w
    warm_compile = sum(d for _, _, d in compile_log[n_c0:])
    t_est = max(warm_s - warm_compile, 0.05)
    n_need = math.ceil(seconds / t_est) + 1
    pool.submit(range(1, n_need + 1))
    with jax.profiler.TraceAnnotation("setup/timed_inputs"):
        inputs = {k: pool.get(k) for k in range(1, n_need + 1)}
    setup_s = time.perf_counter() - t_start
    log(f"setup {setup_s:.3f} s (warm-up study {warm_s:.3f} s, "
        f"{warm_compile:.3f} s of it compiling); {n_need} studies staged")

    # ---- the measured window ------------------------------------------
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR)
    results = []
    built_late = 0
    t0 = time.perf_counter()
    k = 1
    while time.perf_counter() - t0 < seconds:
        if k not in inputs:
            built_late += 1
            inputs[k] = pool.get(k)
        with jax.profiler.TraceAnnotation(f"study/{k}"):
            res = run_study(inputs[k], kw)
        results.append(res)
        k += 1
    t1 = time.perf_counter()
    window_s = t1 - t0
    if trace:
        jax.profiler.stop_trace()
    window_compiles = sum(1 for e, t, _ in compile_log
                          if e == _BACKEND_COMPILE and t0 <= t <= t1)
    n_studies = len(results)
    work = sum(study.work_of(inputs[i + 1], len(points))
               for i in range(n_studies))
    rate = work / window_s
    log(f"window {window_s:.3f} s: {n_studies} studies, {work} simulated "
        f"requests, {rate:.1f} req/s, {built_late} studies built late")

    mem = [d.memory_stats() or {} for d in used]
    peak = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)
    dev = used[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(used), "memory_peak_bytes": peak}

    out = {"correct": None, "attempted": n_studies, "failed": 0}
    if trace:
        import trace_reduce
        red = trace_reduce.reduce_dir(TRACE_DIR, [d.id for d in used])
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        steps = sum(launch_steps(r, inputs[i + 1])
                    for i, r in enumerate(results))
        ctx = {"trace": red, "steps": steps,
               "window_compiles": window_compiles}
        metrics = {}
        for m, unit in layer:
            v = load_reader(m)(ctx)
            if v is not None:
                metrics[m] = {"value": v, "unit": unit}
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        out["metrics"] = metrics
        out["breakdown"] = red["breakdown"]
    else:
        out["metrics"] = {"sim_req_per_s": {"value": rate, "unit": "req/s"},
                          "setup_s": {"value": setup_s, "unit": "s"}}
    out["device"] = device

    # ---- correctness: a seeded sample against the plain reference --------
    sample = study.check_sample(traffic, cfg, seed, n_studies)
    t_c = time.perf_counter()
    verdict = check.compare(results, inputs, sample, points, cfg, traffic)
    log(f"reference replayed {verdict['requests']} requests in "
        f"{time.perf_counter() - t_c:.1f} s")
    out["correct"] = verdict["correct"]
    out["checks"] = verdict["checks"]
    for line in verdict["lines"]:
        log(line)
    return out
