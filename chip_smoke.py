#!/usr/bin/env python3
"""Bring-up smoke test: the simulator's main path on a TPU, checked.

Drives every sweep engine through the user's front door (``Experiment``)
at the thesis's evaluation size (Table 5.1: DDR3-1600, 2 channels, an
eight-core mix at 40,000 requests per core, the 1024-entry / 1 ms HCRAC,
closed rows) and checks the answers by the repo's own references:

* ``trace``   — the mechanism axis on one 8-core mix, RLTL on (the
  device RLTL pass): bitwise against per-point ``simulate()``, and the
  thesis's weighted-speedup ordering base < cc < cc_nuat < lldram with
  every LL-DRAM activation lowered;
* ``oracle``  — every registered mechanism on a short pinned stream, on
  both controller tiers, exactly equal to the numpy host oracle
  (``repro.controller.oracle``, run on the host CPU);
* ``synth``   — the same mechanisms on device-generated streams,
  bitwise against ``materialize`` + ``simulate()``;
* ``window``  — controller × window over the trace tier: in-order riders
  equal the trace tier bitwise, FR-FCFS points serve the same requests;
* ``serving`` — a policy × arrival-rate × mechanism serving grid, and
  FIFO parity with the host scheduler (``repro.serving.loop.oracle``,
  whose hot-page probes run the Pallas HCRAC kernel on the chip).

Usage::

    python3 chip_smoke.py               # one chip, every phase above
    python3 chip_smoke.py --four-chips  # only the sharded trace grid,
                                        # against one-device simulate()

Each phase prints one JSON line (wall time, compile time apart from run
time, points, simulated requests, device kind).  The last line of
standard output is ``{"ok": true, "device": {...}}``, printed only when
every check passed.  Exits non-zero on any failure, and at once when JAX
finds no TPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: Table 5.1's eight-core evaluation: requests per core, cores, seeds
N_CORES = 8
N_REQ = 40_000
N_REQ_SYNTH = 40_000
N_REQ_ORACLE = 300        # 8 x 300: a pinned stream the oracle replays
N_REQ_FOUR = 5_000        # per core, the four-chip sharding check
SERVING_REQS = 256        # benchmarks/serving_loop.py's grid size
SEED = 3
MECHS = ("base", "chargecache", "nuat", "cc_nuat", "rltl", "lldram")
#: the window tier's mechanisms (its step runs the static 16-entry
#: admission loop, the costliest scan of the smoke)
WINDOW_MECHS = ("base", "chargecache")
#: points of each sweep re-run one by one through ``simulate()``
CHECK = ("chargecache", "cc_nuat")
#: the thesis's speedup ordering (Fig 6.1, eight-core)
ORDER = ("base", "chargecache", "cc_nuat", "lldram")

_COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration")
_compile_s = [0.0]


class CheckFailed(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise CheckFailed(msg)


def _on_duration(event: str, duration: float, **_) -> None:
    if event in _COMPILE_EVENTS:
        _compile_s[0] += duration


def thesis_base(n_cores: int = N_CORES):
    """Table 5.1: 128 HCRAC entries per core, 1 ms caching duration and
    its lowered timings, closed rows for multi-core mixes."""
    from repro.core import HCRACConfig, MechanismConfig, SimConfig
    from repro.core.timing import lowered_for_duration, ms_to_cycles
    mech = MechanismConfig(
        kind="base",
        hcrac=HCRACConfig(n_entries=128 * n_cores,
                          caching_cycles=ms_to_cycles(1.0)),
        lowered=lowered_for_duration(1.0))
    return SimConfig(mech=mech, policy="open" if n_cores == 1 else "closed")


def with_kind(cfg, kind: str):
    return dataclasses.replace(
        cfg, mech=dataclasses.replace(cfg.mech, kind=kind))


def mismatches(ref: dict, got: dict, rltl: bool = False) -> list[str]:
    """Keys on which two stats dicts differ (exact integers)."""
    from repro.core.simulator import STAT_KEYS
    bad = [k for k in STAT_KEYS + ("total_cycles",)
           if int(np.asarray(ref[k])) != int(np.asarray(got[k]))]
    if not np.array_equal(ref["core_end"], got["core_end"]):
        bad.append("core_end")
    if rltl:
        if int(ref["rltl_total"]) != int(got["rltl_total"]):
            bad.append("rltl_total")
        if not np.array_equal(ref["rltl_hist"], got["rltl_hist"]):
            bad.append("rltl_hist")
    return bad


def check_same(what: str, ref: dict, got: dict, rltl: bool = False):
    bad = mismatches(ref, got, rltl)
    check(not bad, f"{what}: differs from its reference on {bad}")


def mix_names(n_cores: int = N_CORES) -> list[str]:
    from repro.core.traces import random_mixes
    return random_mixes(1, n_cores)[0]


# --------------------------------------------------------------------------
# phases: each returns (info dict, state for later phases)
# --------------------------------------------------------------------------

def trace_tier(n_req: int):
    from repro.core import simulate, weighted_speedup
    from repro.core.traces import multicore_batch
    from repro.experiment import Experiment
    batch = multicore_batch(mix_names(), n_req, seed=SEED)
    base = thesis_base()
    res = Experiment(traces=batch, axes={"mechanism": MECHS}, base=base,
                     rltl=True).run()
    cells = {m: res.point(mechanism=m) for m in MECHS}
    for m in CHECK:
        check_same(f"trace/{m}", simulate(batch, with_kind(base, m)),
                   cells[m], rltl=True)
    ws = {m: weighted_speedup(cells["base"]["core_end"],
                              cells[m]["core_end"]) for m in MECHS}
    check(all(ws[a] < ws[b] for a, b in zip(ORDER, ORDER[1:])),
          f"weighted speedups out of thesis order {ORDER}: {ws}")
    ll = cells["lldram"]
    check(int(ll["acts"]) > 0 and int(ll["acts_lowered"]) == int(ll["acts"]),
          f"lldram must lower every ACT: {ll['acts_lowered']}/{ll['acts']}")
    check(all(int(c["rltl_total"]) > 0 for c in cells.values()),
          "the RLTL pass matched no ACT to a PRE")
    n = int(batch.length.sum())
    info = {"points": len(MECHS), "sim_requests": n * len(MECHS),
            "checked_points": len(CHECK), "weighted_speedup": ws}
    return info, (batch, cells)


def oracle_tier(n_req: int):
    import jax

    from repro.controller import oracle
    from repro.core import mechanisms as registry
    from repro.core.traces import WorkloadSpec
    from repro.experiment import Experiment
    from repro.workloads.generator import materialize
    short = materialize(WorkloadSpec(names=tuple(mix_names()), n_req=n_req,
                                     seed=7))
    kinds = registry.names()
    host = jax.devices("cpu")[0]
    for ctrl, window in (("inorder", 1), ("frfcfs", 8)):
        base = dataclasses.replace(thesis_base(), controller=ctrl,
                                   window=window)
        res = Experiment(traces=short, axes={"mechanism": kinds},
                         base=base).run()
        for m in kinds:
            with jax.default_device(host):
                ref = oracle.run_host(short, with_kind(base, m))
            check_same(f"oracle/{ctrl}/{m}", ref, res.point(mechanism=m))
    n = int(short.length.sum())
    return {"points": 2 * len(kinds), "sim_requests": 2 * n * len(kinds),
            "mechanisms": list(kinds)}, None


def synth_tier(n_req: int):
    from repro.core import simulate
    from repro.core.traces import WorkloadSpec
    from repro.experiment import Experiment
    from repro.workloads.generator import materialize
    spec = WorkloadSpec(names=tuple(mix_names()), n_req=n_req, seed=SEED)
    base = dataclasses.replace(thesis_base(), workload=spec)
    res = Experiment(traces=None, axes={"mechanism": MECHS},
                     base=base).run()
    batch = materialize(spec, base.dram, base.interleave)
    for m in CHECK:
        check_same(f"synth/{m}", simulate(batch, with_kind(base, m)),
                   res.point(mechanism=m))
    n = int(batch.length.sum())
    return {"points": len(MECHS), "sim_requests": n * len(MECHS),
            "checked_points": len(CHECK)}, None


def window_tier(batch, trace_cells):
    """The window engine at thesis size.  In-order points ride it at
    ``win_cap=1`` and must equal the trace tier bitwise; FR-FCFS points
    serve the same requests (their exactness is the ``oracle`` phase's
    check — a per-point ``simulate()`` here would cost a whole extra
    window-engine scan)."""
    from repro.experiment import Experiment
    res = Experiment(traces=batch,
                     axes={"controller": ["inorder", "frfcfs"],
                           "window": [8, 16], "mechanism": WINDOW_MECHS},
                     base=thesis_base()).run()
    for w in (8, 16):
        for m in WINDOW_MECHS:
            check_same(f"window/inorder/{w}/{m}", trace_cells[m],
                       res.point(controller="inorder", window=w,
                                 mechanism=m))
            # one request served per step on both tiers: the measured
            # (post-warm-up) count agrees, its read/write split need not
            fr = res.point(controller="frfcfs", window=w, mechanism=m)
            n_in = int(trace_cells[m]["n_req"])
            check(int(fr["n_req"]) == n_in
                  and int(fr["reads"]) + int(fr["writes"]) == n_in,
                  f"window/frfcfs/{w}/{m}: measured {int(fr['n_req'])} "
                  f"requests ({int(fr['reads'])} reads, {int(fr['writes'])}"
                  f" writes) vs in-order {n_in}")
    n = int(batch.length.sum())
    n_unique = res.meta["n_unique"]
    return {"points": n_unique, "sim_requests": n * n_unique,
            "checked_points": len(WINDOW_MECHS)}, None


def serving_tier(n_reqs: int):
    from repro.core.simulator import SimConfig, simulate_serving
    from repro.experiment import Experiment
    from repro.serving.loop import ServingSpec
    from repro.serving.loop.oracle import run_host
    from repro.workloads.arrivals import ArrivalConfig
    policies = ("fifo", "charge_aware", "preempting")
    spec = ServingSpec(
        policy="fifo",
        arrival=ArrivalConfig(rate=1.0, burstiness=2.0, prompt_pages_min=1,
                              prompt_pages_max=2, decode_min=4,
                              decode_max=8, seed=11),
        n_reqs=n_reqs, max_batch=8, queue_cap=32, arrivals_max=8,
        cycles_per_step=4000, hot_entries=1024, hot_ways=2,
        hot_caching_ms=0.05, hot_exact=True)
    res = Experiment(traces=None,
                     axes={"policy": list(policies),
                           "arrival_rate": [1.0, 3.0],
                           "mechanism": ["base", "chargecache"]},
                     base=SimConfig(mech=thesis_base(1).mech,
                                    serving=spec)).run()
    retired = res.metric("retired")
    check((retired == n_reqs).all(),
          f"every serving stream must drain: retired {retired.ravel()}")
    hot = {p: float(np.mean(res.sel(policy=p).metric("admit_hot_rate")))
           for p in policies}

    # FIFO parity with the host scheduler on a pinned schedule (the
    # tests/test_serving_loop.py harness)
    n_steps, n_pinned = 160, 48
    pinned = dataclasses.replace(
        spec, arrival=dataclasses.replace(spec.arrival, rate=1.5,
                                          burstiness=1.0, decode_max=12,
                                          seed=7),
        n_reqs=n_pinned, queue_cap=64, arrivals_max=4, n_steps=n_steps,
        hot_entries=1018)
    counts = np.random.default_rng(42).integers(
        0, 4, size=n_steps).astype(np.int32)
    got = simulate_serving(SimConfig(serving=pinned), counts=counts)
    sched, occ = run_host(pinned, counts)
    check(got["retired"] == sched.stats["retired"] == n_pinned,
          f"serving parity: retired {got['retired']} vs host "
          f"{sched.stats['retired']}")
    check(np.array_equal(np.asarray(got["steps"]["occ"]), occ),
          "serving parity: per-step occupancy differs from the host")
    for k in ("admit_probes", "admit_hot"):
        check(got[k] == sched.stats[k],
              f"serving parity: {k} {got[k]} vs host {sched.stats[k]}")
    n_pts = int(res.meta["n_unique"])
    return {"points": n_pts, "sim_requests": n_pts * n_reqs,
            "admit_hot_rate": hot}, None


def four_chip_tier(n_req: int):
    """The grid axis sharded over every visible chip (``_shard_grid``):
    six mechanisms, so the grid pads to a multiple of the device count;
    bitwise against one-device ``simulate()``."""
    import jax

    from repro.core import simulate
    from repro.core import simulator as sim_mod
    from repro.core.traces import multicore_batch
    from repro.experiment import Experiment
    devices = jax.devices()
    check(len(MECHS) % len(devices) != 0,
          f"{len(MECHS)} points would not exercise the padding path on "
          f"{len(devices)} devices")
    batch = multicore_batch(mix_names(), n_req, seed=SEED)
    base = thesis_base()
    spans = []
    drain = sim_mod._drain_batch

    def spy(out, *a, **kw):
        spans.append(len(out[1].sharding.device_set))
        return drain(out, *a, **kw)

    sim_mod._drain_batch = spy
    try:
        res = Experiment(traces=batch, axes={"mechanism": MECHS},
                         base=base, rltl=True).run()
    finally:
        sim_mod._drain_batch = drain
    check(spans and all(s == len(devices) for s in spans),
          f"sweep output spans {spans} devices, expected {len(devices)}")
    with jax.default_device(devices[0]):
        for m in MECHS:
            check_same(f"four_chip/{m}", simulate(batch, with_kind(base, m)),
                       res.point(mechanism=m), rltl=True)
    n = int(batch.length.sum())
    return {"points": len(MECHS), "sim_requests": n * len(MECHS),
            "devices_spanned": spans[0], "checked_points": len(MECHS)}, None


# --------------------------------------------------------------------------

def run_phase(name: str, fn, kind: str, *args):
    """Run one phase; print its JSON line; return its state (or raise)."""
    c0, t0 = _compile_s[0], time.perf_counter()
    info, state = fn(*args)
    wall = time.perf_counter() - t0
    comp = _compile_s[0] - c0
    print(json.dumps({"phase": name, "ok": True, "wall_s": wall,
                      "compile_s": comp, "run_s": wall - comp, **info,
                      "device_kind": kind}), flush=True)
    return state


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the grid sharded over four chips")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, JAX sees {len(devices)}",
              file=sys.stderr)
        return 2

    from repro import compile_cache
    cache = compile_cache.enable()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    print(json.dumps({"platform": dev.platform, "device_kind":
                      dev.device_kind, "count": len(devices),
                      "jax": jax.__version__, "compile_cache": cache}),
          flush=True)

    kind = dev.device_kind
    failed = []

    def attempt(name, fn, *a):
        try:
            return run_phase(name, fn, kind, *a)
        except Exception as e:  # recorded, and fails the run below
            traceback.print_exc()
            print(json.dumps({"phase": name, "ok": False,
                              "error": f"{type(e).__name__}: {e}"[:2000]}),
                  flush=True)
            failed.append(name)
            return None

    t0 = time.perf_counter()
    if args.four_chips:
        attempt("four_chip", four_chip_tier, N_REQ_FOUR)
    else:
        traced = attempt("trace", trace_tier, N_REQ)
        attempt("oracle", oracle_tier, N_REQ_ORACLE)
        attempt("synth", synth_tier, N_REQ_SYNTH)
        if traced is not None:
            attempt("window", window_tier, *traced)
        else:
            failed.append("window (needs trace)")
        attempt("serving", serving_tier, SERVING_REQS)
    print(json.dumps({"total_wall_s": time.perf_counter() - t0,
                      "total_compile_s": _compile_s[0],
                      "failed": failed}), flush=True)
    if failed:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
