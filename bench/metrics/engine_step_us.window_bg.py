"""Device time per scan step of the FR-FCFS window engine with bank-group
timing compiled in.

The engine jit is ``controller/engine._run_window_grid`` (XLA module
``jit__run_window_grid``), as for ``engine_step_us.window``: its device
time in the window over the steps the window's launches ran, mean over
devices.  Read only where the program's ``repro.obs.scan_steps()``
counter shows that every window-engine step of the run took the
bank-group path (tCCD_S/tCCD_L, tRRD_L): set against
``engine_step_us.window`` of a DDR3 cell it is what bank-group timing
costs the step.  A program without that counter gives None.
"""

MODULE = "jit__run_window_grid"
#: the bank-group value of ``scan_steps()``'s path key
BANK_GROUPS = "bank_groups"


def _all_window_steps_bank_grouped() -> bool:
    try:
        from repro import obs
        steps = obs.scan_steps()
    except (ImportError, AttributeError):
        return False
    window = {path: n for (engine, path), n in steps.items()
              if engine.startswith("_run_window")}
    return window.get(BANK_GROUPS, 0) > 0 and all(
        n == 0 for path, n in window.items() if path != BANK_GROUPS)


def read(ctx):
    per = [mods[MODULE] for mods in ctx["trace"]["module_s_by_device"].values()
           if mods.get(MODULE)]
    if not per or not ctx["steps"] or not _all_window_steps_bank_grouped():
        return None
    return sum(per) / len(per) / ctx["steps"] * 1e6
