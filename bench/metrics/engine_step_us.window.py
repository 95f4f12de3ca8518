"""Device time per scan step of the FR-FCFS window engine.

The engine jit is ``controller/engine._run_window_grid`` (XLA module
``jit__run_window_grid``): admission of up to W requests, then one
row-hit-first selection and one service, per step.  Its device time in
the window over the steps the window's launches ran; mean over devices.
"""

MODULE = "jit__run_window_grid"


def read(ctx):
    per = [mods[MODULE] for mods in ctx["trace"]["module_s_by_device"].values()
           if mods.get(MODULE)]
    if not per or not ctx["steps"]:
        return None
    return sum(per) / len(per) / ctx["steps"] * 1e6
