"""Device time per scan step of the in-order trace engine.

The engine jit is ``core/simulator._run_grid`` (XLA module
``jit__run_grid``); every launch scans ``cores x longest stream`` steps
on each device, so its device time in the window over the steps the
window's launches ran is the time of one step across the device's whole
shard of grid lanes.  Mean over the devices used.
"""

MODULE = "jit__run_grid"


def read(ctx):
    per = [mods[MODULE] for mods in ctx["trace"]["module_s_by_device"].values()
           if mods.get(MODULE)]
    if not per or not ctx["steps"]:
        return None
    return sum(per) / len(per) / ctx["steps"] * 1e6
