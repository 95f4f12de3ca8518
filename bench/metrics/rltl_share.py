"""Share of the traced window the device spends in the RLTL pass.

The pass is ``core/simulator._rltl_hist_device`` (XLA module
``jit__rltl_hist_device``): a sort of every ACT and PRE event of a
launch by row, then a segmented match.  Its device time over the
window's length; mean over devices.
"""

MODULE = "jit__rltl_hist_device"


def read(ctx):
    red = ctx["trace"]
    per = [mods[MODULE] for mods in red["module_s_by_device"].values()
           if mods.get(MODULE)]
    if not per or red["window_s"] <= 0:
        return None
    return sum(per) / len(per) / red["window_s"]
