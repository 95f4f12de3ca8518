"""Share of the traced window in which no XLA module runs on a device.

One minus the union of module intervals over the window, mean over the
devices used: host staging, dispatch, finalize and transfers between
launches all show here.
"""


def read(ctx):
    red = ctx["trace"]
    if red["window_s"] <= 0:
        return None
    return 1.0 - red["busy_s"] / red["window_s"]
