"""The program's own counters, read in the harness's process.

The program runs in the process that runs the harness, so what its
counters hold when the per-layer readers run is what the whole run did:
set-up, then the measured window.  A program that keeps no such counter
gives None, and the reader leaves its metric out of the line.
"""

from __future__ import annotations


def jit_cache():
    """``repro.obs.jit_cache()``: ``{phase: {function: (events,
    seconds)}}`` since the process started, or None without it."""
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.jit_cache()


def setup_jit_cache(ctx):
    """The jit-cache counters of the run's set-up.

    Read after the window, they hold the set-up's compiles and the
    window's.  Where the window built no executable (``window_compiles``
    0, as in a warmed cell) every compile and lowering counted is the
    set-up's (a jit is lowered only to be compiled); otherwise the two
    cannot be told apart here and the result is None.
    """
    if ctx.get("window_compiles", 0) != 0:
        return None
    return jit_cache()
