"""Seconds the program spent tracing, lowering and compiling (or loading
from the persistent cache) its jits during set-up, from the program's
jit-cache counters (``repro.obs``, read through ``program_counters``).

Tracing is counted per function, so a jit traced inside another's
trace counts in both.  None for a program without the counters, or
where the measured window compiled anything.
"""

import program_counters

PHASES = ("trace", "lower", "compile")


def read(ctx):
    jit = program_counters.setup_jit_cache(ctx)
    if jit is None:
        return None
    return sum(s for phase in PHASES for _, s in jit.get(phase, {}).values())
