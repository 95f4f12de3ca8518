"""Executables the program built or loaded from the persistent cache
during set-up: its backend-compile events (``repro.obs`` counters, read
through ``program_counters``), eager single-op jits included.  None for
a program without the counters, or where the measured window compiled
anything.
"""

import program_counters


def read(ctx):
    jit = program_counters.setup_jit_cache(ctx)
    if jit is None:
        return None
    return sum(n for n, _ in jit.get("compile", {}).values())
