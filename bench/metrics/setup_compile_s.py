"""Seconds of XLA compiles (and persistent-cache loads) in the program's
own set-up: the compile log that ``repro.obs`` keeps from the process's
``jax.monitoring`` events, summed over the events before the window.  The
log starts when the program is first imported, at the engine's build, so
it holds ``add_docs``, the index build and the warm-up, and not the
harness's corpus generation.  None where the program keeps no such log."""

import sys


def read(ctx):
    log = getattr(sys.modules.get("repro.obs"), "COMPILES", None)
    if log is None:
        return None
    return sum(seconds for t, seconds, _ in log.events() if t < ctx.t0)
