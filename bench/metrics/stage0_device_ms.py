"""Device milliseconds per dispatch under the program's ``stage0`` named
scope (the flat column slice, blocked scan and top-k; IVF's probe, member
mask, fused kernel and tail rows), over the traced part of the window:
scoped device time over the dispatches the program enqueued in it."""

from harness import scopes


def read(ctx):
    found = scopes.per_dispatch(ctx, "/stage0/")
    if found is None:
        return None
    seconds, dispatches = found
    return 1e3 * seconds / len(dispatches)
