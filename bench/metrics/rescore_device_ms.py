"""Device milliseconds per dispatch under the program's ``rescore`` named
scope (the rescore ladder after stage 0), over the traced part of the
window: scoped device time over the dispatches the program enqueued in
it."""

from harness import scopes


def read(ctx):
    found = scopes.per_dispatch(ctx, "/rescore/")
    if found is None:
        return None
    seconds, dispatches = found
    return 1e3 * seconds / len(dispatches)
