"""Real requests per dispatch over the window, from the engine's counters
(searches completed over batches dispatched)."""


def read(ctx):
    n = ctx.window["n_batches"]
    return ctx.window["n_completed"] / n if n else None
