"""Median per-batch ``compute_ms`` of the engine over the window: the host
clock around ``_dispatch``, which ends in ``block_until_ready``."""

import numpy as np


def read(ctx):
    v = ctx.window["batch_ms"]
    return float(np.median(v)) if v else None
