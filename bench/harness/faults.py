"""Faults planted in the timed path, whose runs ``correct`` has to refuse.

Each takes the served engine, breaks it in place, and returns a function
that mends it.  ``bench/tests/test_faults.py`` drives whole runs with them
on the CPU; ``bench/faults.py`` reads them on the chip at a cell's size.
"""

from __future__ import annotations

import jax.numpy as jnp


def _wrap_dispatch(engine, broken):
    dispatch = engine._dispatch
    engine._dispatch = broken(dispatch)
    return lambda: setattr(engine, "_dispatch", dispatch)


def alter_an_answer(engine):
    """Every answer's best id is replaced by its neighbour's row."""
    def broken(dispatch):
        def inner(q_pad, **kw):
            s, i, compiled = dispatch(q_pad, **kw)
            i = i.copy()
            i[:, 0] = (i[:, 0] + 1) % engine.store.size
            return s, i, compiled
        return inner
    return _wrap_dispatch(engine, broken)


def drop_half_the_batch(engine):
    """Only the first half of each batch is searched; the rest are handed
    the first query's answer."""
    def broken(dispatch):
        def inner(q_pad, **kw):
            half = max(q_pad.shape[0] // 2, 1)
            s, i, compiled = dispatch(q_pad, **kw)
            s, i = s.copy(), i.copy()
            s[half:], i[half:] = s[0], i[0]
            return s, i, compiled
        return inner
    return _wrap_dispatch(engine, broken)


def probe_one_list(engine):
    """IVF stage 0 scans the nearest list alone instead of ``n_probe``."""
    backend = engine.backend
    n_probe = backend.n_probe
    backend.n_probe = 1
    engine.warmup()

    def mend():
        backend.n_probe = n_probe
    return mend


def wrong_lists(engine):
    """IVF stage 0 scans the wrong lists: each list is probed by its
    neighbour's centroid."""
    data = engine.index_state.data
    saved = {k: data[k] for k in ("centroids", "cent_sq")}
    data.update({k: jnp.roll(v, 1, axis=0) for k, v in saved.items()})
    return lambda: data.update(saved)


PLANTED = {f.__name__: f for f in (alter_an_answer, drop_half_the_batch,
                                   probe_one_list, wrong_lists)}
