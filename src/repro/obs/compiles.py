"""Process-wide log of XLA compiles, read from ``jax.monitoring``.

One listener, registered when `repro.obs` is first imported, records every
``/jax/core/compile/backend_compile_duration`` event (a backend compile or
a load from the persistent compilation cache; JAX tags it with the jitted
function's ``fun_name``) and counts ``/jax/compilation_cache/cache_hits``
(the loads among them).  ``COMPILES`` keeps the totals and a bounded log
of ``(time.monotonic() at the event, seconds, fun_name)``, which tells an
operator which step compiled and when; each engine's metrics collector
exports the totals as ``repro_jax_compiles_total``,
``repro_jax_compile_seconds_total`` and
``repro_jax_compile_cache_hits_total``.

The totals are the process's, not an engine's: a compile cannot be
attributed to the engine that caused it.  ``RequestStats.compiled`` and
``n_compiles`` stay per engine and count the first use of a dispatch shape.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import List, Tuple

import jax.monitoring

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    """Compile totals and the most recent ``capacity`` compile events."""

    def __init__(self, capacity: int = 4096):
        self._lock = threading.Lock()
        self._log = collections.deque(maxlen=capacity)
        self.n_compiles = 0
        self.seconds = 0.0
        self.n_cache_hits = 0

    def on_duration(self, event: str, seconds: float, **kw) -> None:
        if event != COMPILE_EVENT:
            return
        with self._lock:
            self.n_compiles += 1
            self.seconds += seconds
            self._log.append((time.monotonic(), float(seconds),
                              str(kw.get("fun_name", ""))))

    def on_event(self, event: str, **kw) -> None:
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self.n_cache_hits += 1

    def events(self) -> List[Tuple[float, float, str]]:
        """Logged ``(monotonic time, seconds, fun_name)`` events, oldest
        first."""
        with self._lock:
            return list(self._log)

    def totals(self) -> Tuple[int, float, int]:
        """(compiles, compile seconds, persistent-cache hits) so far."""
        with self._lock:
            return self.n_compiles, self.seconds, self.n_cache_hits


COMPILES = CompileLog()
jax.monitoring.register_event_duration_secs_listener(COMPILES.on_duration)
jax.monitoring.register_event_listener(COMPILES.on_event)
