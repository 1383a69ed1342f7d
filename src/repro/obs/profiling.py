"""Profiling hooks: phase wall timers + optional jax profiler capture
(DESIGN.md §Obs).

`PhaseTimers` splits a run's wall time into the phases that matter for
the scanned engine — ``trace_compile`` (jit trace + XLA compile via the
AOT ``lower().compile()`` path), ``execute`` (device time to
``block_until_ready``), and ``gather`` (device→host transfer of the
metric buffers) — so BENCH/sim regressions can be attributed to the
right layer instead of a single opaque wall number.  Timers are opt-in:
with ``timers=None`` the engine's default jit path is untouched.

:func:`profiler_trace` wraps a run in ``jax.profiler.trace`` when a
directory is given (TensorBoard-loadable), and is a no-op otherwise.
"""
from __future__ import annotations

import contextlib
import time
from typing import Optional


class PhaseTimers:
    """Accumulating named wall timers: ``with timers.phase("execute"):``.
    Re-entering a phase accumulates (loop-mode rounds sum into one
    ``execute`` figure).  ``executables`` keeps each program the engine
    compiled under ``trace_compile``, in order, so a caller can read its
    HLO (``as_text()``) or ``memory_analysis()``."""

    def __init__(self):
        self.seconds: dict[str, float] = {}
        self.executables: list = []

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        try:
            yield self
        finally:
            self.seconds[name] = (self.seconds.get(name, 0.0)
                                  + time.perf_counter() - t0)

    def as_dict(self) -> dict:
        return {k: round(v, 6) for k, v in sorted(self.seconds.items())}


@contextlib.contextmanager
def profiler_trace(trace_dir: Optional[str] = None):
    """``jax.profiler.trace(trace_dir)`` when a directory is given
    (creates it if needed); a no-op context otherwise."""
    if not trace_dir:
        yield
        return
    import jax
    with jax.profiler.trace(str(trace_dir)):
        yield
