"""Profiling hooks: phase spans on the profiler's clock, the round's named
scopes, and optional jax profiler capture (DESIGN.md §Obs).

`PhaseTimers` splits a run into the phases that matter for the scanned
engine — ``prepare`` (the eager set-up of `run_rounds`: clustering,
water-filling, init), ``trace_compile`` (jit trace + XLA compile via the
AOT ``lower().compile()`` path), ``execute`` (device time to
``block_until_ready``) and ``gather`` (device→host transfer of the metric
buffers).  Each phase is a span: it shows in any profiler capture as a
``repro.<name>`` host event, and is kept in memory with its start and end
on the clock the profiler stamps host events with.  Timers are opt-in:
with ``timers=None`` the engine's default jit path is untouched.

The round body opens one named scope per layer (`ROUND_SCOPES`): local
SGD, its minibatch draw, the sync and the eval.  `op_scopes` maps every
instruction of the newest program `PhaseTimers.compiled` kept to the
innermost of those scopes in its ``op_name`` (a fusion carries its
root's), so a device trace's ops, known by instruction name, can be put
down to a layer.

:func:`profiler_trace` wraps a run in ``jax.profiler.trace`` when a
directory is given (TensorBoard-loadable), and is a no-op otherwise.
"""
from __future__ import annotations

import contextlib
import re
import time
from typing import Optional

# The round body's layers, as `jax.named_scope` names: local SGD
# (`sim.engine`, around the vmapped local run), the minibatch draw and
# gather inside each local step (`training.local`), the sync (every
# strategy's aggregation), and the eval of the consensus.
SCOPE_LOCAL, SCOPE_BATCH, SCOPE_SYNC, SCOPE_EVAL = ROUND_SCOPES = (
    "fl_local", "fl_batch", "fl_sync", "fl_eval")

# An instruction line of HLO text and its ``op_name``; a scope segment
# may be wrapped by transformations (``transpose(jvp(fl_local))``).
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([^\s=]+) = .*?"
                          r'metadata=\{op_name="([^"]*)"')
_SCOPE = re.compile(r"(?:^|/)(?:[\w-]+\()*(%s)\)*(?=/|$)"
                    % "|".join(ROUND_SCOPES))

# The newest program `PhaseTimers.compiled` kept, until `op_scopes` reads
# it, and then its op → scope map.  The read is deferred out of set-up:
# fetching the HLO of the paper's MNIST program from a TPU v5e takes about
# 1.4 s (printing and parsing it under 0.1 s), the CIFAR program's 2.5 s.
_newest_program: list = []
_newest_op_scopes: dict[str, str] = {}


def scope_of(op_name: str) -> Optional[str]:
    """The innermost `ROUND_SCOPES` name among the segments of an
    ``op_name``, or ``None``."""
    found = _SCOPE.findall(op_name)
    return found[-1] if found else None


def hlo_op_scopes(hlo_text: str) -> dict[str, str]:
    """Instruction name → innermost round scope, for every instruction of
    an HLO module's text that lies under one."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            scope = scope_of(m.group(2))
            if scope is not None:
                out[m.group(1)] = scope
    return out


def op_scopes() -> dict[str, str]:
    """The op → scope map of the program `PhaseTimers.compiled` kept last
    in this process (empty before any), read from its HLO text (large
    constants elided) on the first call after it was kept; the program is
    then let go."""
    if _newest_program:
        _newest_op_scopes.update(
            hlo_op_scopes(_newest_program.pop().as_text() or ""))
    return dict(_newest_op_scopes)


class PhaseTimers:
    """Accumulating named phases: ``with timers.phase("execute"):``.

    ``seconds`` sums each phase's wall time (re-entering a phase
    accumulates: loop-mode rounds sum into one ``execute`` figure).
    ``spans`` keeps every entry as ``(name, parent, start_ns, end_ns)``,
    ``parent`` the phase it opened inside (or ``None``), on the clock of
    the profiler's host events; each phase is also a
    ``repro.<name>`` ``TraceAnnotation``.  ``executables`` keeps each
    program passed to `compiled`, in order, so a caller can read its HLO
    (``as_text()``) or ``memory_analysis()``."""

    def __init__(self):
        self.seconds: dict[str, float] = {}
        self.spans: list[tuple[str, Optional[str], int, int]] = []
        self.executables: list = []
        self._open: list[str] = []

    @contextlib.contextmanager
    def phase(self, name: str):
        from jax.profiler import TraceAnnotation
        parent = self._open[-1] if self._open else None
        with TraceAnnotation(f"repro.{name}"):
            self._open.append(name)
            t0, ns0 = time.perf_counter(), time.time_ns()
            try:
                yield self
            finally:
                ns1, t1 = time.time_ns(), time.perf_counter()
                self._open.pop()
                self.seconds[name] = self.seconds.get(name, 0.0) + t1 - t0
                self.spans.append((name, parent, ns0, ns1))

    def compiled(self, fn):
        """Keep a compiled program, and make it the one whose map
        `op_scopes` returns: a caller may let the program go before it
        reads a trace.  The process holds it (and its device buffers)
        until that first `op_scopes` call or the next program kept.
        Returns ``fn``."""
        self.executables.append(fn)
        _newest_program[:] = [fn]
        _newest_op_scopes.clear()
        return fn

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
