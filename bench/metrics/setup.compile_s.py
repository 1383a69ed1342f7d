"""setup.compile_s: seconds the entry point spent in ``lower().compile()``
of the cell's program, on the host clock of ``PhaseTimers``
(``trace_compile``).  A persistent-cache hit reads as the load time."""


def read(run):
    return run.timers.get("trace_compile")
