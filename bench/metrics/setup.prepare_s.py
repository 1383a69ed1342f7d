"""setup.prepare_s: seconds the entry point spent in its eager
``prepare`` (clustering, water-filling, init for the plan seed), on the
host clock of ``PhaseTimers`` (phase ``prepare``)."""


def read(run):
    return run.timers.get("prepare")
