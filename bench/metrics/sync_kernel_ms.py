"""sync_kernel_ms: device milliseconds of the fused ``cwfl_round`` sync
kernel per trajectory-round, summed from its events in the trace."""

import re

# The fused sync's custom call, named after its entry point
# (``repro.kernels.cwfl_round.cwfl_round``): ``cwfl_round.8``.
KERNEL = re.compile(r"cwfl_round(\.\d+)?$")


def is_kernel(name: str) -> bool:
    return KERNEL.match(name) is not None


def read(run):
    if run.trace is None or run.rounds == 0:
        return None
    if run.trace.op_count(is_kernel) == 0:
        return None
    return 1e3 * run.trace.op_seconds(is_kernel) / run.rounds
