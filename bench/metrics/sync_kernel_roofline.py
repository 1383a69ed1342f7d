"""sync_kernel_roofline: the least time the chip could take for the
round's sync, over the sync kernel's device time, in %.

The work is counted from K, C and d alone, whatever implements the sync:
bytes = 4·d·(2K + 2C + 1) (read the K signals and both noise fields,
write K new signals and the consensus; a copy of
``repro.kernels.cwfl_round.hbm_bytes_model``'s fused count) and
FLOPs = 2CKd + 2C²d + 2KCd.  The least time is the larger of bytes over
the HBM bandwidth and FLOPs over the bf16 peak."""

import re

# The fused sync's custom call, named after its entry point
# (``repro.kernels.cwfl_round.cwfl_round``): ``cwfl_round.8``.
KERNEL = re.compile(r"cwfl_round(\.\d+)?$")


def is_kernel(name: str) -> bool:
    return KERNEL.match(name) is not None


def work(K: int, C: int, d: int) -> tuple[int, int]:
    """(HBM bytes, FLOPs) of one sync round."""
    return 4 * d * (2 * K + 2 * C + 1), 2 * C * K * d + 2 * C * C * d + (
        2 * K * C * d)


def read(run):
    if run.trace is None or run.peaks is None or run.rounds == 0:
        return None
    seconds = run.trace.op_seconds(is_kernel)
    if seconds <= 0:
        return None
    nbytes, flops = work(run.sizes["K"], run.sizes["C"], run.sizes["d"])
    least = max(nbytes / run.peaks["hbm_bytes_per_s"],
                flops / run.peaks["bf16_flops_per_s"])
    return 100.0 * least * run.rounds / seconds
