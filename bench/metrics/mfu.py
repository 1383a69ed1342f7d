"""mfu: the whole round's model FLOPs over the chip's bf16 peak, in %.

Model FLOPs per trajectory-round come from the configuration's widths
(``harness.sizes``: local forward and backward, the eval's forward pass,
the sync's matmuls once), times the trajectory-rounds finished in the
traced window, over window seconds × chips × peak."""


def read(run):
    if run.peaks is None or run.rounds == 0:
        return None
    flops = run.sizes["round_flops"] * run.rounds
    return 100.0 * flops / (run.window_s * run.chips
                            * run.peaks["bf16_flops_per_s"])
