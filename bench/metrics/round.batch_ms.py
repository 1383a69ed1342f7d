"""round.batch_ms: device self milliseconds per trajectory-round of
the minibatch draw and gather inside each local step
(``fl_batch``).
An op counts under its innermost scope only; the scope map comes from the
program (``repro.obs.profiling.op_scopes``)."""

from pathlib import Path

from benchlib import harness

SCOPE = "fl_batch"
_share = harness.load_module(Path(__file__).with_name(
    "round.unscoped_share.py"))


def read(run):
    return _share.per_round_ms(run, SCOPE)
