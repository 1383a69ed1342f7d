"""round.local_ms: device self milliseconds per trajectory-round of
local SGD: the vmapped local run of every client
(``fl_local``), less its minibatch draw and gather, which
``round.batch_ms`` reads.
An op counts under its innermost scope only; the scope map comes from the
program (``repro.obs.profiling.op_scopes``)."""

from pathlib import Path

from benchlib import harness

SCOPE = "fl_local"
_share = harness.load_module(Path(__file__).with_name(
    "round.unscoped_share.py"))


def read(run):
    return _share.per_round_ms(run, SCOPE)
