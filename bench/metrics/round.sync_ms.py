"""round.sync_ms: device self milliseconds per trajectory-round of
the sync: coefficients, noise, flatten, the fused kernel and
unflatten (``fl_sync``), for every strategy and scenario.
An op counts under its innermost scope only; the scope map comes from the
program (``repro.obs.profiling.op_scopes``)."""

from pathlib import Path

from benchlib import harness

SCOPE = "fl_sync"
_share = harness.load_module(Path(__file__).with_name(
    "round.unscoped_share.py"))


def read(run):
    return _share.per_round_ms(run, SCOPE)
