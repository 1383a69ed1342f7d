"""round.unscoped_share: device self time of the ops that lie under none
of the round's named scopes, over the device's busy time, in %.

The program maps each instruction of the compiled round to the innermost
of its scopes (``repro.obs.profiling.op_scopes``: ``fl_local``,
``fl_batch``, ``fl_sync``, ``fl_eval``); a trace op is known by its
instruction name.  ``by_scope`` is shared with the ``round.*_ms``
metrics, so that the four of them times the rounds, plus this share of
the busy time, add up to the busy self time."""

from benchlib import tracing


def by_scope(run):
    """{scope, or None for no scope: device self seconds summed over the
    devices}, or None when there is no trace or no scope map."""
    if run.trace is None or run.rounds == 0:
        return None
    try:
        from repro.obs.profiling import op_scopes
    except ImportError:           # a program without the round's scopes
        return None
    scopes = op_scopes()
    if not scopes:
        return None
    out = {}
    for events in run.trace.devices.values():
        for name, seconds in tracing.self_times(events):
            scope = scopes.get(name)
            out[scope] = out.get(scope, 0.0) + seconds
    return out


def per_round_ms(run, scope):
    """Device self milliseconds of ``scope``'s ops per trajectory-round."""
    seconds = by_scope(run)
    if seconds is None:
        return None
    return 1e3 * seconds.get(scope, 0.0) / run.rounds


def read(run):
    seconds = by_scope(run)
    if seconds is None:
        return None
    busy = sum(run.trace.busy_s(d) for d in run.trace.devices)
    return 100.0 * seconds.get(None, 0.0) / busy
