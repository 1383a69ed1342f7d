"""The comparison that decides ``correct``: what the timed path produced
against the plain reference (``reference.trajectory``).

Numbers, each held to its limit in the configuration's ``check.limits``:

* ``loss_gap``  — worst relative gap of a round's mean local loss,
  |L_prog − L_ref| / |L_ref|, over the rounds of the call;
* ``first_loss_gap`` — the same for the first round alone, before 70
  rounds of rounding have compounded: it sees a changed step that the
  whole trajectory's spread would hide;
* ``acc_gap``   — worst gap of a round's test accuracy (a share of the
  test set's target positions: its samples, or every token of its
  sequences), over the same rounds;
* ``state_gap`` — for each leaf of the consensus and of the per-client params,
  the gap between the norms of the program's and the reference's change
  over the call, |‖Δθ_prog‖ − ‖Δθ_ref‖|, over the larger of the
  reference's ‖Δθ‖ of that leaf and of the median leaf; the worst leaf.
  Leaves that the reference moves by less than a thousandth of the median
  leaf's change are left out (none are in the paper's models).
"""
from __future__ import annotations

import numpy as np


def loss_gap(prog, ref) -> float:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(prog - ref) / np.maximum(np.abs(ref),
                                                        1e-12)))


def acc_gap(prog, ref) -> float:
    return float(np.max(np.abs(np.asarray(prog, np.float64)
                               - np.asarray(ref, np.float64))))


def _leaves(tree, prefix=""):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _leaves(tree[k], f"{prefix}/{k}")
    else:
        yield prefix, np.asarray(tree, np.float64)


def state_gap(prog0, prog1, ref0, ref1) -> tuple[float, str]:
    """Worst leaf of the change-norm gap, and that leaf's path.  Each
    argument is a dict ``{"consensus": params, "stacked": params}``."""
    names, cp, cr = [], [], []
    for (name, p0), (_, p1), (_, r0), (_, r1) in zip(
            _leaves(prog0), _leaves(prog1), _leaves(ref0), _leaves(ref1)):
        names.append(name)
        cp.append(np.linalg.norm(p1 - p0))
        cr.append(np.linalg.norm(r1 - r0))
    cp, cr = np.asarray(cp), np.asarray(cr)
    median = float(np.median(cr))
    moved = cr >= 1e-3 * median
    gaps = np.where(moved, np.abs(cp - cr) / np.maximum(cr, median), 0.0)
    worst = int(np.argmax(gaps))
    return float(gaps[worst]), names[worst]


def per_round(prog: dict, ref: dict) -> dict:
    """Each round's relative loss gap and accuracy gap."""
    p = {k: np.asarray(prog[k], np.float64) for k in ("loss", "acc")}
    r = {k: np.asarray(ref[k], np.float64) for k in ("loss", "acc")}
    return {"loss": (np.abs(p["loss"] - r["loss"])
                     / np.maximum(np.abs(r["loss"]), 1e-12)).tolist(),
            "acc": np.abs(p["acc"] - r["acc"]).tolist()}


def compare(prog: dict, ref: dict) -> tuple[dict, str]:
    """The numbers of one trajectory: ``prog`` and ``ref`` each hold the
    per-round ``loss`` and ``acc`` of the same rounds, and
    ``state0``/``state1``, the params before and after them."""
    numbers = {"loss_gap": loss_gap(prog["loss"], ref["loss"]),
               "first_loss_gap": loss_gap(prog["loss"][:1], ref["loss"][:1]),
               "acc_gap": acc_gap(prog["acc"], ref["acc"])}
    numbers["state_gap"], leaf = state_gap(prog["state0"], prog["state1"],
                                           ref["state0"], ref["state1"])
    return numbers, (f"{len(ref['loss'])} rounds compared, state_gap worst "
                     f"leaf {leaf}")


def against_reference(prog: dict, conf: dict, model, inputs: dict, *,
                      plan_seed: int, seed: int, **kw) -> tuple[dict, str]:
    """Run the reference over the whole trajectory the program ran, with
    the clustering key from ``plan_seed`` and everything else from
    ``seed``, and compare.
    ``kw`` goes to ``reference.trajectory`` (``dtype``, ``fault``)."""
    from benchlib import reference
    T = conf["fl"]["rounds"]
    plan_key = reference.program_keys(plan_seed, T)[0]
    _, init_key, rkeys = reference.program_keys(seed, T)
    ref = reference.trajectory(model, conf, inputs, plan_key, init_key,
                               rkeys, **kw)
    return compare(prog, ref)


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and the ``{name: {"value", "limit"}}`` record of every
    number compared.  A number that is not finite fails."""
    record = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    ok = all(np.isfinite(v) and v <= limits[k] for k, v in numbers.items())
    return bool(ok), record
