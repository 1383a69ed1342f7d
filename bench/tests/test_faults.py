"""A run with the timed path broken underneath must come out not correct.

Each test drives the harness's whole run (``--rehearse``: it skips the
look for a chip and runs at the configuration's reduced size on the CPU)
with one fault planted in the program, and sees ``correct`` false, in
every cell of ``BENCHMARK.json`` and in the token fixture
``fixtures/tiny_lm_k6`` (int32 token sequences, per-token targets, the
reference's eval computed in blocks), run as a cell of the ``scan`` mix:

* ``state_unchanged`` — every round returns the state it was given;
* ``half_batch``      — every local step's loss, and so its gradient, is
  the mean over the first half of its minibatch alone;
* ``half_clients``    — the sync's sums take only the first half of the
  clients (the batch of the round) and renormalise over them;
* ``no_sync``         — the OTA sync (the exchange between clients) is
  left out: every client keeps its own params, the consensus is their mean;
* ``answer_altered``  — every round's reported accuracy is off by 5% of
  the test set.
"""
import json

import jax.numpy as jnp
import pytest

from benchlib import harness


def _state_unchanged(monkeypatch):
    from repro.sim import engine
    build = engine._build

    def broken(*args, **kwargs):
        prepare, make_body = build(*args, **kwargs)

        def make(ctx):
            body = make_body(ctx)
            return lambda carry, inp: (carry, body(carry, inp)[1])
        return prepare, make
    monkeypatch.setattr(engine, "_build", broken)


def _half_batch(monkeypatch):
    from repro.sim import engine
    runner = engine.make_local_runner

    def broken(loss_fn, *args, **kwargs):
        def half(p, x, y, *rest):
            n = x.shape[0] // 2
            return loss_fn(p, x[:n], y[:n], *rest)
        return runner(half, *args, **kwargs)
    monkeypatch.setattr(engine, "make_local_runner", broken)


def _half_clients(monkeypatch):
    from repro.core import cwfl
    fused = cwfl.cwfl_round_auto

    def broken(s, phase1, *args, **kwargs):
        keep = jnp.arange(s.shape[0]) < s.shape[0] // 2
        a = phase1 * keep[None, :]
        a = a / jnp.maximum(a.sum(axis=1, keepdims=True), 1e-12)
        return fused(s, a, *args, **kwargs)
    monkeypatch.setattr(cwfl, "cwfl_round_auto", broken)


def _no_sync(monkeypatch):
    from repro.core import cwfl
    monkeypatch.setattr(cwfl, "cwfl_round_auto",
                        lambda s, *a, **k: (s, jnp.mean(s, axis=0)))


def _answer_altered(monkeypatch):
    from repro.sim import engine
    acc = engine._accuracy
    monkeypatch.setattr(engine, "_accuracy",
                        lambda logp, y: acc(logp, y) + 0.05)


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "half_clients": _half_clients, "no_sync": _no_sync,
          "answer_altered": _answer_altered}
FIXTURES = {"tiny_lm_k6.scan": harness.BENCH / "tests" / "fixtures"
            / "tiny_lm_k6.json"}
CELLS = [w["name"] for w in json.loads(
    (harness.ROOT / "BENCHMARK.json").read_text())["workloads"]] + list(
        FIXTURES)


def rehearse(workload: str, capsys) -> dict:
    cell = (harness.config_cell(workload, FIXTURES[workload], "scan")
            if workload in FIXTURES else harness.load_cell(workload))
    rc = harness.run_cell(cell, seed=2**31 + 7, seconds=0.0, trace=False,
                          rehearse=True, t_start=0.0)
    err = capsys.readouterr().err
    line = [x for x in err.splitlines() if x.startswith("rehearsal result")]
    result = json.loads(line[-1].split(": ", 1)[1])
    assert rc == (0 if result["correct"] else 1)
    return result


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, capsys):
    result = rehearse(cell, capsys)
    assert result["correct"] and result["failed"] == 0
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_caught(cell, fault, monkeypatch, capsys):
    FAULTS[fault](monkeypatch)
    result = rehearse(cell, capsys)
    assert not result["correct"], result["checks"]
    assert result["failed"] >= 1
