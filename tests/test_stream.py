"""Live streaming + alert monitor (repro.obs.stream / .monitor,
DESIGN.md §Obs-live).

The load-bearing contracts, in order of blast radius:

* **stream-off is free**: with ``stream=None`` the telemetry build's
  traced jaxpr is byte-identical to the pre-stream build — the tap is a
  STATIC opt-in, exactly like telemetry itself;
* **stream-on is bit-neutral**: the tapped run's ``train_loss``/
  ``test_acc`` history is bit-for-bit the untapped run's (the
  single-trajectory tap only *reads* the round's already-materialized
  outputs; the Monte-Carlo tap fires post-scan on the stacked output
  buffers — an in-body tap under ``vmap`` re-fuses the batched loss
  reduction and costs 1 ulp, see DESIGN.md §Obs-live);
* **the stream IS the telemetry**: every drained record equals the
  post-hoc ``history["telemetry"]`` slice bitwise, for all four
  strategies and on every executor (scan, vmap MC, mc-sharded rank-0,
  client-sharded), and a checkpoint-resumed run continues absolute
  round numbers and cumulative ledgers seamlessly;
* the `Monitor` rules fire on synthetic violations, stay silent on
  healthy runs, and ``abort_on_alert`` checkpoint-then-stops a run that
  remains resumable.
"""
import importlib.util
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from goldens.generate import STRATEGIES, workload
import repro.obs
from repro.core import TopologyConfig
from repro.obs import (ConsensusDriftRule, ConvergenceStallRule,
                       JsonlStreamSink, MemorySink, Monitor,
                       NonFiniteLossRule, PowerBudgetRule, PrometheusSink,
                       QuarantineRateRule, RoundStream, default_rules)
from repro.obs.stream import _np_tree, _tree_index
from repro.sim import run_monte_carlo, run_rounds
from repro.training import FLConfig

K = 8
TCFG = TopologyConfig(num_clients=K, num_hotspots=3)

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs >1 device (CI: XLA_FLAGS=--xla_force_host_platform_"
           "device_count=8)")


@pytest.fixture(scope="module")
def wl():
    return workload()


def _cfg(strategy, rounds=2, **kw):
    kw.setdefault("snr_db", 40.0)
    kw.setdefault("eval_samples", 256)
    kw.setdefault("seed", 0)
    return FLConfig(strategy=strategy, rounds=rounds, **kw)


def _run(wl, cfg, **kw):
    init, apply, loss, topo, xs, ys, xte, yte = wl
    return run_rounds(init, apply, loss, topo, xs, ys, xte, yte, cfg, **kw)


def _mc(wl, cfg, **kw):
    init, apply, loss, topo, xs, ys, xte, yte = wl
    return run_monte_carlo(init, apply, loss, topo, xs, ys, xte, yte, cfg,
                           **kw)


def _assert_tree_bitwise(a, b, where=""):
    """Recursive bitwise equality of materialized payload trees (dicts/
    lists of np arrays) — NaN-tolerant via bit-pattern comparison."""
    if isinstance(a, dict) or isinstance(b, dict):
        assert isinstance(a, dict) and isinstance(b, dict), \
            f"{where}: {type(a)} vs {type(b)}"
        assert sorted(a) == sorted(b), f"{where}: keys {sorted(a)} vs " \
                                       f"{sorted(b)}"
        for k in a:
            _assert_tree_bitwise(a[k], b[k], f"{where}.{k}")
        return
    if isinstance(a, (list, tuple)):
        assert len(a) == len(b), f"{where}: len {len(a)} vs {len(b)}"
        for i, (x, y) in enumerate(zip(a, b)):
            _assert_tree_bitwise(x, y, f"{where}[{i}]")
        return
    a, b = np.asarray(a), np.asarray(b)
    assert a.shape == b.shape, f"{where}: shape {a.shape} vs {b.shape}"
    assert np.array_equal(np.atleast_1d(a).view(np.uint8),
                          np.atleast_1d(b).view(np.uint8)), \
        f"{where}: bits differ"


def _assert_stream_is_posthoc(records, h, rounds, seed=0, snr_db=40.0):
    """Every streamed record == the post-hoc history slice, bitwise."""
    assert len(records) == rounds
    tele_tree = _np_tree(h["telemetry"])
    loss = np.asarray(h["train_loss"])
    acc = np.asarray(h["test_acc"])
    for rec in records:
        t = rec["round"] - 1
        assert rec["seed"] == seed and rec["snr_db"] == snr_db
        _assert_tree_bitwise(np.asarray(rec["train_loss"]), loss[t],
                             "train_loss")
        _assert_tree_bitwise(np.asarray(rec["test_acc"]), acc[t],
                             "test_acc")
        _assert_tree_bitwise(rec["telemetry"], _tree_index(tele_tree, t),
                             f"telemetry[t={t}]")


# ---------------------------------------------------------------------------
# Stream-off: the tap is a static no-op.
# ---------------------------------------------------------------------------

def test_stream_off_jaxpr_byte_identical(wl):
    """``stream=None`` leaves the telemetry build's jaxpr byte-identical
    to a build that never saw the stream kwarg (normalized for heap
    addresses) — and free of callback primitives entirely."""
    from repro.sim.engine import _build, make_trajectory_fn
    from repro.sim.scenarios import Scenario

    init, apply, loss, topo, xs, ys, xte, yte = wl
    cfg = _cfg("cwfl")

    def jaxpr_of(**kw):
        prepare, make_body = _build(init, apply, loss, topo, xs, ys, xte,
                                    yte, cfg, Scenario(), TCFG,
                                    telemetry=True, **kw)
        traj = make_trajectory_fn(prepare, make_body)
        txt = str(jax.make_jaxpr(traj)(0, 40.0))
        return re.sub(r"0x[0-9a-f]+", "0xADDR", txt)

    base = jaxpr_of()                    # pre-stream call signature
    off = jaxpr_of(stream=None)
    assert off == base
    assert "callback" not in off
    on = jaxpr_of(stream=RoundStream([MemorySink()]))
    assert on != off and "callback" in on


# ---------------------------------------------------------------------------
# Stream-on: bit-neutral, and the stream IS the post-hoc telemetry.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("strategy", STRATEGIES)
def test_stream_matches_posthoc_bitwise(wl, strategy):
    cfg = _cfg(strategy)
    ref = _run(wl, cfg, telemetry=True)
    sink = MemorySink()
    stream = RoundStream([sink])
    h = _run(wl, cfg, telemetry=True, stream=stream)
    for key in ("train_loss", "test_acc"):
        assert np.array_equal(np.asarray(h[key]), np.asarray(ref[key])), \
            f"{strategy}: streamed run perturbed {key}"
    _assert_stream_is_posthoc(stream.records(), h, cfg.rounds)
    assert sink.of_type("stream") == stream.records()
    assert not stream.errors


def test_stream_requires_telemetry(wl):
    with pytest.raises(ValueError):
        _run(wl, _cfg("cwfl"), stream=RoundStream([MemorySink()]))


def test_mc_vmap_stream_bitwise(wl):
    """Monte-Carlo (vmap) streaming: post-scan trajectory tap — metrics
    bitwise vs the untapped sweep, one record per (seed, round)."""
    cfg = _cfg("cwfl")
    ref = _mc(wl, cfg, seeds=2, telemetry=True)
    stream = RoundStream([MemorySink()])
    h = _mc(wl, cfg, seeds=2, telemetry=True, stream=stream)
    for key in ("train_loss", "test_acc"):
        assert np.array_equal(np.asarray(h[key]), np.asarray(ref[key]))
    assert len(stream.records()) == 2 * cfg.rounds
    tele_tree = _np_tree(h["telemetry"])
    for s in range(2):
        recs = stream.for_trajectory(seed=s, snr_db=40.0)
        assert [r["round"] for r in recs] == list(range(1, cfg.rounds + 1))
        for rec in recs:
            t = rec["round"] - 1
            _assert_tree_bitwise(
                np.asarray(rec["train_loss"]),
                np.asarray(h["train_loss"])[s, t], "train_loss")
            _assert_tree_bitwise(
                rec["telemetry"],
                _tree_index(_tree_index(tele_tree, s), t),
                f"telemetry[s={s},t={t}]")


@multi_device
def test_mc_sharded_stream_rank0(wl):
    """mc-sharded streaming: only rank 0's trajectory chunk is emitted
    (the host-side scope drops the rest), records bitwise vs history."""
    n_dev = len(jax.devices())
    seeds = n_dev  # one trajectory per device -> rank 0 owns seed 0
    cfg = _cfg("cwfl")
    stream = RoundStream([MemorySink()])
    h = _mc(wl, cfg, seeds=seeds, shard="mc", telemetry=True,
            stream=stream)
    recs = stream.records()
    assert {r["seed"] for r in recs} == {0}
    assert len(recs) == cfg.rounds
    # the MC tap fires once per trajectory (rounds expand host-side), so
    # each off-scope trajectory counts one drop
    assert stream.dropped == seeds - 1
    tele_tree = _np_tree(h["telemetry"])
    for rec in recs:
        t = rec["round"] - 1
        _assert_tree_bitwise(
            np.asarray(rec["train_loss"]),
            np.asarray(h["train_loss"])[0, t], "train_loss")
        _assert_tree_bitwise(
            rec["telemetry"], _tree_index(_tree_index(tele_tree, 0), t),
            f"telemetry[t={t}]")


@multi_device
def test_client_sharded_stream_bitwise(wl):
    """client-sharded streaming (unordered tap, rank-0 host filter):
    metrics bitwise vs the unsharded run, stream == post-hoc."""
    from repro.launch.mesh import make_client_mesh

    cfg = _cfg("cwfl")
    ref = _run(wl, cfg, telemetry=True)
    stream = RoundStream([MemorySink()])
    h = _run(wl, cfg, shard="clients", mesh=make_client_mesh(),
             telemetry=True, stream=stream)
    for key in ("train_loss", "test_acc"):
        assert np.array_equal(np.asarray(h[key]), np.asarray(ref[key]))
    _assert_stream_is_posthoc(stream.records(), h, cfg.rounds)


def test_resume_continues_stream(wl, tmp_path):
    """Crash at round 2 of 4, resume: the resumed segments emit ABSOLUTE
    rounds 3..4 and the cumulative ledger continues from the checkpoint
    — together the two streams equal an uninterrupted run's."""
    cfg = _cfg("cwfl", rounds=4)
    ref_stream = RoundStream([MemorySink()])
    ref = _run(wl, cfg, telemetry=True, stream=ref_stream)

    ck = str(tmp_path / "ck")
    s1 = RoundStream([MemorySink()])
    _run(wl, cfg, telemetry=True, stream=s1, checkpoint_dir=ck,
         checkpoint_every=1, stop_after=2)
    assert [r["round"] for r in s1.records()] == [1, 2]
    s2 = RoundStream([MemorySink()])
    h = _run(wl, cfg, telemetry=True, stream=s2, checkpoint_dir=ck,
             checkpoint_every=1, resume=True)
    assert [r["round"] for r in s2.records()] == [3, 4]
    for key in ("train_loss", "test_acc"):
        assert np.array_equal(np.asarray(h[key]), np.asarray(ref[key]))
    merged = s1.records() + s2.records()
    for rec, ref_rec in zip(merged, ref_stream.records()):
        _assert_tree_bitwise(rec["telemetry"], ref_rec["telemetry"],
                             f"round {rec['round']}")


def test_abort_on_alert_checkpoint_then_stop(wl, tmp_path):
    """An escalating alert stops the run at the next checkpoint boundary;
    the aborted run resumes to completion."""
    cfg = _cfg("cwfl", rounds=4)
    ck = str(tmp_path / "ck")
    mon = Monitor([ConsensusDriftRule(max_drift=1e-9)],
                  abort_on_alert=True)
    stream = RoundStream([MemorySink()], monitor=mon)
    h = _run(wl, cfg, telemetry=True, stream=stream, checkpoint_dir=ck,
             checkpoint_every=1)
    assert stream.should_abort
    assert np.asarray(h["train_loss"]).shape[0] == 1     # stopped early
    h2 = _run(wl, cfg, telemetry=True,
              stream=RoundStream([MemorySink()]), checkpoint_dir=ck,
              checkpoint_every=1, resume=True)
    assert np.asarray(h2["train_loss"]).shape[0] == cfg.rounds


def test_abort_without_checkpoint_raises(wl):
    mon = Monitor(default_rules(), abort_on_alert=True)
    with pytest.raises(ValueError):
        _run(wl, _cfg("cwfl"), telemetry=True,
             stream=RoundStream([MemorySink()], monitor=mon))


# ---------------------------------------------------------------------------
# Monitor rules: fire on synthetic violations, silent on healthy runs.
# ---------------------------------------------------------------------------

def _rec(round=1, seed=0, snr_db=40.0, train_loss=2.0, drift=(0.5, 0.6),
         extras=None, **tele):
    telemetry = {"cluster_loss": [2.0, 2.1], "participants": 8.0,
                 "consensus_drift": list(drift), "channel_uses": 9.0,
                 "cum_channel_uses": 9.0 * round, "cum_symbols": 100.0,
                 "reclustered": 0.0, "extras": extras or {}}
    telemetry.update(tele)
    return {"type": "stream", "round": round, "seed": seed,
            "snr_db": snr_db, "train_loss": train_loss, "test_acc": 0.5,
            "telemetry": telemetry}


def test_nonfinite_loss_rule():
    mon = Monitor([NonFiniteLossRule()])
    assert not mon.observe(_rec())
    alerts = mon.observe(_rec(round=2, train_loss=float("nan")))
    assert [a.rule for a in alerts] == ["non_finite_loss"]
    assert alerts[0].round == 2
    rec = alerts[0].to_record()
    assert rec["type"] == "alert" and rec["trajectory"]["seed"] == 0


def test_consensus_drift_rule_blowup():
    mon = Monitor([ConsensusDriftRule(max_drift=100.0, blowup=50.0)])
    assert not mon.observe(_rec(round=1, drift=(0.5,)))
    # 60x the round-1 baseline trips the blowup arm under the ceiling.
    assert mon.observe(_rec(round=2, drift=(30.0,)))
    # Separate trajectory, separate baseline: silent.
    assert not mon.observe(_rec(round=1, seed=7, drift=(30.0,)))


def test_quarantine_rate_rule():
    mon = Monitor([QuarantineRateRule(max_rate=0.5)])
    assert not mon.observe(_rec())                       # no fault plane
    extras = {"fault_quarantined": 6.0,
              "fault_alive": [1.0] * 8}
    assert mon.observe(_rec(extras=extras))


def test_power_budget_rule():
    mon = Monitor([PowerBudgetRule(tol=1.05)])
    assert not mon.observe(_rec(extras={"power_budget_frac": 1.0}))
    alerts = mon.observe(_rec(round=2,
                              extras={"power_budget_frac": 1.2}))
    assert [a.rule for a in alerts] == ["power_budget"]


def test_convergence_stall_rule():
    stall = ConvergenceStallRule(min_rounds=6, rel_tol=0.5)
    mon = Monitor([stall])
    # A clean c/T envelope: silent through 10 rounds.
    for t in range(1, 11):
        assert not mon.observe(_rec(round=t, train_loss=1.0 + 3.0 / t))
    # A rising trajectory (c < 0) fires once enough rounds accumulate.
    mon2 = Monitor([ConvergenceStallRule(min_rounds=6, rel_tol=0.5)])
    fired = []
    for t in range(1, 11):
        fired += mon2.observe(_rec(round=t, train_loss=1.0 + 0.3 * t))
    assert any(a.rule == "convergence_stall" for a in fired)


def test_broken_rule_is_contained():
    class Bomb(ConsensusDriftRule):
        name = "bomb"

        def observe(self, rec):
            raise RuntimeError("boom")

    mon = Monitor([Bomb()])
    alerts = mon.observe(_rec())
    assert [a.rule for a in alerts] == ["bomb!error"]


def test_abort_on_named_rules_only():
    mon = Monitor([NonFiniteLossRule(), PowerBudgetRule()],
                  abort_on_alert=["non_finite_loss"])
    mon.observe(_rec(extras={"power_budget_frac": 2.0}))
    assert not mon.should_abort
    mon.observe(_rec(round=2, train_loss=float("inf")))
    assert mon.should_abort


def test_default_rules_silent_on_healthy_stream(wl):
    """The CI invariant: zero alerts on a healthy paper-static run."""
    mon = Monitor(default_rules())
    stream = RoundStream([MemorySink()], monitor=mon)
    _run(wl, _cfg("cwfl", rounds=3), telemetry=True, stream=stream)
    assert mon.summary()["alerts"] == 0


# ---------------------------------------------------------------------------
# Sinks + the terminal watcher.
# ---------------------------------------------------------------------------

def test_jsonl_sink_appends_and_prom_textfile(tmp_path):
    path = tmp_path / "s.jsonl"
    sink = JsonlStreamSink(str(path))
    sink.write({"type": "manifest", "x": 1})
    sink.write(_rec())
    sink.close()
    sink2 = JsonlStreamSink(str(path), append=True)    # resume mode
    sink2.write(_rec(round=2))
    sink2.close()
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert [l.get("round") for l in lines] == [None, 1, 2]

    prom = tmp_path / "s.prom"
    ps = PrometheusSink(str(prom))
    ps.write(_rec(round=3))
    ps.write({"type": "alert", "rule": "power_budget",
              "trajectory": {"seed": 0, "snr_db": 40.0}})
    ps.close()
    text = prom.read_text()
    assert 'repro_round{seed="0",snr_db="40"} 3' in text
    assert "repro_alerts_total" in text


def test_watch_run_renders_and_gates(tmp_path):
    path = tmp_path / "s.jsonl"
    sink = JsonlStreamSink(str(path))
    for t in range(1, 4):
        sink.write(_rec(round=t, train_loss=3.0 - 0.5 * t))
    sink.close()
    script = os.path.join(os.path.dirname(__file__), "..", "examples",
                          "watch_run.py")
    r = subprocess.run([sys.executable, script, str(path)],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    assert "round 3" in r.stdout and "cum_uses" in r.stdout

    sink = JsonlStreamSink(str(path), append=True)
    sink.write({"type": "alert", "rule": "nonfinite_loss", "round": 4,
                "trajectory": {"seed": 0, "snr_db": 40.0},
                "message": "loss is nan"})
    sink.close()
    r = subprocess.run([sys.executable, script, str(path),
                        "--fail-on-alert"], capture_output=True, text=True)
    assert r.returncode == 2
    assert "nonfinite_loss" in r.stdout


@pytest.mark.parametrize("sink_fails", [False, True])
def test_run_scenario_exit_code_reflects_tap_errors(tmp_path, monkeypatch,
                                                    sink_fails):
    """The tap swallows host-side errors into ``stream.errors`` so the
    scan survives them; ``examples/run_scenario.py`` must still exit
    non-zero when any were recorded, and 0 when none were."""
    class FailingSink(JsonlStreamSink):
        def write(self, record):
            if record.get("type") == "stream":
                raise OSError("sink refused the record")
            super().write(record)

    if sink_fails:
        monkeypatch.setattr(repro.obs, "JsonlStreamSink", FailingSink)
    # A set variable leaves the process's compile cache as it is.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    script = os.path.join(os.path.dirname(__file__), "..", "examples",
                          "run_scenario.py")
    spec = importlib.util.spec_from_file_location("run_scenario", script)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(sys, "argv", [
        "run_scenario.py", "--clients", "4", "--rounds", "2", "--hidden",
        "8", "--train", "400", "--test", "64",
        "--stream", str(tmp_path / "live.jsonl")])
    if sink_fails:
        with pytest.raises(SystemExit) as e:
            mod.main()
        assert e.value.code not in (0, None)
        assert "sink refused the record" in str(e.value.code)
    else:
        mod.main()
        lines = (tmp_path / "live.jsonl").read_text().splitlines()
        assert [json.loads(l)["type"] for l in lines].count("stream") == 2
