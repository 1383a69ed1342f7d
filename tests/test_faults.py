"""repro.sim.faults + engine fault path: processes, handoff, resume.

Three contracts pinned here (DESIGN.md §Faults):

* the fault processes are scan-legal and statistically correct
  (Markov occupancy, burst correlation, blackout countdown), and the
  divergence guard's quarantine flag catches exactly the poisoned rows;
* strategy recovery is well-defined — ``reelect_heads`` hands a crashed
  head to the surviving max-gain member and leaves geometry alone;
* a trivial ``FaultConfig`` adds ZERO traced ops (jaxpr-identical to a
  scenario with no faults field at all), and interrupted+resumed
  trajectories are BITWISE identical to uninterrupted ones — with and
  without live faults — for every registered strategy.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import TopologyConfig, clustering as cl, make_topology
from repro.sim import (FaultConfig, FaultState, Scenario, get_scenario,
                       init_faults, quarantine_mask, run_rounds, step_faults)
from repro.sim.engine import _build, make_trajectory_fn
from repro.training import FLConfig

from goldens.generate import STRATEGIES, workload

K = 8


# ---------------------------------------------------------------------------
# Fault processes.
# ---------------------------------------------------------------------------

def test_fault_config_trivial_flags():
    assert FaultConfig().is_trivial
    assert not FaultConfig(crash_prob=0.1).is_trivial
    assert not FaultConfig(burst_prob=0.1).is_trivial
    assert not FaultConfig(deep_fade_prob=0.1).is_trivial
    assert not FaultConfig(divergence_guard=True).is_trivial
    # recover/burst_frac alone do nothing without their driving process
    assert FaultConfig(recover_prob=0.9, burst_frac=0.9).is_trivial


def _scan_views(cfg, T, key):
    def body(st, k):
        st, view = step_faults(st, cfg, k)
        return st, view
    keys = jax.random.split(key, T)
    _, views = jax.lax.scan(body, init_faults(cfg, K), keys)
    return views


def test_all_off_process_keeps_everyone_up():
    views = _scan_views(FaultConfig(), 50, jax.random.PRNGKey(0))
    assert np.asarray(views.alive).min() == 1.0
    assert np.asarray(views.tx_ok).min() == 1.0
    assert np.asarray(views.deep_fade).max() == 0.0


def test_markov_crash_occupancy():
    """Long-run P(down) of the 2-state chain is p_c/(p_c+p_r)."""
    p_c, p_r = 0.3, 0.5
    views = _scan_views(FaultConfig(crash_prob=p_c, recover_prob=p_r),
                        600, jax.random.PRNGKey(1))
    alive = np.asarray(views.alive)            # (T, K)
    assert set(np.unique(alive)) <= {0.0, 1.0}
    down = 1.0 - alive[100:].mean()            # burn-in
    assert abs(down - p_c / (p_c + p_r)) < 0.05


def test_deep_fade_blackout_length_and_totality():
    """A blackout silences EVERY client for exactly its configured span."""
    views = _scan_views(
        FaultConfig(deep_fade_prob=0.2, deep_fade_rounds=3),
        400, jax.random.PRNGKey(2))
    fade = np.asarray(views.deep_fade)
    tx = np.asarray(views.tx_ok)
    assert fade.max() == 1.0                   # it does fire
    # while fading, nobody transmits; alive is untouched
    assert tx[fade > 0].max() == 0.0
    assert np.asarray(views.alive).min() == 1.0
    # contiguous fade runs are whole blackouts: multiples of 3 rounds
    # (a fresh blackout may start the round the previous one drains)
    padded = np.concatenate([[0.0], fade, [0.0]])
    starts = np.where(np.diff(padded) > 0)[0]
    ends = np.where(np.diff(padded) < 0)[0]
    lengths = (ends - starts).tolist()
    assert lengths and all(n % 3 == 0 for n in lengths) and 3 in lengths


def test_burst_dropout_is_correlated():
    """Burst hits only exist while the shared burst state is active —
    the cross-client correlation per-client i.i.d. dropout cannot have."""
    views = _scan_views(
        FaultConfig(burst_prob=0.15, burst_recover_prob=0.4,
                    burst_frac=0.6),
        400, jax.random.PRNGKey(3))
    burst = np.asarray(views.burst)
    tx = np.asarray(views.tx_ok)
    assert 0.0 < burst.mean() < 1.0
    assert tx[burst == 0].min() == 1.0         # calm rounds: nobody dropped
    assert tx[burst == 1].mean() < 0.7         # burst rounds: many dropped


def test_processes_are_jit_and_vmap_legal():
    cfg = FaultConfig(crash_prob=0.2, recover_prob=0.2, burst_prob=0.1,
                      burst_recover_prob=0.3, burst_frac=0.5,
                      deep_fade_prob=0.05, deep_fade_rounds=2)
    st = init_faults(cfg, K)
    step = jax.jit(lambda s, k: step_faults(s, cfg, k))
    keys = jax.random.split(jax.random.PRNGKey(4), 5)
    st2, view = jax.vmap(step, in_axes=(None, 0))(st, keys)
    assert isinstance(st2, FaultState) and view.alive.shape == (5, K)


# ---------------------------------------------------------------------------
# Divergence guard.
# ---------------------------------------------------------------------------

def _stack(vals):
    """K-client stack of a 2-leaf pytree with per-client scale ``vals``."""
    base = {"w": jnp.ones((K, 3, 2)), "b": jnp.ones((K, 2))}
    v = jnp.asarray(vals)[:, None]
    return {"w": base["w"] * v[..., None], "b": base["b"] * v}


def test_quarantine_flags_nonfinite_rows_only():
    s = _stack(np.ones(K))
    s["w"] = s["w"].at[2, 0, 0].set(jnp.nan)
    s["b"] = s["b"].at[5, 1].set(jnp.inf)
    q = np.asarray(quarantine_mask(s))
    expect = np.ones(K)
    expect[[2, 5]] = 0.0
    np.testing.assert_array_equal(q, expect)


def test_quarantine_power_threshold():
    vals = np.ones(K)
    vals[3] = 100.0                            # ‖θ‖²/d = 1e4
    s = _stack(vals)
    np.testing.assert_array_equal(np.asarray(quarantine_mask(s)),
                                  np.ones(K))  # limit=0: finite ⇒ healthy
    q = np.asarray(quarantine_mask(s, limit=50.0))
    assert q[3] == 0.0 and q.sum() == K - 1


# ---------------------------------------------------------------------------
# Head-failure handoff (CWFL recovery hook).
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def plan():
    topo = make_topology(jax.random.PRNGKey(0),
                         TopologyConfig(num_clients=K, num_hotspots=3))
    return (cl.make_cluster_plan(topo.link_snr, topo.adjacency, 3,
                                 jax.random.PRNGKey(1)), topo)


def test_reelect_keeps_alive_heads(plan):
    p, topo = plan
    p2 = cl.reelect_heads(p, topo.link_snr, jnp.ones((K,)))
    np.testing.assert_array_equal(np.asarray(p2.heads), np.asarray(p.heads))
    np.testing.assert_array_equal(np.asarray(p2.cluster_snr),
                                  np.asarray(p.cluster_snr))


def test_reelect_replaces_dead_head_with_surviving_max_gain(plan):
    p, topo = plan
    dead = int(p.heads[0])
    alive = jnp.ones((K,)).at[dead].set(0.0)
    p2 = cl.reelect_heads(p, topo.link_snr, alive)
    h = int(p2.heads[0])
    assert h != dead
    # stays within the cluster, is alive, and maximizes aggregate SNR
    assert int(p.assignment[h]) == 0
    members = np.where(np.asarray(p.assignment) == 0)[0]
    score = np.asarray(p.membership @ topo.link_snr.T)[0]
    live = [m for m in members if m != dead]
    assert h == max(live, key=lambda m: score[m])
    # other clusters untouched; geometry untouched
    np.testing.assert_array_equal(np.asarray(p2.heads[1:]),
                                  np.asarray(p.heads[1:]))
    np.testing.assert_array_equal(np.asarray(p2.membership),
                                  np.asarray(p.membership))
    assert float(p2.head_mask.sum()) == 3.0


def test_reelect_fully_dead_cluster_keeps_stale_head(plan):
    """A cluster with no survivors keeps its (dead) head — the
    alive-aware round coefficients zero its row so the index is inert."""
    p, topo = plan
    members = np.where(np.asarray(p.assignment) == 1)[0]
    alive = jnp.ones((K,))
    for m in members:
        alive = alive.at[int(m)].set(0.0)
    p2 = jax.jit(cl.reelect_heads)(p, topo.link_snr, alive)
    assert int(p2.heads[1]) == int(p.heads[1])


# ---------------------------------------------------------------------------
# Engine: inertness, fault runs, checkpoint/resume determinism.
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def wl():
    return workload()


def _traj_jaxpr(wl, scenario, strategy="cwfl", telemetry=False):
    init, apply, loss, topo, xs, ys, xte, yte = wl
    cfg = FLConfig(strategy=strategy, rounds=3, snr_db=40.0,
                   eval_samples=256, seed=0)
    prepare, make_body = _build(init, apply, loss, topo, xs, ys, xte, yte,
                                cfg, scenario, None, telemetry=telemetry)
    jx = str(jax.make_jaxpr(make_trajectory_fn(prepare, make_body))(0, 40.0))
    # function-object reprs embed per-process heap addresses — not ops
    return re.sub(r"0x[0-9a-f]+", "0xADDR", jx)


@pytest.mark.parametrize("telemetry", [False, True])
def test_trivial_faults_trace_zero_extra_ops(wl, telemetry):
    """Static-flag discipline: an all-off FaultConfig must be literally
    invisible in the traced computation (same contract as telemetry)."""
    base = _traj_jaxpr(wl, Scenario(), telemetry=telemetry)
    off = _traj_jaxpr(wl, Scenario(faults=FaultConfig()),
                      telemetry=telemetry)
    assert base == off
    faulty = _traj_jaxpr(
        wl, Scenario(faults=FaultConfig(crash_prob=0.1, recover_prob=0.3)),
        telemetry=telemetry)
    assert faulty != base                      # and the fault path is real


def _hist(wl, strategy, scenario=None, rounds=4, **kw):
    init, apply, loss, topo, xs, ys, xte, yte = wl
    cfg = FLConfig(strategy=strategy, rounds=rounds, snr_db=40.0,
                   eval_samples=256, seed=0)
    return run_rounds(init, apply, loss, topo, xs, ys, xte, yte, cfg,
                      scenario=scenario, **kw)


def _bits(x):
    return np.asarray(x, np.float32).view(np.uint32).tolist()


@pytest.mark.parametrize("name", ["head-failure", "flaky-clients"])
def test_fault_scenarios_fire_and_stay_finite(wl, name):
    h = _hist(wl, "cwfl", scenario=get_scenario(name), rounds=6,
              telemetry=True)
    tl = np.asarray(h["train_loss"])
    assert np.isfinite(tl).all() and np.isfinite(h["test_acc"]).all()
    ex = h["telemetry"].extras
    alive = np.asarray(ex["fault_alive"])
    assert alive.shape == (6, K)
    assert alive.min() == 0.0                  # faults actually fire @seed 0
    assert np.asarray(ex["fault_tx_ok"]).min() == 0.0
    # deterministic replay: same seed ⇒ same bits, faults included
    h2 = _hist(wl, "cwfl", scenario=get_scenario(name), rounds=6,
               telemetry=True)
    assert _bits(h["train_loss"]) == _bits(h2["train_loss"])


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_resume_is_bitwise_identical(wl, strategy, tmp_path):
    """Interrupt at round 2 of 4 (checkpoint every round), resume — the
    stitched history must equal the uninterrupted run bit-for-bit."""
    full = _hist(wl, strategy)
    part = _hist(wl, strategy, checkpoint_dir=tmp_path,
                 checkpoint_every=1, stop_after=2)
    assert np.asarray(part["train_loss"]).shape == (2,)
    res = _hist(wl, strategy, checkpoint_dir=tmp_path,
                checkpoint_every=1, resume=True)
    assert _bits(res["train_loss"]) == _bits(full["train_loss"])
    assert _bits(res["test_acc"]) == _bits(full["test_acc"])
    assert res["round"].tolist() == [1, 2, 3, 4]


@pytest.mark.parametrize("stop", [1, 3])
def test_resume_from_every_boundary(wl, stop, tmp_path):
    full = _hist(wl, "cwfl")
    _hist(wl, "cwfl", checkpoint_dir=tmp_path, checkpoint_every=1,
          stop_after=stop)
    res = _hist(wl, "cwfl", checkpoint_dir=tmp_path, checkpoint_every=1,
                resume=True)
    assert _bits(res["train_loss"]) == _bits(full["train_loss"])


def test_resume_with_live_faults_is_bitwise(wl, tmp_path):
    """FaultState rides the checkpointed carry: an interrupted run under
    an ACTIVE fault process resumes onto the same crash/burst sample
    path, so the stitched trajectory still replays bit-for-bit."""
    sc = get_scenario("flaky-clients")
    full = _hist(wl, "cwfl", scenario=sc, rounds=6)
    _hist(wl, "cwfl", scenario=sc, rounds=6, checkpoint_dir=tmp_path,
          checkpoint_every=2, stop_after=3)
    res = _hist(wl, "cwfl", scenario=sc, rounds=6, checkpoint_dir=tmp_path,
                checkpoint_every=2, resume=True)
    assert _bits(res["train_loss"]) == _bits(full["train_loss"])
    assert _bits(res["test_acc"]) == _bits(full["test_acc"])


def test_checkpoint_manifest_rejects_config_drift(wl, tmp_path):
    _hist(wl, "cwfl", checkpoint_dir=tmp_path, checkpoint_every=1,
          stop_after=1)
    with pytest.raises(ValueError, match="manifest"):
        _hist(wl, "cwfl", scenario=get_scenario("head-failure"),
              checkpoint_dir=tmp_path, checkpoint_every=1, resume=True)
    with pytest.raises(FileNotFoundError):
        _hist(wl, "cwfl", checkpoint_dir=tmp_path / "nowhere", resume=True)


def test_checkpoint_api_validation(wl, tmp_path):
    with pytest.raises(ValueError, match="checkpoint_dir"):
        _hist(wl, "cwfl", resume=True)
    with pytest.raises(ValueError, match="checkpoint_dir"):
        _hist(wl, "cwfl", stop_after=2)


@pytest.mark.slow
@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs >1 device (CI exports 8 fake devices)")
def test_client_sharded_resume_is_bitwise(wl, tmp_path):
    """The client-sharded path checkpoints the same way: an interrupted
    run resumes onto the bits of the uninterrupted CHUNKED run (identical
    compiled segments).  Against the single-scan sharded run the chunked
    one re-fuses per segment length — the same ≤2-ulp class
    tests/test_sim_sharded.py documents for batch-size fusion — so that
    comparison gets the ulp bound, not the bitwise pin."""
    base = _hist(wl, "cwfl", shard="clients",
                 checkpoint_dir=tmp_path / "base", checkpoint_every=1)
    _hist(wl, "cwfl", shard="clients", checkpoint_dir=tmp_path / "crash",
          checkpoint_every=1, stop_after=2)
    res = _hist(wl, "cwfl", shard="clients",
                checkpoint_dir=tmp_path / "crash",
                checkpoint_every=1, resume=True)
    assert _bits(res["train_loss"]) == _bits(base["train_loss"])
    assert _bits(res["test_acc"]) == _bits(base["test_acc"])
    full = _hist(wl, "cwfl", shard="clients")
    ia = np.asarray(res["train_loss"], np.float32).view(np.int32)
    ib = np.asarray(full["train_loss"], np.float32).view(np.int32)
    assert int(np.max(np.abs(ia.astype(np.int64) - ib))) <= 2
    assert _bits(res["test_acc"]) == _bits(full["test_acc"])
