"""Scan-engine equivalence + scenario behaviour (repro.sim.engine).

The heart of the subsystem's correctness story: the scanned trajectory
under the ``paper-static`` scenario must reproduce the legacy per-round
loop (and hence the pre-refactor `run_federated`) BIT-FOR-BIT, and the
participation-mask machinery must be exactly inert at an all-ones mask.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import TopologyConfig, make_topology
from repro.data import SyntheticImageConfig, make_synthetic_images, partition_iid
from repro.models import make_mnist_mlp, nll_loss
from repro.sim import (Scenario, ScheduleConfig, get_scenario,
                       run_monte_carlo, run_rounds)
from repro.training import FLConfig, run_federated

K = 8
TCFG = TopologyConfig(num_clients=K, num_hotspots=3)


@pytest.fixture(scope="module")
def setup():
    key = jax.random.PRNGKey(0)
    dcfg = SyntheticImageConfig.mnist_like(num_train=960, num_test=256)
    (xtr, ytr), (xte, yte) = make_synthetic_images(key, dcfg)
    topo = make_topology(jax.random.PRNGKey(7), TCFG)
    xs, ys = partition_iid(jax.random.PRNGKey(1), xtr, ytr, K)
    init, apply = make_mnist_mlp(hidden=(32,))
    loss = lambda p, x, y: nll_loss(apply(p, x), y)
    return init, apply, loss, topo, xs, ys, xte, yte


def _hist_equal(h1, h2):
    return (bool(jnp.array_equal(h1["train_loss"], h2["train_loss"]))
            and bool(jnp.array_equal(h1["test_acc"], h2["test_acc"])))


# ---------------------------------------------------------------------------
# Satellite: static-scenario scan == legacy loop, bit-for-bit.
# ---------------------------------------------------------------------------

def test_scan_equals_loop_bitwise_cwfl(setup):
    """Tiny MLP, odd round count (exercises the unroll=2 remainder): the
    single-jit scanned trajectory reproduces the per-round-jit loop — the
    pre-refactor `run_federated` structure — exactly."""
    init, apply, loss, topo, xs, ys, xte, yte = setup
    cfg = FLConfig(strategy="cwfl", rounds=5, snr_db=40.0,
                   eval_samples=256, seed=3)
    h_scan = run_rounds(init, apply, loss, topo, xs, ys, xte, yte, cfg,
                        mode="scan")
    h_loop = run_rounds(init, apply, loss, topo, xs, ys, xte, yte, cfg,
                        mode="loop")
    assert _hist_equal(h_scan, h_loop)
    for a, b in zip(jax.tree.leaves(h_scan["final_params"]),
                    jax.tree.leaves(h_loop["final_params"])):
        assert bool(jnp.array_equal(a, b))


@pytest.mark.parametrize("strategy", ["cotaf", "fedavg", "decentralized"])
@pytest.mark.slow
def test_scan_equals_loop_bitwise_baselines(setup, strategy):
    init, apply, loss, topo, xs, ys, xte, yte = setup
    cfg = FLConfig(strategy=strategy, rounds=3, snr_db=40.0,
                   eval_samples=256, seed=3)
    h_scan = run_rounds(init, apply, loss, topo, xs, ys, xte, yte, cfg,
                        mode="scan")
    h_loop = run_rounds(init, apply, loss, topo, xs, ys, xte, yte, cfg,
                        mode="loop")
    assert _hist_equal(h_scan, h_loop)


def test_run_federated_wraps_engine_exactly(setup):
    """The compatibility wrapper's float lists match the engine arrays
    (and the progress-callback loop path matches the scan path)."""
    init, apply, loss, topo, xs, ys, xte, yte = setup
    cfg = FLConfig(strategy="cwfl", rounds=4, snr_db=40.0,
                   eval_samples=256, seed=1)
    h_eng = run_rounds(init, apply, loss, topo, xs, ys, xte, yte, cfg)
    seen = []
    h_wrap = run_federated(init, apply, loss, topo, xs, ys, xte, yte, cfg,
                           progress=lambda r, l, a: seen.append((r, l, a)))
    assert h_wrap["train_loss"] == [float(x) for x in h_eng["train_loss"]]
    assert h_wrap["test_acc"] == [float(x) for x in h_eng["test_acc"]]
    assert h_wrap["round"] == list(range(1, 5))
    assert len(seen) == 4 and seen[0][0] == 1
    assert h_wrap["avg_acc"] == pytest.approx(float(h_eng["avg_acc"]))


# ---------------------------------------------------------------------------
# Satellite: all-ones participation mask == unmasked path.
# ---------------------------------------------------------------------------

def test_engine_all_ones_mask_path_matches_static(setup):
    """A schedule with a huge energy budget is non-trivial (the mask code
    path runs every round) but produces all-ones masks — the trajectory
    must match the static path exactly."""
    init, apply, loss, topo, xs, ys, xte, yte = setup
    cfg = FLConfig(strategy="cwfl", rounds=3, snr_db=40.0,
                   eval_samples=256, seed=2)
    sc = Scenario(name="all-ones",
                  schedule=ScheduleConfig(energy_budget=1e9))
    h_mask = run_rounds(init, apply, loss, topo, xs, ys, xte, yte, cfg,
                        scenario=sc, topo_cfg=TCFG)
    h_ref = run_rounds(init, apply, loss, topo, xs, ys, xte, yte, cfg)
    np.testing.assert_allclose(np.asarray(h_mask["train_loss"]),
                               np.asarray(h_ref["train_loss"]), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(h_mask["test_acc"]),
                               np.asarray(h_ref["test_acc"]), atol=1e-6)


# ---------------------------------------------------------------------------
# Monte-Carlo: one jit over seeds × SNR grid.
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_monte_carlo_snr_sweep_single_jit(setup):
    init, apply, loss, topo, xs, ys, xte, yte = setup
    cfg = FLConfig(strategy="cwfl", rounds=2, eval_samples=256, seed=0)
    sc = get_scenario("snr-sweep")
    h = run_monte_carlo(init, apply, loss, topo, xs, ys, xte, yte, cfg,
                        scenario=sc, seeds=2)
    G = len(sc.snr_grid)
    assert h["train_loss"].shape == (2, G, 2)
    assert h["test_acc"].shape == (2, G, 2)
    assert h["final_acc"].shape == (2, G)
    assert bool(jnp.isfinite(h["train_loss"]).all())
    # distinct seeds produce distinct trajectories
    assert not bool(jnp.array_equal(h["train_loss"][0], h["train_loss"][1]))


@pytest.mark.slow
def test_monte_carlo_seed_axis_matches_single_run(setup):
    """Each vmapped Monte-Carlo element reproduces the standalone scanned
    trajectory for that seed (batching must not change the math beyond
    reassociation-level noise)."""
    init, apply, loss, topo, xs, ys, xte, yte = setup
    cfg = FLConfig(strategy="cwfl", rounds=2, snr_db=40.0,
                   eval_samples=256, seed=11)
    h_mc = run_monte_carlo(init, apply, loss, topo, xs, ys, xte, yte, cfg,
                           seeds=2)
    assert h_mc["train_loss"].shape == (2, 2)
    h1 = run_rounds(init, apply, loss, topo, xs, ys, xte, yte, cfg)
    np.testing.assert_allclose(np.asarray(h_mc["train_loss"][0]),
                               np.asarray(h1["train_loss"]), rtol=2e-5)
    np.testing.assert_allclose(np.asarray(h_mc["test_acc"][0]),
                               np.asarray(h1["test_acc"]), atol=1e-2)


# ---------------------------------------------------------------------------
# Dynamic scenarios run and stay sane.
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("name", ["mobile-fading", "cluster-churn",
                                  "straggler-heavy"])
def test_dynamic_scenarios_run(setup, name):
    init, apply, loss, topo, xs, ys, xte, yte = setup
    cfg = FLConfig(strategy="cwfl", rounds=2, snr_db=40.0,
                   eval_samples=256, seed=0)
    h = run_rounds(init, apply, loss, topo, xs, ys, xte, yte, cfg,
                   scenario=get_scenario(name), topo_cfg=TCFG)
    loss_arr = np.asarray(h["train_loss"])
    assert loss_arr.shape == (2,) and np.isfinite(loss_arr).all()
    # the dynamic world actually differs from the static one — compare the
    # final consensus params (train_loss lags masking by a round and the
    # argmax accuracy is too coarse to register small consensus shifts)
    h_ref = run_rounds(init, apply, loss, topo, xs, ys, xte, yte, cfg)
    assert any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(h["final_params"]),
                        jax.tree.leaves(h_ref["final_params"])))


def test_all_masked_round_skips_sync(setup):
    """Every client straggling every round ⇒ no OTA sync ever happens:
    the consensus (and hence the reported accuracy) stays frozen at the
    initial parameters while clients keep training locally."""
    init, apply, loss, topo, xs, ys, xte, yte = setup
    cfg = FLConfig(strategy="cwfl", rounds=3, snr_db=40.0,
                   eval_samples=256, seed=2)
    sc = Scenario(name="blackout",
                  schedule=ScheduleConfig(num_stragglers=K,
                                          straggler_period=1))
    h = run_rounds(init, apply, loss, topo, xs, ys, xte, yte, cfg,
                   scenario=sc, topo_cfg=TCFG)
    acc = np.asarray(h["test_acc"])
    assert np.isfinite(np.asarray(h["train_loss"])).all()
    assert (acc == acc[0]).all()          # consensus never updated
    # local training still progressed (loss changes across rounds)
    loss_arr = np.asarray(h["train_loss"])
    assert not (loss_arr == loss_arr[0]).all()


def test_csi_only_scenario_needs_no_topo_cfg(setup):
    """Imperfect CSI alone perturbs only the water-filling gains — no
    geometry evolution, so no TopologyConfig is required and the result
    differs from perfect-CSI only through the power allocation."""
    from repro.sim import ChannelProcessConfig
    init, apply, loss, topo, xs, ys, xte, yte = setup
    cfg = FLConfig(strategy="cwfl", rounds=2, snr_db=40.0,
                   eval_samples=256, seed=4)
    sc = Scenario(name="csi-only",
                  channel=ChannelProcessConfig(csi_error_std=0.5))
    assert not sc.channel.evolves_geometry and sc.channel.is_dynamic
    h = run_rounds(init, apply, loss, topo, xs, ys, xte, yte, cfg,
                   scenario=sc)                   # no topo_cfg
    assert np.isfinite(np.asarray(h["train_loss"])).all()
    h_ref = run_rounds(init, apply, loss, topo, xs, ys, xte, yte, cfg)
    assert any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(h["final_params"]),
                        jax.tree.leaves(h_ref["final_params"])))


def test_dynamic_channel_requires_topo_cfg(setup):
    init, apply, loss, topo, xs, ys, xte, yte = setup
    cfg = FLConfig(strategy="cwfl", rounds=1, snr_db=40.0, eval_samples=64)
    with pytest.raises(ValueError, match="TopologyConfig"):
        run_rounds(init, apply, loss, topo, xs, ys, xte, yte, cfg,
                   scenario=get_scenario("mobile-fading"))


def test_unknown_strategy_raises(setup):
    init, apply, loss, topo, xs, ys, xte, yte = setup
    with pytest.raises(KeyError, match="unknown strategy"):
        run_rounds(init, apply, loss, topo, xs, ys, xte, yte,
                   FLConfig(strategy="nope", rounds=1))


# ---------------------------------------------------------------------------
# Row-major client shards: the draw gathers flat rows, bit for bit x[idx].
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sample", [(28, 28, 1), (32, 32, 3), (10,)])
def test_row_draw_matches_sample_shaped_gather(sample):
    """`client_rows` + `draw_minibatch` (the engine's draw, vmapped over
    clients under jit) give bit for bit the minibatch that ``x[idx]``
    gives on the sample-shaped shard under the same key."""
    from repro.sim.engine import client_rows
    from repro.training.local import draw_minibatch

    k, n, b = 3, 96, 16
    xs = jax.random.normal(jax.random.PRNGKey(0), (k, n) + sample)
    ys = jax.random.randint(jax.random.PRNGKey(1), (k, n), 0, 10)
    keys = jax.random.split(jax.random.PRNGKey(2), k)
    rows, shape = client_rows(xs)
    assert rows.shape == (k, n, int(np.prod(sample))) and shape == sample
    xb, yb = jax.jit(jax.vmap(
        lambda x, y, key: draw_minibatch(x, y, key, b, shape)))(rows, ys, keys)
    for c in range(k):
        idx = jax.random.randint(keys[c], (b,), 0, n)
        want = np.asarray(xs[c][idx])
        assert xb[c].shape == want.shape
        np.testing.assert_array_equal(np.asarray(xb[c]).view(np.uint32),
                                      want.view(np.uint32))
        np.testing.assert_array_equal(np.asarray(yb[c]),
                                      np.asarray(ys[c][idx]))


def _sample_shaped_runner(loss_fn, optimizer, batch_size, local_steps,
                          mu_prox=0.0, sample_shape=None):
    """The local step as it was with sample-shaped shards: ``x[idx]`` on
    ``(n_k, *sample)`` (no FedProx: the runs below use plain CWFL)."""
    assert mu_prox == 0.0
    grad_fn = jax.value_and_grad(loss_fn)

    def run(params, opt_state, x, y, key):
        def step(carry, k):
            p, s = carry
            idx = jax.random.randint(k, (batch_size,), 0, x.shape[0])
            loss, grads = grad_fn(p, x[idx], y[idx])
            updates, s = optimizer.update(grads, s, p)
            return (jax.tree.map(jnp.add, p, updates), s), loss

        (params, opt_state), losses = jax.lax.scan(
            step, (params, opt_state), jax.random.split(key, local_steps))
        return params, opt_state, jnp.mean(losses)

    return run


@pytest.mark.parametrize("model", ["mlp", "cnn"])
def test_row_shards_replay_sample_shaped_round_bitwise(model, monkeypatch):
    """A short `run_rounds` on row-major shards gives the same
    train_loss / test_acc bits as the engine built with sample-shaped
    shards and the old ``x[idx]`` step."""
    from repro.models import make_cifar_cnn
    from repro.sim import engine

    if model == "mlp":
        dcfg = SyntheticImageConfig.mnist_like(num_train=512, num_test=128)
        init, apply = make_mnist_mlp(hidden=(32,))
    else:
        dcfg = SyntheticImageConfig("cifar-like", 8, 8, 3, 10, 512, 128)
        init, apply = make_cifar_cnn(input_hw=(8, 8, 3))
    (xtr, ytr), (xte, yte) = make_synthetic_images(jax.random.PRNGKey(0),
                                                   dcfg)
    k = 4
    topo = make_topology(jax.random.PRNGKey(7),
                         TopologyConfig(num_clients=k, num_hotspots=2))
    xs, ys = partition_iid(jax.random.PRNGKey(1), xtr, ytr, k)
    loss = lambda p, x, y: nll_loss(apply(p, x), y)
    cfg = FLConfig(strategy="cwfl", rounds=3, batch_size=32, num_clusters=2,
                   snr_db=40.0, eval_samples=128, seed=5)
    h_rows = run_rounds(init, apply, loss, topo, xs, ys, xte, yte, cfg)
    monkeypatch.setattr(engine, "client_rows",
                        lambda x: (x, tuple(x.shape[2:])))
    monkeypatch.setattr(engine, "make_local_runner", _sample_shaped_runner)
    h_old = run_rounds(init, apply, loss, topo, xs, ys, xte, yte, cfg)
    assert _hist_equal(h_rows, h_old)
