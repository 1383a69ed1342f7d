"""Device-parallel scenario execution (repro.sim.sharded).

Parity contract (DESIGN.md §Sharded-MC): the sharded sweep runs the SAME
traced trajectory body as the vmap sweep; the only thing the mesh
changes is the batch size XLA compiles for (global N vs per-device N/n),
and batch-size-dependent elementwise fusion can differ by ≤1 ulp per
round, compounding through SGD (the same class the engine documents for
``unroll=2``/eager ``prepare``).  Pinned here as: train-loss histories
within 2 ulp at T=2 (in practice bitwise for most strategies — COTAF's
precode chain is the one observed to re-fuse), accuracy histories
bitwise, shapes/grids identical.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import TopologyConfig, make_topology
from repro.data import SyntheticImageConfig, make_synthetic_images, partition_iid
from repro.dist.sharding_rules import client_specs, trajectory_specs
from repro.models import make_mnist_mlp, nll_loss
from repro.sim import get_scenario, run_monte_carlo, run_rounds
from repro.sim.engine import _build, make_trajectory_fn
from repro.sim.scenarios import Scenario
from repro.sim.sharded import monte_carlo_sharded
from repro.training import FLConfig

K = 8
TCFG = TopologyConfig(num_clients=K, num_hotspots=3)

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs >1 device (CI: XLA_FLAGS=--xla_force_host_platform_"
           "device_count=8)")


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


def _mc(setup, cfg, **kw):
    init, apply, loss, topo, xs, ys, xte, yte = setup
    return run_monte_carlo(init, apply, loss, topo, xs, ys, xte, yte, cfg,
                           **kw)


def _max_ulp(a, b) -> int:
    ia = np.asarray(a, np.float32).view(np.int32).astype(np.int64)
    ib = np.asarray(b, np.float32).view(np.int32).astype(np.int64)
    return int(np.max(np.abs(ia - ib)))


def _assert_sweep_parity(h_v, h_s, max_ulp: int = 2):
    """The documented sharded==vmap bound: losses within ``max_ulp``
    (bitwise in most cases), accuracies bitwise."""
    ulp = _max_ulp(h_v["train_loss"], h_s["train_loss"])
    assert ulp <= max_ulp, f"train_loss off by {ulp} ulp"
    assert bool(jnp.array_equal(h_v["test_acc"], h_s["test_acc"]))


# ---------------------------------------------------------------------------
# Trajectory-parallel Monte-Carlo (shard="mc").
# ---------------------------------------------------------------------------

@multi_device
def test_sharded_seeds_sweep_matches_vmap_cwfl(setup):
    """Acceptance: the seeds-only sharded sweep reproduces the
    single-device vmap path (within the documented ulp bound; observed
    bitwise for CWFL on CPU)."""
    cfg = FLConfig(strategy="cwfl", rounds=2, snr_db=40.0,
                   eval_samples=256, seed=0)
    h_v = _mc(setup, cfg, seeds=8)
    h_s = _mc(setup, cfg, seeds=8, shard="mc")
    assert h_s["train_loss"].shape == (8, 2)
    _assert_sweep_parity(h_v, h_s)


@pytest.mark.slow
@pytest.mark.parametrize("strategy", ["cotaf", "fedavg", "decentralized"])
@multi_device
def test_sharded_seeds_sweep_matches_vmap_baselines(setup, strategy):
    cfg = FLConfig(strategy=strategy, rounds=2, snr_db=40.0,
                   eval_samples=256, seed=0)
    h_v = _mc(setup, cfg, seeds=8)
    h_s = _mc(setup, cfg, seeds=8, shard="mc")
    _assert_sweep_parity(h_v, h_s)


@pytest.mark.slow
@multi_device
def test_sharded_grid_sweep_matches_flattened_vmap(setup):
    """The mesh itself adds nothing: the sharded flattened grid equals a
    ONE-device vmap over the same flattened pairs (observed bitwise; only
    per-device batch-size fusion can split them, bounded at 2 ulp).  The
    standard run_monte_carlo grid path batches nested instead — that gap
    is a vmap-structure property, covered by the tolerance test below."""
    init, apply, loss, topo, xs, ys, xte, yte = setup
    cfg = FLConfig(strategy="cwfl", rounds=2, eval_samples=256, seed=0)
    grid = (0.0, 20.0, 40.0)
    prepare, make_body = _build(init, apply, loss, topo, xs, ys, xte, yte,
                                cfg, Scenario(), None)
    traj = make_trajectory_fn(prepare, make_body)
    seeds = jnp.arange(2)
    sf = jnp.repeat(seeds, 3)
    gf = jnp.tile(jnp.asarray(grid, jnp.float32), 2)
    l_flat, a_flat = jax.jit(jax.vmap(traj))(sf, gf)
    l_sh, a_sh, _ = monte_carlo_sharded(traj, seeds, grid, None, 2)
    assert l_sh.shape == (2, 3, 2)
    assert _max_ulp(l_sh.reshape(6, 2), l_flat) <= 2
    assert bool(jnp.array_equal(a_sh.reshape(6, 2), a_flat))


@pytest.mark.slow
@multi_device
def test_sharded_snr_grid_matches_vmap_ulp(setup):
    """Against the standard nested-vmap grid path: ulp-level agreement
    (flattening changes XLA batching by ~1 ulp/round, compounding through
    SGD — DESIGN.md §Sharded-MC), with identical shapes and grids."""
    cfg = FLConfig(strategy="cwfl", rounds=2, eval_samples=256, seed=0)
    sc = get_scenario("snr-sweep")
    h_v = _mc(setup, cfg, scenario=sc, seeds=2)
    h_s = _mc(setup, cfg, scenario=sc, seeds=2, shard="mc")
    assert h_s["train_loss"].shape == h_v["train_loss"].shape == (2, 5, 2)
    np.testing.assert_allclose(np.asarray(h_s["train_loss"]),
                               np.asarray(h_v["train_loss"]),
                               rtol=2e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h_s["test_acc"]),
                               np.asarray(h_v["test_acc"]), atol=1e-2)


@multi_device
def test_sharded_padding_non_divisible(setup):
    """3 seeds on an 8-way mesh: the grid pads to the device count and the
    padded trajectories are sliced off — results still match vmap."""
    cfg = FLConfig(strategy="cwfl", rounds=2, snr_db=40.0,
                   eval_samples=256, seed=5)
    h_v = _mc(setup, cfg, seeds=3)
    h_s = _mc(setup, cfg, seeds=3, shard="mc")
    assert h_s["train_loss"].shape == (3, 2)
    _assert_sweep_parity(h_v, h_s)


def test_bad_shard_names(setup):
    cfg = FLConfig(strategy="cwfl", rounds=1, eval_samples=64)
    init, apply, loss, topo, xs, ys, xte, yte = setup
    with pytest.raises(ValueError, match="shard='mc'"):
        run_monte_carlo(init, apply, loss, topo, xs, ys, xte, yte, cfg,
                        seeds=2, shard="clients")
    with pytest.raises(ValueError, match="shard='clients'"):
        run_rounds(init, apply, loss, topo, xs, ys, xte, yte, cfg,
                   shard="mc")


# ---------------------------------------------------------------------------
# Client-parallel trajectory (shard="clients").
# ---------------------------------------------------------------------------

@multi_device
def test_client_sharded_matches_unsharded(setup):
    """Splitting the K-client axis over the mesh reproduces the unsharded
    trajectory: metrics to psum-reassociation tolerance (the per-cluster
    OTA sums ride the collective), final params within a few ulp."""
    init, apply, loss, topo, xs, ys, xte, yte = setup
    cfg = FLConfig(strategy="cwfl", rounds=3, snr_db=40.0,
                   eval_samples=256, seed=3)
    h_u = run_rounds(init, apply, loss, topo, xs, ys, xte, yte, cfg)
    h_c = run_rounds(init, apply, loss, topo, xs, ys, xte, yte, cfg,
                     shard="clients")
    np.testing.assert_allclose(np.asarray(h_c["train_loss"]),
                               np.asarray(h_u["train_loss"]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(h_c["test_acc"]),
                               np.asarray(h_u["test_acc"]), atol=1e-2)
    for a, b in zip(jax.tree.leaves(h_c["final_params"]),
                    jax.tree.leaves(h_u["final_params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_client_sharded_guards(setup):
    from repro.sim import ChannelProcessConfig
    init, apply, loss, topo, xs, ys, xte, yte = setup
    cfg = FLConfig(strategy="cotaf", rounds=1, eval_samples=64)
    # capability-flag gate names the strategy's class, not a hard-coded
    # name check
    with pytest.raises(NotImplementedError, match="COTAFStrategy"):
        run_rounds(init, apply, loss, topo, xs, ys, xte, yte, cfg,
                   shard="clients")
    cfg = FLConfig(strategy="cwfl", rounds=1, eval_samples=64)
    sc = Scenario(name="csi", channel=ChannelProcessConfig(csi_error_std=0.3))
    with pytest.raises(NotImplementedError, match="static"):
        run_rounds(init, apply, loss, topo, xs, ys, xte, yte, cfg,
                   scenario=sc, shard="clients")
    # live-progress / loop mode would be silently dead on the sharded
    # path — must refuse loudly instead
    with pytest.raises(ValueError, match="progress"):
        run_rounds(init, apply, loss, topo, xs, ys, xte, yte, cfg,
                   shard="clients", progress=lambda *a: None)


# ---------------------------------------------------------------------------
# Sharding-rules / mesh helpers (run on any device count).
# ---------------------------------------------------------------------------

def test_trajectory_and_client_specs():
    from repro.launch.mesh import make_client_mesh, make_mc_mesh
    n = len(jax.devices())
    mesh = make_mc_mesh()
    sh = {"m": jax.ShapeDtypeStruct((n * 3, 7), jnp.float32),
          "odd": jax.ShapeDtypeStruct((n * 2 + 1,), jnp.float32)}
    specs = trajectory_specs(sh, mesh)
    assert specs["m"] == P("mc", None)
    # non-divisible leading dim falls back to replication (fit rule)
    assert specs["odd"] == (P("mc") if n == 1 else P(None))

    cmesh = make_client_mesh()
    cs = client_specs({"w": jax.ShapeDtypeStruct((n * 4, 5), jnp.float32)},
                      cmesh)
    assert cs["w"] == P("clients", None)


def test_mesh_device_cap_errors():
    from repro.launch.mesh import make_mc_mesh
    with pytest.raises(ValueError, match="devices"):
        make_mc_mesh(len(jax.devices()) + 1)


# ---------------------------------------------------------------------------
# Both executors on four virtual devices, whatever this process has.
# ---------------------------------------------------------------------------

FOUR_DEVICE_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax
import numpy as np
from repro.core import TopologyConfig, make_topology
from repro.data import SyntheticImageConfig, make_synthetic_images, partition_iid
from repro.launch.mesh import make_client_mesh, make_mc_mesh
from repro.models import make_mnist_mlp, nll_loss
from repro.sim import run_monte_carlo, run_rounds
from repro.training import FLConfig

K = 8
dcfg = SyntheticImageConfig.mnist_like(num_train=960, num_test=256)
(xtr, ytr), (xte, yte) = make_synthetic_images(jax.random.PRNGKey(0), dcfg)
topo = make_topology(jax.random.PRNGKey(7),
                     TopologyConfig(num_clients=K, num_hotspots=3))
xs, ys = partition_iid(jax.random.PRNGKey(1), xtr, ytr, K)
init, apply = make_mnist_mlp(hidden=(32,))
loss = lambda p, x, y: nll_loss(apply(p, x), y)
args = (init, apply, loss, topo, xs, ys, xte, yte,
        FLConfig(strategy="cwfl", rounds=2, eval_samples=256, seed=0))

mc_mesh, cl_mesh = make_mc_mesh(), make_client_mesh()
h_s = run_monte_carlo(*args, seeds=4, shard="mc", mesh=mc_mesh)
h_v = run_monte_carlo(*args, seeds=4)
h_c = run_rounds(*args, shard="clients", mesh=cl_mesh)
h_u = run_rounds(*args)
diff = lambda a, b: float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
print("RESULT::" + json.dumps({
    "devices": len(jax.devices()),
    "mesh_sizes": [int(mc_mesh.devices.size), int(cl_mesh.devices.size)],
    "mc_spans": len(h_s["train_loss"].sharding.device_set),
    "clients_spans": min(len(x.sharding.device_set)
                         for x in jax.tree.leaves(h_c["final_params"])),
    "mc_shape": list(np.shape(h_s["train_loss"])),
    "mc_loss": diff(h_s["train_loss"], h_v["train_loss"]),
    "mc_acc": diff(h_s["test_acc"], h_v["test_acc"]),
    "clients_loss_rel": diff(h_c["train_loss"], h_u["train_loss"])
                        / float(np.max(np.abs(np.asarray(h_u["train_loss"])))),
    "clients_acc": diff(h_c["test_acc"], h_u["test_acc"]),
}))
"""


def test_sharded_executors_on_four_virtual_devices():
    """The mc-sharded sweep and the client-sharded trajectory run across
    a 4-device mesh and agree with their single-device runs (the mesh is
    the whole device set, and results span it) — in a subprocess that
    sets its own device count, so this runs on any host."""
    src = str(Path(__file__).resolve().parents[1] / "src")
    proc = subprocess.run(
        [sys.executable, "-c", FOUR_DEVICE_SCRIPT], capture_output=True,
        text=True, timeout=300,
        env={**os.environ, "PYTHONPATH": src, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT::")]
    out = json.loads(line[0][len("RESULT::"):])
    assert out["devices"] == 4 and out["mesh_sizes"] == [4, 4]
    assert out["mc_spans"] == 4 and out["clients_spans"] == 4
    assert out["mc_shape"] == [4, 2]
    # seeds-only sweeps are bitwise (see the parity contract above);
    # the client psum re-associates the OTA sums (ulp-level)
    assert out["mc_loss"] == 0.0 and out["mc_acc"] == 0.0
    assert out["clients_loss_rel"] <= 1e-5 and out["clients_acc"] <= 1e-2
