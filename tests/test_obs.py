"""Observability subsystem (repro.obs, DESIGN.md §Obs).

The load-bearing contract: telemetry is a STATIC opt-in — with the flag
off the engine's traced computation is byte-identical to the pre-obs
build (the committed goldens replay bitwise, pinned by
``tests/test_goldens.py`` since telemetry-off IS the default path), and
with the flag on the ``train_loss``/``test_acc`` history is STILL
bit-for-bit unchanged: every telemetry quantity reads already-
materialized round intermediates plus one fresh full-shard loss eval
(never the fusion-sensitive minibatch loss buffer — see
`repro.sim.engine`).  Plus: the channel-use ledger as the one source of
truth for the paper's §IV cost claim, manifest determinism, the JSONL
sink round-trip, and the report renderer.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from goldens.generate import GOLDEN_DIR, STRATEGIES, workload
from repro.core import TopologyConfig, cwfl
from repro.obs import (PhaseTimers, RoundTelemetry, build_manifest,
                       config_hash, per_client_dim, per_round_table,
                       read_run, symbols_per_round, to_jsonable,
                       uses_per_round, write_history)
from repro.sim import get_scenario, run_monte_carlo, run_rounds
from repro.training import FLConfig

K = 8
TCFG = TopologyConfig(num_clients=K, num_hotspots=3)
GOLDEN_PATH = os.path.join(GOLDEN_DIR, "paper_static_T4_K8.json")

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


def _ulp_dist(a, b) -> int:
    ia = np.asarray(a, np.float32).view(np.int32).astype(np.int64)
    ib = np.asarray(b, np.float32).view(np.int32).astype(np.int64)
    return int(np.max(np.abs(ia - ib)))


# ---------------------------------------------------------------------------
# The bit-neutrality contract: telemetry-on leaves the history unchanged.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("strategy", STRATEGIES)
def test_telemetry_on_replays_golden_bits(strategy):
    """Telemetry-ON at the exact golden protocol reproduces the committed
    telemetry-off bits — recording observations must not perturb the
    trajectory (same bound as tests/test_goldens.py: bitwise on the
    pinned CI config, ≤2 ulp elsewhere)."""
    from goldens.generate import run_strategy  # telemetry-off reference

    with open(GOLDEN_PATH) as f:
        golden = json.load(f)
    p = golden["protocol"]
    pinned = (jax.default_backend() == p["backend"]
              and len(jax.devices()) == p["devices"]
              and jax.__version__ == p["jax"])
    max_ulp = 0 if pinned else 2

    init, apply, loss, topo, xs, ys, xte, yte = workload()
    cfg = FLConfig(strategy=strategy, rounds=4, snr_db=40.0,
                   eval_samples=256, seed=0)
    h = run_rounds(init, apply, loss, topo, xs, ys, xte, yte, cfg,
                   telemetry=True)
    g = golden["strategies"][strategy]
    want_loss = np.asarray(
        [int(x, 16) for x in g["train_loss_bits"]], np.uint32
    ).view(np.float32)
    want_acc = np.asarray(
        [int(x, 16) for x in g["test_acc_bits"]], np.uint32
    ).view(np.float32)
    for name, got, want in (("train_loss", h["train_loss"], want_loss),
                            ("test_acc", h["test_acc"], want_acc)):
        ulp = _ulp_dist(got, want)
        assert ulp <= max_ulp, (
            f"{strategy} telemetry-on {name} drifted {ulp} ulp from the "
            f"telemetry-off golden (bound {max_ulp})")
    assert "telemetry" in h


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_telemetry_pytree_shapes_and_finiteness(wl, strategy):
    """Every strategy's telemetry rides the scan: round-stacked leading
    axis, fixed shapes, finite values, monotone ledger."""
    T = 2
    h = _run(wl, _cfg(strategy, rounds=T), telemetry=True)
    tele = h["telemetry"]
    assert isinstance(tele, RoundTelemetry)
    for leaf in jax.tree.leaves(tele):
        assert leaf.shape[0] == T
        assert bool(jnp.isfinite(leaf).all())
    C = tele.cluster_loss.shape[1]
    assert tele.consensus_drift.shape == (T, C)
    assert tele.participants.shape == (T,)
    np.testing.assert_array_equal(np.asarray(tele.participants),
                                  np.full(T, float(K)))
    # ledger: per-round uses match the strategy's arithmetic, cumulative
    # sums are exact (integer-valued float accumulation)
    uses = float(uses_per_round(strategy, K, 3))
    np.testing.assert_array_equal(np.asarray(tele.channel_uses),
                                  np.full(T, uses))
    np.testing.assert_array_equal(np.asarray(tele.cum_channel_uses),
                                  uses * np.arange(1, T + 1))
    init, *_ = wl
    d = per_client_dim(jax.tree.map(
        lambda x: x[None], init(jax.random.PRNGKey(0))))
    np.testing.assert_array_equal(np.asarray(tele.cum_symbols),
                                  uses * d * np.arange(1, T + 1))


def test_masked_scenario_telemetry(wl):
    """straggler-heavy: effective participation drops below K and the
    CWFL extras stay finite under masked rounds."""
    h = _run(wl, _cfg("cwfl", rounds=4), scenario=get_scenario(
        "straggler-heavy"), topo_cfg=TCFG, telemetry=True)
    tele = h["telemetry"]
    p = np.asarray(tele.participants)
    assert (p <= K).all() and p.min() < K
    for leaf in jax.tree.leaves(tele):
        assert bool(jnp.isfinite(leaf).all())
    # telemetry-on leaves the masked trajectory unchanged too
    h_off = _run(wl, _cfg("cwfl", rounds=4), scenario=get_scenario(
        "straggler-heavy"), topo_cfg=TCFG)
    assert bool(jnp.array_equal(h["train_loss"], h_off["train_loss"]))
    assert bool(jnp.array_equal(h["test_acc"], h_off["test_acc"]))


def test_recluster_events_recorded(wl):
    """cluster-churn (recluster_every=5): the ``reclustered`` flag marks
    exactly the rounds where the lax.cond gate fired (t % 5 == 0)."""
    sc = get_scenario("cluster-churn")
    T = 7
    h = _run(wl, _cfg("cwfl", rounds=T), scenario=sc, topo_cfg=TCFG,
             telemetry=True)
    want = (np.arange(T) % sc.recluster_every == 0).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(h["telemetry"].reclustered),
                                  want)


def test_monte_carlo_telemetry_batches(wl):
    """MC sweeps batch the telemetry pytree over the seed axis."""
    init, apply, loss, topo, xs, ys, xte, yte = wl
    S, T = 2, 2
    h = run_monte_carlo(init, apply, loss, topo, xs, ys, xte, yte,
                        _cfg("cwfl", rounds=T), seeds=S, telemetry=True)
    tele = h["telemetry"]
    assert tele.cluster_loss.shape == (S, T, 3)
    assert tele.participants.shape == (S, T)
    np.testing.assert_array_equal(
        np.asarray(tele.cum_channel_uses)[:, -1],
        np.full(S, float(uses_per_round("cwfl", K, 3)) * T))


# ---------------------------------------------------------------------------
# Channel-use ledger: ONE source of truth for the §IV cost claim.
# ---------------------------------------------------------------------------

def test_ledger_arithmetic():
    assert uses_per_round("cwfl", 12, 3) == 3 * 2 + 3          # C(C−1)+C
    assert uses_per_round("decentralized", 50) == 50 * 49       # K(K−1)
    assert uses_per_round("cotaf", 50) == 1
    assert uses_per_round("fedavg", 50) == 0
    # masked decentralized: P(P−1) with the round's effective P
    assert uses_per_round("decentralized", 50, participants=10.0) == 90.0
    tab = per_round_table(50, 3)
    assert tab == {"cwfl": 9, "decentralized": 2450, "server_ota": 1}
    assert symbols_per_round("cwfl", dim=100, num_clients=50,
                             num_clusters=3) == 900


def test_core_channel_uses_delegates_to_ledger():
    """`repro.core.cwfl.channel_uses_per_round` resolves through the same
    ledger — the benchmark table and the in-scan ledger cannot disagree."""
    for K_, C_ in ((12, 3), (50, 4), (27, 8)):
        assert cwfl.channel_uses_per_round(K_, C_) == per_round_table(K_, C_)


# ---------------------------------------------------------------------------
# Manifests, sink, report.
# ---------------------------------------------------------------------------

def test_manifest_fields_and_hash_stability():
    cfg = _cfg("cwfl")
    man = build_manifest(cfg=cfg, scenario=get_scenario("paper-static"),
                         strategy="cwfl", extra={"note": "t"})
    for field in ("schema", "git", "jax_version", "backend", "device_count",
                  "config", "config_hash", "created_unix", "note"):
        assert field in man
    assert man["strategy"] == "cwfl" and man["scenario"] == "paper-static"
    assert man["config"]["rounds"] == cfg.rounds
    json.dumps(man)     # fully serializable
    # identical protocol ⇒ identical identity hash; any field change flips it
    man2 = build_manifest(cfg=cfg, scenario=get_scenario("paper-static"),
                          strategy="cwfl")
    assert man["config_hash"] == man2["config_hash"]
    man3 = build_manifest(cfg=_cfg("cwfl", rounds=3),
                          scenario=get_scenario("paper-static"),
                          strategy="cwfl")
    assert man["config_hash"] != man3["config_hash"]
    assert config_hash({"b": 1, "a": 2}) == config_hash({"a": 2, "b": 1})


def test_to_jsonable_handles_arrays_dataclasses_namedtuples():
    out = to_jsonable({"cfg": _cfg("cwfl"),
                       "arr": jnp.arange(3),
                       "scalar": jnp.float32(1.5),
                       "tele": RoundTelemetry(*([0.0] * 7), extras={})})
    json.dumps(out)
    assert out["arr"] == [0, 1, 2]
    assert out["scalar"] == 1.5
    assert out["cfg"]["strategy"] == "cwfl"


def test_sink_round_trip_and_report_render(wl, tmp_path):
    """write_history → read_run → examples/obs_report.py is the full
    observability pipeline on a real telemetry run."""
    h = _run(wl, _cfg("cwfl"), telemetry=True)
    man = build_manifest(cfg=_cfg("cwfl"), scenario="paper-static",
                         strategy="cwfl", extra={"clients": K})
    path = tmp_path / "run.jsonl"
    timers = PhaseTimers()
    with timers.phase("execute"):
        pass
    n = write_history(path, h, manifest=man, timings=timers.as_dict())
    assert n == 1 + 2 + 1        # manifest + T rounds + summary

    run = read_run(path)
    assert run["manifest"]["config_hash"] == man["config_hash"]
    assert len(run["rounds"]) == 2
    r1 = run["rounds"][0]
    assert r1["round"] == 1
    assert len(r1["telemetry"]["cluster_loss"]) == 3
    assert r1["telemetry"]["cum_channel_uses"] == 9.0
    assert run["summary"]["cum_channel_uses"] == 18.0
    assert "execute" in run["summary"]["timings"]

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ,
               PYTHONPATH=os.path.join(repo, "src"), JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "examples", "obs_report.py"),
         str(path)], capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode == 0, out.stderr
    md = out.stdout
    for section in ("# Observability report", "## Per-cluster convergence",
                    "## Communication cost", "## Phase timings"):
        assert section in md
    assert "cwfl saves" in md           # the §IV savings row


def test_monte_carlo_sink_tags_trajectories(wl, tmp_path):
    init, apply, loss, topo, xs, ys, xte, yte = wl
    h = run_monte_carlo(init, apply, loss, topo, xs, ys, xte, yte,
                        _cfg("cwfl"), seeds=2, telemetry=True)
    path = tmp_path / "mc.jsonl"
    write_history(path, h)
    run = read_run(path)
    assert len(run["rounds"]) == 4                  # 2 seeds × 2 rounds
    seeds = {r["seed"] for r in run["rounds"]}
    assert seeds == {0, 1}
    assert run["summary"]["trajectories"] == 2


def test_phase_timers_accumulate():
    t = PhaseTimers()
    with t.phase("a"):
        pass
    with t.phase("a"):
        pass
    with t.phase("b"):
        pass
    d = t.as_dict()
    assert set(d) == {"a", "b"} and all(v >= 0 for v in d.values())


# ---------------------------------------------------------------------------
# Phases are spans on the profiler's clock; the round's layers are named
# scopes that survive into the compiled program's op metadata.
# ---------------------------------------------------------------------------

def _hlo_ops(text: str) -> list[tuple[str, str]]:
    """(instruction name, opcode) of every instruction of HLO text."""
    import re
    pat = re.compile(r"^\s*(?:ROOT )?%([^\s=]+) = \S+ ([\w-]+)\(")
    return [m.groups() for m in map(pat.match, text.splitlines()) if m]


def test_phase_spans_share_the_profiler_clock(tmp_path):
    import glob
    import time

    from jax.profiler import ProfileData
    t = PhaseTimers()
    with jax.profiler.trace(str(tmp_path)):
        with t.phase("outer"):
            time.sleep(0.002)
            with t.phase("inner"):
                jnp.ones(8).block_until_ready()
                time.sleep(0.002)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    profile = ProfileData.from_file(path)
    # Host events are stamped relative to the capture's start, which the
    # trace records on the wall clock in ns.
    (start,) = [v for p in profile.planes for k, v in p.stats
                if k == "profile_start_time"]
    events = {ev.name: ev for p in profile.planes if p.name.startswith("/host")
              for line in p.lines for ev in line.events
              if ev.name.startswith("repro.")}
    assert [(n, parent) for n, parent, _, _ in t.spans] == [
        ("inner", "outer"), ("outer", None)]
    for name, _, s_ns, e_ns in t.spans:
        ev = events[f"repro.{name}"]
        assert abs(start + ev.start_ns - s_ns) < 100_000
        assert abs(start + ev.end_ns - e_ns) < 100_000
        assert e_ns > s_ns
    assert t.seconds["outer"] >= t.seconds["inner"] > 0


def test_run_rounds_records_prepare_phase(wl):
    t = PhaseTimers()
    _run(wl, _cfg("cwfl"), timers=t)
    names = [n for n, _, _, _ in t.spans]
    assert names == ["prepare", "trace_compile", "execute"]
    assert all(parent is None for _, parent, _, _ in t.spans)
    assert t.seconds["prepare"] > 0
    assert len(t.executables) == 1


def test_run_monte_carlo_has_no_prepare_phase(wl):
    from repro.obs.profiling import MODEL_SCOPES, ROUND_SCOPES, op_scopes
    init, apply, loss, topo, xs, ys, xte, yte = wl
    t = PhaseTimers()
    run_monte_carlo(init, apply, loss, topo, xs, ys, xte, yte, _cfg("cwfl"),
                    seeds=2, timers=t)
    assert "prepare" not in t.seconds      # traced into the program
    # An MLP opens none of the model scopes (latent attention, experts).
    assert set(op_scopes().values()) == set(ROUND_SCOPES) - set(MODEL_SCOPES)


def test_op_scopes_of_compiled_round():
    """The scopes of a small CPU-compiled round (MLP, K = 4): the
    minibatch gather under ``fl_batch``, a matmul of the local step under
    ``fl_local``, the sync's ops under ``fl_sync``, the eval's matmul under
    ``fl_eval``; innermost wins."""
    from repro.core import make_topology
    from repro.data import (SyntheticImageConfig, make_synthetic_images,
                            partition_iid)
    from repro.models import make_mnist_mlp, nll_loss
    from repro.obs.profiling import op_scopes

    k = 4
    (xtr, ytr), (xte, yte) = make_synthetic_images(
        jax.random.PRNGKey(0),
        SyntheticImageConfig.mnist_like(num_train=512, num_test=128))
    topo = make_topology(jax.random.PRNGKey(7),
                         TopologyConfig(num_clients=k, num_hotspots=2))
    xs, ys = partition_iid(jax.random.PRNGKey(1), xtr, ytr, k)
    init, apply = make_mnist_mlp(hidden=(32,))
    loss = lambda p, x, y: nll_loss(apply(p, x), y)
    t = PhaseTimers()
    run_rounds(init, apply, loss, topo, xs, ys, xte, yte,
               _cfg("cwfl", num_clusters=2, eval_samples=128, batch_size=32),
               timers=t)
    scopes = op_scopes()
    ops = _hlo_ops(t.executables[-1].as_text())
    by_op = {}
    for name, opcode in ops:
        by_op.setdefault(opcode, set()).add(scopes.get(name))
    assert "fl_batch" in by_op["gather"]
    assert {"fl_local", "fl_eval"} <= by_op["dot"]
    assert "fl_sync" in set(scopes.values())
    # A matmul is never put down to the minibatch draw.
    assert "fl_batch" not in by_op["dot"]
    # Ops outside the round body (the scan's own bookkeeping) map to nothing.
    assert any(name not in scopes for name, _ in ops)


@pytest.mark.parametrize("op_name,scope", [
    ("jit(f)/while/body/fl_local/vmap()/while/body/fl_batch/gather",
     "fl_batch"),
    ("jit(f)/closed_call/fl_local/vmap()/transpose(jvp(jit(relu)))/max",
     "fl_local"),
    ("jit(f)/closed_call/fl_sync/jit(cwfl_round)/cwfl_round/pallas_call",
     "fl_sync"),
    ("transpose(jvp(fl_eval))/dot_general", "fl_eval"),
    ("jit(f)/fl_localize/add", None),
    ("jit(f)/my_fl_sync/add", None),
    ("", None),
])
def test_scope_of_op_name(op_name, scope):
    from repro.obs.profiling import scope_of
    assert scope_of(op_name) == scope


# ---------------------------------------------------------------------------
# Device-parallel paths carry telemetry too.
# ---------------------------------------------------------------------------

@multi_device
def test_mc_sharded_telemetry_matches_vmap(wl):
    from repro.launch.mesh import make_mc_mesh
    init, apply, loss, topo, xs, ys, xte, yte = wl
    cfg = _cfg("cwfl")
    kw = dict(seeds=2, telemetry=True)
    h_v = run_monte_carlo(init, apply, loss, topo, xs, ys, xte, yte, cfg,
                          **kw)
    h_s = run_monte_carlo(init, apply, loss, topo, xs, ys, xte, yte, cfg,
                          shard="mc", mesh=make_mc_mesh(2), **kw)
    tv, ts = h_v["telemetry"], h_s["telemetry"]
    assert jax.tree.structure(tv) == jax.tree.structure(ts)
    for a, b in zip(jax.tree.leaves(tv), jax.tree.leaves(ts)):
        assert a.shape == b.shape
    # the ledger is exact integer arithmetic — sharding cannot move it
    np.testing.assert_array_equal(np.asarray(tv.cum_channel_uses),
                                  np.asarray(ts.cum_channel_uses))
    np.testing.assert_array_equal(np.asarray(tv.participants),
                                  np.asarray(ts.participants))


@multi_device
def test_client_sharded_telemetry(wl):
    from repro.launch.mesh import make_client_mesh
    h = _run(wl, _cfg("cwfl"), shard="clients",
             mesh=make_client_mesh(2), telemetry=True)
    tele = h["telemetry"]
    assert tele.cluster_loss.shape == (2, 3)
    for leaf in jax.tree.leaves(tele):
        assert bool(jnp.isfinite(leaf).all())
    np.testing.assert_array_equal(np.asarray(tele.cum_channel_uses),
                                  9.0 * np.arange(1, 3))
    # and the sharded history itself is unperturbed by recording
    h_off = _run(wl, _cfg("cwfl"), shard="clients", mesh=make_client_mesh(2))
    assert bool(jnp.array_equal(h["train_loss"], h_off["train_loss"]))
    assert bool(jnp.array_equal(h["test_acc"], h_off["test_acc"]))
