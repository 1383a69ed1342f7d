"""Device-parallel execution of the scenario engine (DESIGN.md §Sharded-MC).

`repro.sim.engine.run_monte_carlo` batches the whole seeds × SNR grid onto
ONE device with ``vmap``; this module distributes the same traced
trajectory body (`engine.make_trajectory_fn` — shared, not re-derived)
across the mesh:

* ``monte_carlo_sharded`` — the trajectory grid is flattened seed-major,
  padded up to the ``("mc",)`` mesh axis size, and run under
  ``shard_map``: each device vmaps its own chunk of trajectories with
  per-trajectory metric buffers staying on that device until the single
  gather implied by the ``P("mc")`` out-spec.  Trajectories are
  embarrassingly parallel, so the body contains no collective at all —
  the sharded sweep computes exactly what the single-device vmap sweep
  computes (parity is pinned bitwise by ``tests/test_sim_sharded.py``;
  see DESIGN.md §Sharded-MC for why batch-size-dependent XLA fusion is
  the only thing that could ever split them).

* ``run_rounds_client_sharded`` — within ONE large-K trajectory, the
  stacked client axis is split over a ``("clients",)`` mesh
  (`repro.dist.sharding_rules.client_specs`): each rank trains its K/n
  clients locally and the CWFL sync runs as a two-phase collective in the
  mold of `repro.dist.fl_integration.hierarchical_ota_allreduce` — the
  per-cluster OTA sums ride a masked ``psum`` over the client axis
  (phase 1), the tiny inter-head consensus mix stays rank-local
  (phase 2), and each rank applies only its own rows of the phase-3
  downlink.  Channel-noise keys are replicated, so every rank sees the
  same channel realization, exactly like the hierarchical collective.
  Parity with the unsharded engine is *ulp-level*, not bitwise: the
  ``psum`` re-associates the over-the-air superposition Σ_k Ã_ck θ_k
  (and the gathered precoding norms) across ranks — documented in
  DESIGN.md §Sharded-MC and pinned with tolerances in the tests.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import cwfl
from repro.dist.sharding_rules import client_specs, trajectory_specs
from repro.kernels.ota_aggregate import SYNC_PRECISION
from repro.launch.mesh import make_client_mesh, make_mc_mesh
from repro.models.small import accuracy as _accuracy
from repro.obs.telemetry import RoundTelemetry, per_client_dim
from repro.sim.engine import (_SCAN_UNROLL, _build, _run_options,
                              _run_scan_checkpointed, _trajectory_history,
                              client_rows, make_round_local_runner)
from repro.sim.scenarios import Scenario
from repro.strategies import get_strategy
from repro.training.federated import FLConfig
from repro.training.local import frozen_base


# ---------------------------------------------------------------------------
# Trajectory-parallel Monte-Carlo (shard="mc").
# ---------------------------------------------------------------------------

def _pad_to(x: jnp.ndarray, n: int) -> jnp.ndarray:
    """Pad the leading axis up to ``n`` by repeating the last entry (the
    padded trajectories are real but redundant work, sliced off after the
    gather — a uniform per-device workload beats a ragged one)."""
    short = n - x.shape[0]
    if short <= 0:
        return x
    return jnp.concatenate([x, jnp.broadcast_to(x[-1], (short,) + x.shape[1:])])


def make_sharded_sweep_fn(traj, n_pad: int, rounds: int, mesh,
                          snr_db=None, with_grid: bool = False,
                          telemetry: bool = False):
    """Build the jitted ``shard_map`` sweep over ``n_pad`` flattened
    trajectories (``n_pad`` must divide over the ``mc`` axis).

    Returns ``f(seed_flat[, snr_flat]) -> (loss, acc)`` of shape
    ``(n_pad, rounds)`` each — plus the trajectory-batched
    `RoundTelemetry` when ``telemetry`` (a telemetry-enabled ``traj``
    returns a third element; its out-specs are derived from the traced
    output shapes via ``eval_shape``, leading trajectory dim over
    ``mc``).  Build ONCE and reuse — every call to this factory traces
    and compiles afresh (the bench measures steady-state throughput on
    the returned callable).
    """
    in_spec = trajectory_specs(
        jax.ShapeDtypeStruct((n_pad,), jnp.int32), mesh)
    out_spec = trajectory_specs(
        jax.ShapeDtypeStruct((n_pad, rounds), jnp.float32), mesh)

    # check_vma=False: the body is collective-free (replication checking
    # has nothing to verify) and the fused CWFL pallas_call has no
    # replication rule.
    if with_grid:
        body = lambda s, g: jax.vmap(traj)(s, g)
        in_specs: tuple = (in_spec, in_spec)
        eval_args = (jax.ShapeDtypeStruct((n_pad,), jnp.int32),
                     jax.ShapeDtypeStruct((n_pad,), jnp.float32))
    else:
        # snr_db may be a plain float or None — keep it a closure constant
        # exactly like the vmap path's in_axes=(0, None).
        body = lambda s: jax.vmap(lambda z: traj(z, snr_db))(s)
        in_specs = (in_spec,)
        eval_args = (jax.ShapeDtypeStruct((n_pad,), jnp.int32),)
    if telemetry:
        # Fit specs from the real (loss, acc, telemetry) output pytree —
        # only on the telemetry path, so the untelemetered sweep keeps
        # its hand-built specs (and jaxpr) untouched.
        out_specs = trajectory_specs(jax.eval_shape(body, *eval_args), mesh)
    else:
        out_specs = (out_spec, out_spec)
    return jax.jit(shard_map(
        body, mesh=mesh, in_specs=in_specs,
        out_specs=out_specs, check_vma=False))


def monte_carlo_sharded(traj, seeds: jnp.ndarray, snr_grid, snr_db,
                        rounds: int, mesh=None, telemetry: bool = False,
                        stream=None):
    """Run the flattened seeds × SNR grid under ``shard_map`` on the ``mc``
    mesh axis.

    ``traj`` is the engine's shared per-trajectory closure
    (`engine.make_trajectory_fn`).  Returns ``(loss, acc, grid)`` with the
    same shapes/dtypes as the vmap path: (S, T) when ``snr_grid`` is
    empty, else (S, G, T) in seed-major grid order.  With ``telemetry``
    (``traj`` must be a telemetry-enabled build) the return grows a
    fourth element — the `RoundTelemetry` pytree with (S,[G,]T) leading
    axes, unpadded and grid-reshaped exactly like the metric buffers.

    ``stream``: when ``traj`` carries a stream tap
    (`run_monte_carlo`'s post-scan `stream_trajectory_tap` wrapper —
    unordered, since the tap sits under the per-device vmap), the
    stream is scoped to rank 0's contiguous trajectory chunk by
    ``(seed, snr)`` tag before launch — "rank-0 emit" without a
    trace-time axis name, which would break the `eval_shape` the sweep
    factory uses for telemetry out-specs (``lax.axis_index`` is unbound
    outside the mesh body).
    """
    if mesh is None:
        mesh = make_mc_mesh()
    if "mc" not in mesh.axis_names:
        raise ValueError(
            f"shard='mc' needs a mesh with an ('mc',) axis "
            f"(launch.mesh.make_mc_mesh); got axes {mesh.axis_names}")
    n_dev = dict(mesh.shape)["mc"]
    S = int(seeds.shape[0])

    if snr_grid is not None and len(snr_grid) > 0:
        grid = jnp.asarray(snr_grid, jnp.float32)
        G = int(grid.shape[0])
        # seed-major flattening: pair i = (seed[i // G], grid[i % G]) — the
        # same order vmap(seeds) ∘ vmap(grid) fills (S, G), so the reshape
        # below is a pure relabeling.
        seed_flat = jnp.repeat(seeds, G)
        snr_flat = jnp.tile(grid, S)
    else:
        grid, G = None, 0
        seed_flat = seeds
        snr_flat = None

    n = int(seed_flat.shape[0])
    n_pad = -(-n // n_dev) * n_dev
    seed_flat = _pad_to(seed_flat, n_pad)

    if stream is not None:
        # Rank-0 emit: shard_map splits the flat trajectory axis into
        # contiguous per-device chunks, so rank 0 owns the first
        # n_pad / n_dev trajectories — scope the host stream to their
        # (seed, snr) tags (padding repeats the LAST entry, so rank 0's
        # chunk is all-real whenever it holds any real trajectory).
        chunk = n_pad // n_dev
        seeds_np = np.asarray(seed_flat)[:min(chunk, n)]
        if snr_flat is not None:
            snrs_np = np.asarray(snr_flat)[:min(chunk, n)]
            stream.scope_to_trajectories(zip(seeds_np, snrs_np))
        else:
            snr0 = None if snr_db is None else float(np.float32(snr_db))
            stream.scope_to_trajectories(
                (s, snr0) for s in seeds_np)

    f = make_sharded_sweep_fn(traj, n_pad, rounds, mesh, snr_db=snr_db,
                              with_grid=snr_flat is not None,
                              telemetry=telemetry)
    args = ((seed_flat,) if snr_flat is None
            else (seed_flat, _pad_to(snr_flat, n_pad)))
    if telemetry:
        loss, acc, tele = f(*args)
        tele = jax.tree.map(lambda x: x[:n], tele)
    else:
        loss, acc = f(*args)
    if stream is not None:
        jax.block_until_ready(loss)
        jax.effects_barrier()

    loss, acc = loss[:n], acc[:n]
    if grid is not None:
        loss = loss.reshape(S, G, rounds)
        acc = acc.reshape(S, G, rounds)
        if telemetry:
            tele = jax.tree.map(
                lambda x: x.reshape((S, G) + x.shape[1:]), tele)
    if telemetry:
        return loss, acc, grid, tele
    return loss, acc, grid


# ---------------------------------------------------------------------------
# Client-parallel single trajectory (shard="clients").
# ---------------------------------------------------------------------------

# Extras keys `_client_sharded_sync(with_telemetry=True)` reports (minus
# ``consensus_drift``, which feeds the RoundTelemetry field directly) —
# the shard_map out-spec layout for the telemetry pytree.
_CLIENT_TELE_EXTRAS = ("client_power", "noise_energy", "phase1_noise_std",
                       "phase2_noise_std", "power_budget_frac",
                       "precode_scale", "tx_power")

def _client_sharded_sync(stacked_local, state, key: jax.Array, axis: str,
                         with_telemetry: bool = False):
    """One CWFL sync with the K clients split over ``axis``.

    The K'-clients-per-rank generalization of
    `repro.dist.fl_integration.hierarchical_ota_allreduce`: phase 1's
    per-cluster OTA sums ride ``psum`` (the superposition over clients IS
    the collective), phase 2's (C, C) consensus mix is rank-local, and
    phase 3 applies only this rank's rows of the downlink matrix.  Noise
    streams replicate `cwfl._aggregate_flat`'s per-leaf key schedule with
    shared keys, so every rank sees the identical channel realization and
    the only divergence from the unsharded flat path is the ``psum``'s
    cross-rank re-association (ulp-level; DESIGN.md §Sharded-MC).

    ``with_telemetry`` additionally returns the sync's internals as a
    third element — the same extras dict keys `CWFLStrategy.telemetry`
    reports on the unsharded path, plus ``consensus_drift`` (per-head
    ‖θ̄_c − θ̄‖, already replicated across ranks by the psum).
    """
    leaves, treedef = jax.tree.flatten(stacked_local)
    kl = leaves[0].shape[0]
    C = state.num_clusters
    k1, k2 = jax.random.split(key)

    flat = cwfl._flat_pack(leaves, kl)
    d = flat.shape[1]

    # eq. (5) precoding needs every client's per-channel-use power: gather
    # the (K',) local norms into the global (K,) vector on every rank.
    sq_local = jnp.sum(flat * flat, axis=1)
    mean_sq = jax.lax.all_gather(sq_local, axis, tiled=True) / d
    A, eff_std1, B, kappa, m_back = cwfl.round_coefficients(
        state, None, mean_sq=mean_sq)

    r = jax.lax.axis_index(axis)
    a_loc = jax.lax.dynamic_slice_in_dim(A, r * kl, kl, axis=1)   # (C, K')

    # f32 matmuls on every backend, as in the fused kernel.
    mm = lambda x, y: jnp.matmul(x, y, precision=SYNC_PRECISION)

    # Phase 1 (eq. 8): the OTA MAC — per-cluster sums over all K clients
    # ride the mesh collective; receiver AWGN is shared-key replicated.
    theta_tilde = jax.lax.psum(mm(a_loc, flat), axis)             # (C, d)
    theta_tilde = theta_tilde + cwfl._flat_leaf_noise(
        k1, leaves, C, eff_std1)

    # Phase 2 (eq. 9 / lemma 2): tiny (C, C) mix, rank-local.
    theta_bar = mm(B, theta_tilde) + cwfl._flat_leaf_noise(k2, leaves, C,
                                                          kappa)

    # Phase 3: error-free downlink — this rank's clients only.
    m_loc = jax.lax.dynamic_slice_in_dim(m_back, r * kl, kl, axis=0)
    new_flat = mm(m_loc, theta_bar)                               # (K', d)
    cons_flat = jnp.mean(theta_bar, axis=0)                       # (d,)
    new, cons = cwfl._flat_unpack(new_flat, cons_flat, leaves, treedef, kl)
    if not with_telemetry:
        return new, cons
    pre = cwfl.precode_scale(state, mean_sq)
    member = 1.0 - state.plan.head_mask
    tx_power = (member * (state.client_power / state.total_power)
                * pre**2 * mean_sq)
    extras = {
        "consensus_drift": jnp.sqrt(jnp.sum(
            jnp.square(theta_bar - cons_flat[None, :]), axis=1)),
        "precode_scale": pre,
        "client_power": state.client_power,
        "tx_power": tx_power,
        "power_budget_frac": jnp.sum(tx_power) / state.total_power,
        "phase1_noise_std": eff_std1,
        "phase2_noise_std": kappa,
        "noise_energy": d * (jnp.sum(eff_std1**2) + jnp.sum(kappa**2)),
    }
    return new, cons, extras


def run_rounds_client_sharded(init_fn, apply_fn, loss_fn, topology,
                              xs: jnp.ndarray, ys: jnp.ndarray,
                              x_test: jnp.ndarray, y_test: jnp.ndarray,
                              cfg: FLConfig,
                              scenario: Optional[Scenario] = None,
                              mesh=None,
                              telemetry: bool = False,
                              checkpoint_dir: Optional[str] = None,
                              checkpoint_every: int = 0,
                              resume: bool = False,
                              resume_step: Optional[int] = None,
                              stop_after: Optional[int] = None,
                              stream=None) -> dict[str, Any]:
    """One trajectory with the stacked K-client axis sharded over a
    ``("clients",)`` mesh: per-rank local training (vmap over K/n local
    clients) + the `psum`-riding CWFL sync, scanned over rounds.

    Static CWFL scenarios only — the per-round state rebuilds of dynamic
    scenarios replicate fine, but masking/re-clustering haven't been
    taught the sharded sync yet (raise rather than silently diverge).
    Models over a frozen base (`repro.training.local.FrozenBase`) are
    refused too: this round body closes over the raw ``loss_fn``, which
    would embed the base in the program as a constant.  The carry, the
    key schedule and the round tags come from `engine._build`'s own
    eager ``prepare`` (not a copy), so they track the unsharded path by
    construction; metrics agree to psum-reassociation tolerance.  The
    round body itself is this module's own (DESIGN.md §Sharded-MC).

    ``telemetry=True`` (static flag) emits ``history["telemetry"]`` with
    the same `RoundTelemetry` fields as the unsharded engine: per-cluster
    losses ride one extra tiny ``psum`` (membership-sliced (C, K') @
    local losses), everything else falls out of the sync's own
    replicated internals (`_client_sharded_sync`'s extras).

    ``checkpoint_dir``/``checkpoint_every``/``resume``/``resume_step``/
    ``stop_after``: the jitted ``shard_map`` trajectory has the
    ``fn(carry, scan_xs) -> (carry, out)`` form `engine.run_rounds`
    scans, so the engine's own segment driver
    (`engine._run_scan_checkpointed`) checkpoints it: the full carry
    (sharded param/opt stacks gathered to host, consensus, ledger) is
    persisted at each boundary, manifest-stamped (the manifest's
    strategy field carries an ``@clients`` suffix so sharded and
    unsharded checkpoints — equal only to psum-reassociation ulps —
    can never be spliced).

    ``stream`` (STATIC; turns ``telemetry`` on): a
    `repro.obs.stream.RoundStream` tapped from inside the shard_map'd
    scan body — every rank fires the callback on its replicated round
    values and passes ``lax.axis_index("clients")`` along, and the host
    keeps rank 0 only (effects cannot hide behind a traced `lax.cond`),
    so the stream carries exactly one record per round.  The callback
    is unordered (an ordered effect token inside a jitted shard_map
    aborts XLA's sharding propagation on this toolchain); each record's
    absolute round tag carries the ordering instead.
    """
    scenario = scenario or Scenario()
    telemetry = _run_options(telemetry=telemetry, stream=stream,
                             checkpoint_dir=checkpoint_dir, resume=resume,
                             stop_after=stop_after)
    if not scenario.is_static:
        raise NotImplementedError(
            "shard='clients' supports static scenarios only (dynamic "
            "masking/re-clustering haven't been taught the sharded sync)")
    if frozen_base(loss_fn) is not None:
        raise NotImplementedError(
            "shard='clients' does not take a model over a frozen base "
            "(FrozenBase): its round body would embed the base in the "
            "program as a constant")
    strategy = get_strategy(cfg.strategy)
    if not strategy.supports_client_sharding:
        raise NotImplementedError(
            f"shard='clients' needs a strategy whose sync is implemented "
            f"as a client-axis mesh collective (supports_client_sharding); "
            f"{type(strategy).__name__} (strategy {cfg.strategy!r}) has "
            f"none")
    if mesh is None:
        mesh = make_client_mesh()
    if "clients" not in mesh.axis_names:
        raise ValueError(
            f"shard='clients' needs a mesh with a ('clients',) axis "
            f"(launch.mesh.make_client_mesh); got axes {mesh.axis_names}")
    n_dev = dict(mesh.shape)["clients"]
    K, n_k = int(xs.shape[0]), int(xs.shape[1])
    if K % n_dev:
        raise ValueError(
            f"K={K} clients must divide over the {n_dev}-way clients axis")
    kl = K // n_dev

    # EAGER prepare — the engine's own setup and PRNG schedule; a static
    # scenario's ctx IS the strategy state.  The carry holds stacked,
    # opt, consensus (+ the "obs" ledger with telemetry); the scan inputs
    # hold the round keys (+ the absolute round tags with a stream).
    prepare, _ = _build(init_fn, apply_fn, loss_fn, topology, xs, ys,
                        x_test, y_test, cfg, scenario, None,
                        telemetry=telemetry, stream=stream)
    state0, carry0, scan_xs = prepare(cfg.seed, cfg.snr_db)

    xs, sample_shape = client_rows(xs)
    _, local_run = make_round_local_runner(loss_fn, cfg, n_k, sample_shape)
    x_ev = x_test[: cfg.eval_samples]
    y_ev = y_test[: cfg.eval_samples]

    membership = state0.plan.membership                  # (C, K), static
    counts = jnp.maximum(membership.sum(axis=1), 1.0)
    uses = jnp.asarray(
        strategy.channel_uses(K, num_clusters=cfg.num_clusters),
        jnp.float32)

    def traj(carry, seg, xs_l, ys_l):
        r = jax.lax.axis_index("clients")

        def body(carry, inp):
            carry = dict(carry)
            k_local, k_agg = jax.random.split(inp["rkey"])
            client_keys = jax.random.split(k_local, K)   # global schedule
            ck = jax.lax.dynamic_slice_in_dim(client_keys, r * kl, kl)
            st, opt, losses = jax.vmap(local_run)(
                carry["stacked"], carry["opt"], xs_l, ys_l, ck)
            if telemetry:
                new, consensus, extras = _client_sharded_sync(
                    st, state0, k_agg, "clients", with_telemetry=True)
            else:
                new, consensus = _client_sharded_sync(st, state0, k_agg,
                                                      "clients")
            loss = jax.lax.psum(jnp.sum(losses), "clients") / K
            logits = apply_fn(consensus, x_ev)
            acc = _accuracy(logits, y_ev)
            carry.update(stacked=new, opt=opt, consensus=consensus)
            if not telemetry:
                return carry, (loss, acc)
            mem_loc = jax.lax.dynamic_slice_in_dim(membership, r * kl, kl,
                                                   axis=1)     # (C, K')
            # Fresh full-shard losses for telemetry — reading the
            # minibatch `losses` again would re-fuse its psum-mean and
            # perturb the reported train_loss by ulps (same contract as
            # the unsharded engine body).
            tele_losses = jax.vmap(loss_fn)(
                st, xs_l.reshape(xs_l.shape[:2] + sample_shape), ys_l)
            cluster_loss = jax.lax.psum(mem_loc @ tele_losses,
                                        "clients") / counts
            d = per_client_dim(st)
            ledger = carry["obs"]
            carry["obs"] = {"uses": ledger["uses"] + uses,
                            "symbols": ledger["symbols"] + uses * d}
            tele = RoundTelemetry(
                cluster_loss=cluster_loss,
                participants=jnp.asarray(K, jnp.float32),
                consensus_drift=extras.pop("consensus_drift"),
                channel_uses=uses,
                cum_channel_uses=carry["obs"]["uses"],
                cum_symbols=carry["obs"]["symbols"],
                reclustered=jnp.zeros((), jnp.float32),
                extras=extras)
            if stream is not None:
                # In-body tap on replicated round values; the axis index
                # rides the payload and the host drops ranks != 0.
                # UNORDERED: an ordered effect token inside a jitted
                # shard_map trips XLA's sharding-propagation parameter
                # check (hard abort at compile time on this toolchain) —
                # the absolute round tag in the payload carries the
                # ordering instead, and consumers sort by it.
                from repro.obs.stream import stream_tap
                tags = inp["stream"]
                stream_tap(stream, t=tags["t"], seed=tags["seed"],
                           snr=tags["snr"], loss=loss, acc=acc,
                           telemetry=tele, rank=r, ordered=False)
            return carry, (loss, acc, tele)

        return jax.lax.scan(body, carry, seg, unroll=_SCAN_UNROLL)

    # Specs come from the dist rules layer: leading K over "clients" for
    # every stacked leaf, replication for everything per-rank identical.
    k_spec = lambda tree: client_specs(jax.eval_shape(lambda t: t, tree),
                                       mesh)
    rep = lambda tree: jax.tree.map(lambda _: P(), tree)
    carry_spec = rep(carry0)
    carry_spec.update(stacked=k_spec(carry0["stacked"]),
                      opt=k_spec(carry0["opt"]))
    out_spec: tuple = (P(), P())
    if telemetry:
        # Every telemetry value is psum-replicated or a rank-constant —
        # all-P() specs, keyed off the known extras layout.
        out_spec = out_spec + (RoundTelemetry(
            cluster_loss=P(), participants=P(), consensus_drift=P(),
            channel_uses=P(), cum_channel_uses=P(), cum_symbols=P(),
            reclustered=P(),
            extras={k: P() for k in _CLIENT_TELE_EXTRAS}),)
    fj = jax.jit(shard_map(
        traj, mesh=mesh,
        in_specs=(carry_spec, rep(scan_xs), P("clients"), P("clients")),
        out_specs=(carry_spec, out_spec),
        check_vma=False))  # scan+psum bodies defeat the replication checker
    fn = lambda carry, seg: fj(carry, seg, xs, ys)

    if checkpoint_dir is not None:
        # "@clients" keys the manifest hash: sharded and unsharded
        # histories agree only to psum-reassociation ulps — never splice
        # them.
        carry, out, _ = _run_scan_checkpointed(
            fn, carry0, scan_xs, cfg.rounds, checkpoint_dir,
            checkpoint_every, resume=resume, resume_step=resume_step,
            stop_after=stop_after, cfg=cfg, scenario=scenario,
            strategy_name=strategy.name + "@clients", stream=stream)
    else:
        carry, out = fn(carry0, scan_xs)
    if stream is not None:
        jax.block_until_ready(out)
        jax.effects_barrier()
    return _trajectory_history(out, carry["consensus"], telemetry)
