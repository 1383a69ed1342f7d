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
from repro.obs.telemetry import RoundTelemetry, init_ledger, per_client_dim
from repro.sim.engine import (_SCAN_UNROLL, client_rows,
                              make_round_local_runner)
from repro.sim.scenarios import Scenario
from repro.strategies import get_strategy
from repro.training.federated import FLConfig


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
    The carry and key schedule come from `engine._build`'s own eager
    ``prepare`` (not a copy), so they track the unsharded path by
    construction; metrics agree to psum-reassociation tolerance.

    ``telemetry=True`` (static flag) emits ``history["telemetry"]`` with
    the same `RoundTelemetry` fields as the unsharded engine: per-cluster
    losses ride one extra tiny ``psum`` (membership-sliced (C, K') @
    local losses), everything else falls out of the sync's own
    replicated internals (`_client_sharded_sync`'s extras).

    ``checkpoint_dir``/``checkpoint_every``/``resume``/``resume_step``/
    ``stop_after``: chunked checkpoint/resume with the same contract as
    `engine.run_rounds` — the scan is split into segments and the full
    carry (sharded param/opt stacks gathered to host, consensus, ledger)
    is persisted at each boundary, manifest-stamped (the manifest's
    strategy field carries an ``@clients`` suffix so sharded and
    unsharded checkpoints — equal only to psum-reassociation ulps —
    can never be spliced).  With checkpointing off the traced
    computation is byte-identical to before (static-flag discipline).

    ``stream`` (STATIC, needs ``telemetry=True``): a
    `repro.obs.stream.RoundStream` tapped from inside the shard_map'd
    scan body — every rank fires the callback on its replicated round
    values and passes ``lax.axis_index("clients")`` along, and the host
    keeps rank 0 only (effects cannot hide behind a traced `lax.cond`),
    so the stream carries exactly one record per round.  The callback
    is unordered (an ordered effect token inside a jitted shard_map
    aborts XLA's sharding propagation on this toolchain); each record's
    absolute round tag carries the ordering instead.
    """
    from repro.sim.engine import _build, checkpoint_manifest

    scenario = scenario or Scenario()
    ckpt = checkpoint_dir is not None
    streaming = stream is not None
    if not ckpt and (resume or stop_after is not None):
        raise ValueError(
            "resume/stop_after need checkpoint_dir — there is nothing to "
            "restore from or checkpoint into")
    if streaming:
        if not telemetry:
            raise ValueError(
                "stream= drains RoundTelemetry live and needs "
                "telemetry=True")
        if stream.escalates and not ckpt:
            raise ValueError(
                "abort-on-alert escalates via the checkpoint machinery "
                "(checkpoint-then-stop, resumable); pass checkpoint_dir")
    if not scenario.is_static:
        raise NotImplementedError(
            "shard='clients' supports static scenarios only (dynamic "
            "masking/re-clustering haven't been taught the sharded sync)")
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
    T = cfg.rounds

    # EAGER prepare — the engine's own (bit-identity-protected) setup and
    # PRNG schedule; a static scenario's ctx IS the strategy state.
    prepare, _ = _build(init_fn, apply_fn, loss_fn, topology, xs, ys,
                        x_test, y_test, cfg, scenario, None)
    state0, carry0, scan_xs = prepare(cfg.seed, cfg.snr_db)
    stacked, opt_state = carry0["stacked"], carry0["opt"]
    params0 = carry0["consensus"]
    round_keys = scan_xs["rkey"]

    xs, sample_shape = client_rows(xs)
    _, local_run = make_round_local_runner(loss_fn, cfg, n_k, sample_shape)
    x_ev = x_test[: cfg.eval_samples]
    y_ev = y_test[: cfg.eval_samples]

    membership = state0.plan.membership                  # (C, K), static
    counts = jnp.maximum(membership.sum(axis=1), 1.0)
    uses = jnp.asarray(
        strategy.channel_uses(K, num_clusters=cfg.num_clusters),
        jnp.float32)

    def traj(stacked0, opt0, cons0, xs_l, ys_l, rkeys, *extra):
        # extra = ([sts] when streaming) + ([ledger0] on the checkpointed
        # telemetry path) — absolute round indices for the stream tap
        # (sliced alongside rkeys by the segment driver, so a resumed
        # stream keeps absolute rounds) and the cumulative channel-use
        # ledger that must survive a resume.
        extra = list(extra)
        sts = extra.pop(0) if streaming else None
        r = jax.lax.axis_index("clients")

        def body(carry, inp):
            if streaming:
                rkey, st_t = inp
            else:
                rkey, st_t = inp, None
            if telemetry:
                st, opt, _, ledger = carry
            else:
                st, opt, _ = carry
            k_local, k_agg = jax.random.split(rkey)
            client_keys = jax.random.split(k_local, K)   # global schedule
            ck = jax.lax.dynamic_slice_in_dim(client_keys, r * kl, kl)
            st, opt, losses = jax.vmap(local_run)(st, opt, xs_l, ys_l, ck)
            if telemetry:
                new, consensus, extras = _client_sharded_sync(
                    st, state0, k_agg, "clients", with_telemetry=True)
            else:
                new, consensus = _client_sharded_sync(st, state0, k_agg,
                                                      "clients")
            loss = jax.lax.psum(jnp.sum(losses), "clients") / K
            logits = apply_fn(consensus, x_ev)
            acc = _accuracy(logits, y_ev)
            if not telemetry:
                return (new, opt, consensus), (loss, acc)
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
            new_ledger = {"uses": ledger["uses"] + uses,
                          "symbols": ledger["symbols"] + uses * d}
            tele = RoundTelemetry(
                cluster_loss=cluster_loss,
                participants=jnp.asarray(K, jnp.float32),
                consensus_drift=extras.pop("consensus_drift"),
                channel_uses=uses,
                cum_channel_uses=new_ledger["uses"],
                cum_symbols=new_ledger["symbols"],
                reclustered=jnp.zeros((), jnp.float32),
                extras=extras)
            if streaming:
                # In-body tap on replicated round values; the axis index
                # rides the payload and the host drops ranks != 0.
                # UNORDERED: an ordered effect token inside a jitted
                # shard_map trips XLA's sharding-propagation parameter
                # check (hard abort at compile time on this toolchain) —
                # the absolute round tag in the payload carries the
                # ordering instead, and consumers sort by it.
                from repro.obs.stream import stream_tap
                stream_tap(stream, t=st_t, seed=cfg.seed, snr=cfg.snr_db,
                           loss=loss, acc=acc, telemetry=tele, rank=r,
                           ordered=False)
            return (new, opt, consensus, new_ledger), (loss, acc, tele)

        xs_scan = (rkeys, sts) if streaming else rkeys
        if telemetry:
            ledger0 = extra.pop(0) if ckpt else init_ledger()
            (st_f, opt_f, final, ledger_f), out = jax.lax.scan(
                body, (stacked0, opt0, cons0, ledger0), xs_scan,
                unroll=_SCAN_UNROLL)
            loss, acc, tele = out
            if ckpt:
                return loss, acc, final, tele, st_f, opt_f, ledger_f
            return loss, acc, final, tele
        (st_f, opt_f, final), (loss, acc) = jax.lax.scan(
            body, (stacked0, opt0, cons0), xs_scan, unroll=_SCAN_UNROLL)
        if ckpt:
            return loss, acc, final, st_f, opt_f
        return loss, acc, final

    # Specs come from the dist rules layer: leading K over "clients" for
    # every stacked leaf, replication for everything per-rank identical.
    k_spec = lambda tree: client_specs(jax.eval_shape(lambda t: t, tree),
                                       mesh)
    rep = lambda tree: jax.tree.map(lambda _: P(), tree)
    ledger0 = init_ledger() if telemetry else None
    sts_full = jnp.arange(T, dtype=jnp.int32) if streaming else None
    in_specs: tuple = (k_spec(stacked), k_spec(opt_state), rep(params0),
                       P("clients"), P("clients"), P())
    if streaming:
        in_specs = in_specs + (P(),)          # sts: replicated round tags
    out_specs: tuple = (P(), P(), rep(params0))
    if telemetry:
        # Every telemetry value is psum-replicated or a rank-constant —
        # all-P() specs, keyed off the known extras layout.
        tele_spec = RoundTelemetry(
            cluster_loss=P(), participants=P(), consensus_drift=P(),
            channel_uses=P(), cum_channel_uses=P(), cum_symbols=P(),
            reclustered=P(),
            extras={k: P() for k in _CLIENT_TELE_EXTRAS})
        out_specs = out_specs + (tele_spec,)
    if ckpt:
        out_specs = out_specs + (k_spec(stacked), k_spec(opt_state))
        if telemetry:
            in_specs = in_specs + (rep(ledger0),)
            out_specs = out_specs + (rep(ledger0),)
    f = shard_map(
        traj, mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False)   # scan+psum bodies defeat the replication checker
    fj = jax.jit(f)

    tele = None
    if not ckpt:
        args = (stacked, opt_state, params0, xs, ys, round_keys)
        if streaming:
            args = args + (sts_full,)
        out = fj(*args)
        if streaming:
            jax.block_until_ready(out)
            jax.effects_barrier()
        if telemetry:
            loss, acc, consensus, tele = out
        else:
            loss, acc, consensus = out
    else:
        loss, acc, consensus, tele = _client_sharded_checkpointed(
            fj, stacked, opt_state, params0, ledger0, xs, ys, round_keys,
            T, cfg, scenario, strategy, telemetry=telemetry,
            checkpoint_dir=checkpoint_dir, checkpoint_every=checkpoint_every,
            resume=resume, resume_step=resume_step, stop_after=stop_after,
            manifest_fn=checkpoint_manifest, stream=stream,
            sts_full=sts_full)

    history = {
        "round": np.arange(1, int(loss.shape[0]) + 1),
        "train_loss": loss,
        "test_acc": acc,
        "final_params": consensus,
        "avg_acc": jnp.mean(acc),
        "final_acc": acc[-1],
    }
    if telemetry:
        history["telemetry"] = tele
    return history


def _client_sharded_checkpointed(fj, stacked, opt_state, params0, ledger0,
                                 xs, ys, round_keys, T: int, cfg, scenario,
                                 strategy, *, telemetry: bool,
                                 checkpoint_dir, checkpoint_every: int,
                                 resume: bool, resume_step, stop_after,
                                 manifest_fn, stream=None, sts_full=None):
    """Segment driver for the checkpointed client-sharded trajectory —
    the `engine._run_scan_checkpointed` contract on the shard_map path:
    run ``checkpoint_every``-round chunks, persist the full carry +
    accumulated metrics at each boundary, restore and continue on
    ``resume`` (bitwise — the chunked scan is the same per-round body).
    """
    from pathlib import Path

    from repro.checkpoint import (latest_step, load_checkpoint,
                                  save_checkpoint)

    directory = Path(checkpoint_dir)
    every = (T if checkpoint_every is None or int(checkpoint_every) <= 0
             else min(int(checkpoint_every), T))
    # "@clients" keys the manifest hash: sharded and unsharded histories
    # agree only to psum-reassociation ulps — never splice them.
    manifest_fn(directory, cfg, scenario, strategy.name + "@clients",
                resume)

    streaming = stream is not None

    def call(st, opt, cons, ld, keys, sts_seg):
        args = (st, opt, cons, xs, ys, keys)
        if streaming:
            args = args + (sts_seg,)
        if telemetry:
            args = args + (ld,)
        return fj(*args)

    def out_template(n):
        # Abstract-evaluate the jitted shard_map fn for an n-round chunk:
        # the (loss, acc[, telemetry]) accumulator template for resume.
        args = (stacked, opt_state, params0, xs, ys, round_keys[:n])
        if streaming:
            args = args + (sts_full[:n],)
        if telemetry:
            args = args + (ledger0,)
        shapes = jax.eval_shape(fj, *args)
        sub = ((shapes[0], shapes[1], shapes[3]) if telemetry
               else (shapes[0], shapes[1]))
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), sub)

    st, opt, cons, ld = stacked, opt_state, params0, ledger0
    start, acc_out = 0, None
    if resume:
        step = (resume_step if resume_step is not None
                else latest_step(directory))
        if step is None:
            raise FileNotFoundError(
                f"resume: no checkpoint steps in {directory}")
        if not 0 < step <= T:
            raise ValueError(
                f"resume: checkpoint step {step} outside this run's "
                f"1..{T} round range")
        template = {"stacked": stacked, "opt": opt_state,
                    "consensus": params0, "out": out_template(step)}
        if telemetry:
            template["ledger"] = ledger0
        payload = load_checkpoint(directory, template, step=step)
        st, opt, cons = (payload["stacked"], payload["opt"],
                         payload["consensus"])
        ld = payload.get("ledger", ledger0)
        acc_out, start = payload["out"], int(step)

    pos = start
    while pos < T:
        end = min(pos + every, T)
        res = call(st, opt, cons, ld, round_keys[pos:end],
                   sts_full[pos:end] if streaming else None)
        if telemetry:
            loss_s, acc_s, cons, tele_s, st, opt, ld = res
            seg = (loss_s, acc_s, tele_s)
        else:
            loss_s, acc_s, cons, st, opt = res
            seg = (loss_s, acc_s)
        acc_out = seg if acc_out is None else jax.tree.map(
            lambda a, b: jnp.concatenate([a, b], axis=0), acc_out, seg)
        pos = end
        payload = {"stacked": st, "opt": opt, "consensus": cons,
                   "out": acc_out}
        if telemetry:
            payload["ledger"] = ld
        save_checkpoint(directory, pos, payload)
        if stop_after is not None and pos >= int(stop_after) and pos < T:
            break
        if streaming:
            jax.effects_barrier()   # drain the segment before polling
            if stream.should_abort and pos < T:
                break

    if telemetry:
        return acc_out[0], acc_out[1], cons, acc_out[2]
    return acc_out[0], acc_out[1], cons, None
