"""The scanned Monte-Carlo round engine (DESIGN.md §Sim).

The engine runs the *whole trajectory* as a single ``lax.scan`` — T
rounds on device, per-round loss/accuracy accumulated in on-device scan
outputs — and is vmap-able over seeds and scenario scalars, so an
8-seed × SNR-grid Monte-Carlo sweep compiles to exactly one jit.

Round body:

    local:  E epochs of minibatch SGD per client   (vmap over K)
    sync:   strategy aggregation — CWFL routes through the fused
            `repro.kernels.cwfl_round` Pallas fast path via
            ``cwfl.aggregate``'s flatten-once auto-route
    eval:   consensus accuracy on the held-out set (on device)

Scenario hooks (all `lax.scan`-carried, nothing touches the host):

* time-varying channels  → per-round ``Strategy.state_from_view``
  rebuilds (`repro.strategies`) from the `repro.sim.processes` channel
  view;
* client scheduling      → participation masks folded into the round
  coefficients (mask-aware renormalization) on the transmit side, and a
  keep-local-params ``where`` on the receive side;
* cluster churn          → periodic on-device re-clustering
  (``lax.cond``-gated K-means + head election inside the scan body).

The trajectory's bits under the ``paper-static`` scenario are pinned by
the committed goldens (``tests/goldens``, replayed by
``tests/test_goldens.py``).
"""
from __future__ import annotations

import json
import warnings
from pathlib import Path
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import channel as ch
from repro.core.topology import Topology, TopologyConfig
from repro.models.small import accuracy as _accuracy
from repro.obs.profiling import SCOPE_EVAL, SCOPE_LOCAL, SCOPE_SYNC
from repro.obs.telemetry import build_round_telemetry, init_ledger
from repro.optim import sgd
from repro.sim.faults import init_faults, quarantine_mask, step_faults
from repro.sim.processes import (ChannelView, channel_view, csi_perturbation,
                                 init_channel, step_channel)
from repro.sim.scenarios import Scenario
from repro.sim.scheduling import init_schedule, participation_mask
from repro.strategies import get_strategy
from repro.training.federated import FLConfig
from repro.training.local import bind_frozen, frozen_base, make_local_runner

# fold_in salt separating the scenario-process key stream (channel, masks,
# CSI, re-clustering) from the paper's training stream, so adding a
# scenario process never moves the training keys.
_SIM_SALT = 0x51B

# lax.scan unroll for the round loop.  The chip programs recorded in
# PERF_LEDGER.jsonl were measured at unroll=2 (the scan body is compiled
# twice, so every device op of a round appears under two names); changing
# it changes the compiled programs, and so it is a speed question, not a
# matter of correctness.
_SCAN_UNROLL = 2


def client_rows(xs) -> tuple[jnp.ndarray, tuple[int, ...]]:
    """``(K, n_k, *sample)`` client shards as ``(K, n_k, F)`` rows, and the
    sample shape.  Call it on the concrete data, outside any trace, so
    the shards the round program embeds are row-major and the minibatch
    draw is a row gather (`repro.training.local.make_local_runner`'s
    layout contract); reshaped inside a trace, the layout of the
    embedded shards is left to XLA's constant folding."""
    return jnp.reshape(xs, tuple(xs.shape[:2]) + (-1,)), tuple(xs.shape[2:])


def make_round_local_runner(loss_fn: Callable, cfg: FLConfig, n_k: int,
                            sample_shape: tuple[int, ...]):
    """The per-round local-training runner exactly as the engine builds
    it: E epochs of minibatch SGD over a client's ``n_k`` examples, held
    as flat rows of samples shaped ``sample_shape`` (`client_rows`).
    Returns ``(optimizer, local_run)``; `repro.sim.sharded` reuses this
    so the sharded trajectory can never drift from the engine's step
    budget or optimizer construction.

    The FedProx µ_p resolves through the strategy (prox variants such as
    ``cwfl_prox`` carry the paper's default; an explicit
    ``cfg.mu_prox > 0`` overrides it) — `repro.training.local.
    fedprox_wrap` then wires the proximal local objective in."""
    strategy = get_strategy(cfg.strategy)
    optimizer = sgd(cfg.lr)
    steps_per_round = max(cfg.local_epochs * (n_k // cfg.batch_size), 1)
    return optimizer, make_local_runner(
        loss_fn, optimizer, cfg.batch_size, steps_per_round,
        strategy.effective_mu_prox(cfg.mu_prox), sample_shape=sample_shape)


def _tree_where(mask: jnp.ndarray, a, b):
    """Per-leaf ``where(mask_k, a_k, b_k)`` over K-stacked pytrees."""
    def pick(x, y):
        m = mask.reshape((mask.shape[0],) + (1,) * (x.ndim - 1))
        return jnp.where(m > 0, x, y)
    return jax.tree.map(pick, a, b)


def _build(init_fn: Callable, apply_fn: Callable, loss_fn: Callable,
           topology: Topology, xs: jnp.ndarray, ys: jnp.ndarray,
           x_test: jnp.ndarray, y_test: jnp.ndarray, cfg: FLConfig,
           scenario: Scenario, topo_cfg: Optional[TopologyConfig],
           telemetry: bool = False, stream=None):
    """Returns ``(prepare, make_body)``: ``prepare(seed, snr_db) -> (ctx,
    carry, scan_xs)`` builds the per-trajectory context, the scan carry
    and the per-round inputs; ``make_body(ctx)`` is the round function
    over them.  Both are pure jnp: `run_rounds` runs `prepare` eagerly
    and jits the scan, a Monte-Carlo sweep traces both under vmap.

    ``telemetry`` is a STATIC python flag: when False the carry, scan
    outputs, and every traced op are exactly the untelemetered build.  When
    True the carry grows a cumulative channel-use ledger (``"obs"``) and
    ``body`` emits a third `RoundTelemetry` scan output assembled from
    intermediates the round already computes (`repro.obs.telemetry`).

    ``stream`` (STATIC; callers turn ``telemetry`` on with it,
    `_run_options`) is an optional `repro.obs.stream.RoundStream`: the
    scan inputs grow an absolute ``(t, seed, snr)`` tag triple and the
    body ends with one ORDERED `io_callback` draining the round's
    already-computed metrics + telemetry to the host
    (`repro.obs.stream.stream_tap`) — no new arithmetic, so streamed
    metrics stay bitwise.  Unbatched bodies only: Monte-Carlo sweeps
    must NOT pass ``stream`` here (in-body taps break under vmap) —
    `run_monte_carlo` wraps the trajectory with the post-scan
    `stream_trajectory_tap` instead."""
    strategy = get_strategy(cfg.strategy)
    if scenario.strategy is not None and scenario.strategy != strategy.name:
        # The scenario pins a preferred strategy (resolved by CLIs when no
        # explicit choice is given) but FLConfig.strategy always wins in
        # the engine — since the config default is indistinguishable from
        # an explicit choice, the override must at least be loud.
        warnings.warn(
            f"scenario {scenario.name!r} pins strategy "
            f"{scenario.strategy!r} but the run uses cfg.strategy="
            f"{strategy.name!r}; pass FLConfig(strategy="
            f"{scenario.strategy!r}) to honor the scenario's pin",
            UserWarning, stacklevel=3)

    K, n_k = xs.shape[0], xs.shape[1]
    xs, sample_shape = client_rows(xs)
    static = scenario.is_static
    dyn_chan = scenario.channel.evolves_geometry  # CSI-only needs no geometry
    masked = not scenario.schedule.is_trivial
    faulty = not scenario.faults.is_trivial       # STATIC flag, like telemetry
    fcfg = scenario.faults
    recluster = scenario.recluster_every
    total_power = float(topology.total_power)
    if dyn_chan and topo_cfg is None:
        raise ValueError(
            "dynamic-channel scenarios need the TopologyConfig that "
            "generated the topology (geometry statics: area, d0, ς, "
            "outage threshold)")

    optimizer = make_round_local_runner(loss_fn, cfg, n_k, sample_shape)[0]
    # A model over a frozen base (`repro.training.local.FrozenBase`): the
    # base rides the carry, so the round program takes it as an argument
    # (a closed-over base would be embedded as an HLO constant); the
    # clients' vmap closes over it unbatched.  None for every other model,
    # whose round is traced exactly as before.
    frozen = frozen_base(loss_fn)
    loss_and_load = getattr(loss_fn, "with_routed_load", None)
    x_ev = x_test[: cfg.eval_samples]
    y_ev = y_test[: cfg.eval_samples]

    def prepare(seed, snr_db):
        key = jax.random.PRNGKey(seed)
        k_state, k_init, k_rounds = jax.random.split(key, 3)
        state0 = strategy.init(topology, k_state, cfg, snr_db=snr_db)
        params0 = init_fn(k_init)
        stacked = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (K,) + x.shape), params0)
        opt_state = jax.vmap(optimizer.init)(stacked)
        round_keys = jax.random.split(k_rounds, cfg.rounds)

        carry = {"stacked": stacked, "opt": opt_state, "consensus": params0}
        if frozen is not None:
            carry["frozen"] = frozen
        if telemetry:
            carry["obs"] = init_ledger()
        scan_xs = {"rkey": round_keys}
        if stream is not None:
            # Absolute round tags for the live tap.  These are scan
            # INPUTS (not carried state) so the checkpoint driver's
            # sliced(lo, hi) hands resumed segments their true absolute
            # round indices and the stream continues seamlessly.
            snr_tag = (jnp.full((), jnp.nan, jnp.float32) if snr_db is None
                       else jnp.asarray(snr_db, jnp.float32))
            scan_xs["stream"] = {
                "t": jnp.arange(cfg.rounds, dtype=jnp.int32),
                "seed": jnp.broadcast_to(jnp.asarray(seed, jnp.int32),
                                         (cfg.rounds,)),
                "snr": jnp.broadcast_to(snr_tag, (cfg.rounds,)),
            }
        if not static:
            scan_xs["skey"] = jax.random.split(
                jax.random.fold_in(key, _SIM_SALT), cfg.rounds)
            scan_xs["t"] = jnp.arange(cfg.rounds)
            nv = (topology.noise_var if snr_db is None
                  else ch.snr_db_to_noise_var(total_power, snr_db))
            if masked:
                carry["sched"] = init_schedule(scenario.schedule, K)
            if faulty:
                carry["faults"] = init_faults(fcfg, K)
            if dyn_chan:
                carry["chan"] = init_channel(
                    topology, topo_cfg, jax.random.fold_in(key, _SIM_SALT + 1))
            if strategy.reclusters and recluster > 0:
                carry["plan"] = state0.plan
            state0 = (state0, jnp.asarray(nv, jnp.float32))
        return state0, carry, scan_xs

    def make_body(ctx):
        """Bind the per-trajectory context (strategy state; + noise var in
        dynamic mode) into the round body as a CLOSURE.  With `run_rounds`'
        eager `prepare` the round compiles with the state embedded as
        constants: the chip programs recorded in PERF_LEDGER.jsonl were
        measured that way, and passing the state as an argument instead
        changes XLA's fusion, so it is a speed question (and moves the
        goldens by ulps).
        """
        if static:
            state0, nv = ctx, None
        else:
            state0, nv = ctx

        def dynamic_sync(carry, stacked, inp, k_agg):
            """One scenario-aware sync: channel step → fault step →
            state rebuild → masked aggregation.  Mutates ``carry`` (a
            per-round copy).  Returns ``(new, consensus, state, mask,
            reclustered, fault_extras)`` — the trailing four feed the
            telemetry hook and are plain Python ``None``s (no extra
            traced ops) when unused."""
            t = inp["t"]
            if faulty:
                (k_chan, k_csi, k_mask, k_cluster, k_fault,
                 k_handoff) = jax.random.split(inp["skey"], 6)
            else:
                k_chan, k_csi, k_mask, k_cluster = jax.random.split(
                    inp["skey"], 4)

            if dyn_chan:
                chan = step_channel(carry["chan"], scenario.channel, topo_cfg,
                                    k_chan)
                carry["chan"] = chan
                view = channel_view(chan, topo_cfg)
            else:
                view = ChannelView(link_gain=topology.link_gain,
                                   link_snr=topology.link_snr,
                                   adjacency=topology.adjacency)

            mask = None
            if masked:
                mask, carry["sched"] = participation_mask(
                    scenario.schedule, carry["sched"], t, k_mask, K)

            alive = None
            fault_extras = None
            if faulty:
                # Fault plane (repro.sim.faults): advance the crash /
                # burst / blackout chains, fold transmit outages into the
                # participation mask (same renormalization path as
                # scheduling absences), and quarantine poisoned client
                # updates BEFORE they can touch a MAC matmul — a
                # quarantined client transmits nothing and keeps its own
                # pre-round params (0 × NaN = NaN, so masking alone
                # cannot contain a non-finite update).
                carry["faults"], fview = step_faults(carry["faults"], fcfg,
                                                     k_fault)
                alive = fview.alive
                mask = (fview.tx_ok if mask is None
                        else mask * fview.tx_ok)
                q = None
                if fcfg.divergence_guard:
                    q = quarantine_mask(stacked, fcfg.quarantine_norm)
                    stacked = _tree_where(q, stacked, carry["stacked"])
                    mask = mask * q
                if telemetry:
                    fault_extras = {
                        "alive": alive,
                        "tx_ok": fview.tx_ok,
                        "burst": fview.burst,
                        "deep_fade": fview.deep_fade,
                        "quarantined": (jnp.zeros((), jnp.float32)
                                        if q is None else jnp.sum(1.0 - q)),
                    }
            # Imperfect CSI hits every strategy that water-fills power
            # from channel estimates (CWFL member→head, COTAF →server).
            csi = (csi_perturbation(k_csi, K, scenario.channel.csi_error_std)
                   if (strategy.water_fills
                       and scenario.channel.csi_error_std > 0) else None)

            plan = None
            reclustered = None
            if strategy.reclusters and recluster > 0:
                fire = (t % recluster) == 0
                plan = jax.lax.cond(
                    fire,
                    lambda: strategy.recluster(view, cfg.num_clusters,
                                               k_cluster),
                    lambda: carry["plan"])
                carry["plan"] = plan
                if telemetry:
                    reclustered = fire

            if faulty:
                # Infrastructure handoff (stateless — derived fresh each
                # round, so a recovered head/server resumes on its own):
                # CWFL re-elects dead cluster-heads; strategies without a
                # plan pass through.  The re-elected plan deliberately
                # does NOT go back into carry["plan"].
                plan = strategy.on_head_failure(state0, plan, view, alive,
                                                k_handoff)

            state = strategy.state_from_view(state0, view, nv, csi=csi,
                                             mask=mask, plan=plan,
                                             alive=alive)
            new, consensus = strategy.aggregate(stacked, state, k_agg,
                                                mask=mask, alive=alive)

            recv = (strategy.receive_mask(state, mask, alive=alive)
                    if mask is not None else None)
            if recv is not None:
                # Receive side: absent clients keep their locally-trained
                # params (no downlink for a client out of the round) while
                # forced-present receivers (heads/server) keep the
                # aggregate they hold; if NOBODY participated the sync is
                # skipped and the previous consensus stands (also swallows
                # fedavg's 0/0 weights).  A ``None`` recv means the
                # aggregate already encodes absences (decentralized's
                # pruned graph) — no fold at all.
                present = jnp.sum(mask) > 0
                new = _tree_where(recv * present, new, stacked)
                consensus = jax.tree.map(
                    lambda n, o: jnp.where(present, n, o),
                    consensus, carry["consensus"])
            return new, consensus, state, mask, reclustered, fault_extras

        def body(carry, inp):
            carry = dict(carry)
            base = carry.get("frozen")
            loss_b, apply_b = (bind_frozen(loss_fn, base),
                               bind_frozen(apply_fn, base))
            run = make_round_local_runner(loss_b, cfg, n_k, sample_shape)[1]
            k_local, k_agg = jax.random.split(inp["rkey"])
            client_keys = jax.random.split(k_local, K)
            with jax.named_scope(SCOPE_LOCAL):
                trained, opt_state, losses = jax.vmap(run)(
                    carry["stacked"], carry["opt"], xs, ys, client_keys)
            with jax.named_scope(SCOPE_SYNC):
                if static:
                    stacked, consensus = strategy.aggregate(trained, state0,
                                                            k_agg)
                    state, mask, reclustered, fault_extras = (state0, None,
                                                              None, None)
                else:
                    (stacked, consensus, state, mask, reclustered,
                     fault_extras) = dynamic_sync(carry, trained, inp, k_agg)
            with jax.named_scope(SCOPE_EVAL):
                logits = apply_b(consensus, x_ev)
                acc = _accuracy(logits, y_ev)
            carry.update(stacked=stacked, opt=opt_state, consensus=consensus)
            if not telemetry:
                return carry, (jnp.mean(losses), acc)
            # Telemetry losses are a FRESH full-shard forward pass on the
            # locally-trained params — NOT the minibatch `losses` above.
            # Any reduction over `losses` other than the round's own
            # jnp.mean (which CSEs with it) gives the buffer a second
            # consumer, un-fuses the mean from the training loop, and
            # perturbs the reported train_loss by ulps; `trained` is
            # already materialized (it feeds the sync), so reading it is
            # bit-neutral.  Full-batch per-client loss is also the better
            # observable: deterministic, minibatch-noise-free.
            shards = xs.reshape((K, n_k) + sample_shape)
            load = None
            if loss_and_load is None:
                tele_losses = jax.vmap(loss_b)(trained, shards, ys)
            else:
                # The same pass also counts the (token, expert) pairs on
                # each held expert, summed over the clients.
                tele_losses, load = jax.vmap(bind_frozen(
                    loss_and_load, base))(trained, shards, ys)
                load = jnp.sum(load, axis=0)
            tele, carry["obs"] = build_round_telemetry(
                strategy, state, losses=tele_losses, stacked=trained,
                new_stacked=stacked, consensus=consensus, mask=mask,
                num_clients=K, num_clusters=cfg.num_clusters,
                ledger=carry["obs"], reclustered=reclustered,
                fault_extras=fault_extras, routed_load=load)
            train_loss = jnp.mean(losses)
            if stream is not None:
                # Live tap: operands are the values this round already
                # computed — the tap adds an effect, never an equation
                # (stream-on metrics stay bitwise; pinned by
                # tests/test_stream.py).
                from repro.obs.stream import stream_tap
                stream_tap(stream, t=inp["stream"]["t"],
                           seed=inp["stream"]["seed"],
                           snr=inp["stream"]["snr"], loss=train_loss,
                           acc=acc, telemetry=tele, ordered=True)
            return carry, (train_loss, acc, tele)

        return body

    return prepare, make_body


def checkpoint_manifest(directory, cfg, scenario, strategy_name: str,
                        resume: bool) -> None:
    """Stamp (or validate) the checkpoint directory's run identity.

    First save writes an `repro.obs.manifest` record whose
    ``config_hash`` covers (config, scenario, strategy); every later
    save/resume against the same directory must hash identically —
    resuming a trajectory under a different protocol would silently
    splice incompatible histories, so it is an error instead.
    """
    from repro.obs.manifest import build_manifest, config_hash, to_jsonable

    directory = Path(directory)
    chash = config_hash(to_jsonable(cfg), to_jsonable(scenario),
                        strategy_name)
    path = directory / "manifest.json"
    if path.exists():
        recorded = json.loads(path.read_text()).get("config_hash")
        if recorded != chash:
            raise ValueError(
                f"checkpoint directory {directory} belongs to a different "
                f"run protocol (manifest config_hash {recorded!r} != this "
                f"run's {chash!r}); use a fresh checkpoint dir or the "
                f"original config/scenario/strategy")
    elif resume:
        raise FileNotFoundError(
            f"resume: {path} not found — nothing to resume from")
    else:
        directory.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(
            build_manifest(cfg, scenario, strategy_name,
                           extra={"kind": "trajectory-checkpoint"}),
            indent=2, sort_keys=True))


def _run_scan_checkpointed(fn, carry, scan_xs, T: int, directory,
                           every: int, *, resume: bool,
                           resume_step: Optional[int], stop_after:
                           Optional[int], cfg, scenario, strategy_name: str,
                           stream=None):
    """Drive a scanned trajectory ``fn(carry, scan_xs) -> (carry, out)``
    in checkpointed segments — the one segment driver of `run_rounds` and
    `repro.sim.sharded.run_rounds_client_sharded`.

    The T-round scan is split at every ``every`` rounds; after each
    segment the FULL carry (param stacks, optimizer + strategy/process
    states, telemetry ledger) and the metrics accumulated so far are
    persisted via `repro.checkpoint` under ``step_<rounds_done>``, and
    the manifest is stamped with ``strategy_name``.  Every segment runs
    the same compiled scan body on the carry the last one left, and
    `prepare` is eager and deterministic, so the per-round scan inputs
    regenerate identically on resume and only the carry needs disk: an
    interrupted-and-resumed trajectory replays the uninterrupted
    checkpointed run BITWISE.

    Returns ``(carry, out, rounds_done)``; ``rounds_done < T`` only when
    ``stop_after`` deliberately kills the run at a segment boundary (the
    CI chaos-smoke's crash stand-in) or an attached ``stream``'s monitor
    escalated an alert to an abort (`repro.obs.monitor`) — in both cases
    the segment's checkpoint is already on disk, so the run resumes
    exactly where it stopped (checkpoint-then-stop).
    """
    from repro.checkpoint import (latest_step, load_checkpoint,
                                  save_checkpoint)

    directory = Path(directory)
    every = T if every is None or int(every) <= 0 else min(int(every), T)
    checkpoint_manifest(directory, cfg, scenario, strategy_name, resume)

    def sliced(lo, hi):
        return jax.tree.map(lambda x: x[lo:hi], scan_xs)

    def out_template(n):
        shapes = jax.eval_shape(fn, carry, sliced(0, n))[1]
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)

    start, acc = 0, None
    if resume:
        step = resume_step if resume_step is not None else (
            latest_step(directory))
        if step is None:
            raise FileNotFoundError(
                f"resume: no checkpoint steps in {directory}")
        if not 0 < step <= T:
            raise ValueError(
                f"resume: checkpoint step {step} outside this run's "
                f"1..{T} round range")
        payload = load_checkpoint(
            directory, {"carry": carry, "out": out_template(step)},
            step=step)
        carry, acc, start = payload["carry"], payload["out"], int(step)

    pos = start
    while pos < T:
        end = min(pos + every, T)
        carry, seg = fn(carry, sliced(pos, end))
        acc = seg if acc is None else jax.tree.map(
            lambda a, b: jnp.concatenate([a, b], axis=0), acc, seg)
        pos = end
        save_checkpoint(directory, pos, {"carry": carry, "out": acc})
        if stop_after is not None and pos >= int(stop_after) and pos < T:
            break
        if stream is not None:
            # Callbacks dispatch asynchronously; drain the segment's
            # records before polling the monitor's escalation decision.
            jax.effects_barrier()
        if stream is not None and stream.should_abort and pos < T:
            # Alert escalation: the ordered tap has already drained this
            # segment's rounds, the checkpoint above has the full carry —
            # stop here, resumable.
            break
    return carry, acc, pos


def make_trajectory_fn(prepare: Callable, make_body: Callable) -> Callable:
    """The per-trajectory closure: ``traj(seed, snr_db) -> (loss, acc)``,
    both ``(T,)`` — plus a round-stacked `RoundTelemetry` third element on
    telemetry-enabled builds.  This is the ONE traced body every
    Monte-Carlo executor consumes — `run_monte_carlo`'s single-device
    ``vmap`` grid and the device-parallel ``shard_map`` grid in
    :mod:`repro.sim.sharded` batch the same function, so the two paths can
    only differ by how XLA batches it (see the parity notes in DESIGN.md
    §Sharded-MC)."""
    def traj(seed, snr_db):
        ctx, carry0, scan_xs = prepare(seed, snr_db)
        _, out = jax.lax.scan(make_body(ctx), carry0, scan_xs,
                              unroll=_SCAN_UNROLL)
        return out
    return traj


def _run_options(*, telemetry: bool, stream, checkpoint_dir=None,
                 resume: bool = False, stop_after: Optional[int] = None,
                 timers=None) -> bool:
    """Check the run options `run_rounds`, `run_monte_carlo` and
    `repro.sim.sharded.run_rounds_client_sharded` share.  Returns whether
    telemetry is on: a stream drains the round telemetry live, so passing
    one turns it on."""
    if checkpoint_dir is None:
        if resume or stop_after is not None:
            raise ValueError(
                "resume/stop_after need checkpoint_dir — there is nothing "
                "to restore from or checkpoint into")
        if stream is not None and stream.escalates:
            raise ValueError(
                "abort-on-alert escalates via the checkpoint machinery "
                "(checkpoint-then-stop, resumable); pass checkpoint_dir "
                "to a single-trajectory run")
    elif timers is not None:
        raise ValueError(
            "timers profile a single-segment run; combine them with "
            "checkpointing and the phases stop meaning anything")
    return telemetry or stream is not None


def _trajectory_history(out, consensus, telemetry: bool) -> dict[str, Any]:
    """A single trajectory's history from its stacked scan outputs
    ``(loss, acc[, telemetry])`` and final consensus."""
    loss, acc = out[0], out[1]
    history = {
        # rounds actually run: == T except when stop_after killed the
        # checkpointed run at a segment boundary (crash simulation).
        "round": np.arange(1, int(loss.shape[0]) + 1),
        "train_loss": loss,
        "test_acc": acc,
        "final_params": consensus,
        "avg_acc": jnp.mean(acc),
        "final_acc": acc[-1],
    }
    if telemetry:
        history["telemetry"] = out[2]
    return history


def run_rounds(init_fn: Callable, apply_fn: Callable, loss_fn: Callable,
               topology: Topology, xs: jnp.ndarray, ys: jnp.ndarray,
               x_test: jnp.ndarray, y_test: jnp.ndarray, cfg: FLConfig,
               scenario: Optional[Scenario] = None,
               topo_cfg: Optional[TopologyConfig] = None,
               shard: Optional[str] = None,
               mesh=None,
               telemetry: bool = False,
               timers=None,
               checkpoint_dir: Optional[str] = None,
               checkpoint_every: int = 0,
               resume: bool = False,
               resume_step: Optional[int] = None,
               stop_after: Optional[int] = None,
               stream=None) -> dict[str, Any]:
    """Run one FL trajectory as one jitted scan (no per-round host sync);
    returns history with on-device ``(T,)`` metric arrays.

    ``shard="clients"``: distribute the stacked K-client axis over a
    ``("clients",)`` mesh (`repro.sim.sharded.run_rounds_client_sharded`
    — local training per rank, the per-cluster OTA sums riding a mesh
    collective); static CWFL scenarios only.
    ``telemetry=True`` (static flag, `repro.obs`): record a per-round
    `RoundTelemetry` under ``history["telemetry"]`` — with the flag off
    the traced computation is the untelemetered round.
    ``timers``: an optional `repro.obs.profiling.PhaseTimers` splitting
    the run into ``prepare``, ``trace_compile`` (AOT
    ``lower().compile()``) and ``execute`` (to ``block_until_ready``)
    wall phases; ``None`` keeps the default jit path untouched.

    Checkpoint/resume (DESIGN.md §Faults): ``checkpoint_dir`` persists
    the full scan carry + accumulated metrics every
    ``checkpoint_every`` rounds (0 ⇒ one final checkpoint) via
    `repro.checkpoint`, manifest-stamped with the run's config hash;
    ``resume=True`` restores the latest step (or ``resume_step``) and
    continues such that the interrupted+resumed history is BITWISE
    identical to an uninterrupted run.  ``stop_after=r`` deliberately
    exits at the first segment boundary ≥ r (crash simulation — CI's
    chaos-smoke).

    ``stream`` (STATIC; turns ``telemetry`` on): a
    `repro.obs.stream.RoundStream` drained live from inside the scan via
    an ordered `io_callback` — records arrive on the host in round order
    while the trajectory runs, metrics stay bitwise, and with
    ``stream=None`` the traced jaxpr is byte-identical to a
    streaming-unaware build.  A stream whose monitor escalates alerts to
    aborts requires ``checkpoint_dir`` (the abort IS a
    checkpoint-then-stop).
    """
    scenario = scenario or Scenario()
    if shard is not None:
        if shard != "clients":
            raise ValueError(
                f"run_rounds shards the client axis only (shard='clients'); "
                f"got {shard!r} — trajectory sharding (shard='mc') lives in "
                "run_monte_carlo")
        from repro.sim import sharded
        return sharded.run_rounds_client_sharded(
            init_fn, apply_fn, loss_fn, topology, xs, ys, x_test, y_test,
            cfg, scenario=scenario, mesh=mesh, telemetry=telemetry,
            checkpoint_dir=checkpoint_dir, checkpoint_every=checkpoint_every,
            resume=resume, resume_step=resume_step, stop_after=stop_after,
            stream=stream)
    telemetry = _run_options(telemetry=telemetry, stream=stream,
                             checkpoint_dir=checkpoint_dir, resume=resume,
                             stop_after=stop_after, timers=timers)
    prepare, make_body = _build(init_fn, apply_fn, loss_fn, topology, xs, ys,
                                x_test, y_test, cfg, scenario, topo_cfg,
                                telemetry=telemetry, stream=stream)

    # `prepare` runs EAGERLY, so the strategy state is concrete and the
    # round program closes over it as constants (`make_body`); only
    # Monte-Carlo sweeps trace `prepare` (under vmap over seeds/scenario
    # scalars).
    if timers is None:
        ctx, carry, scan_xs = prepare(cfg.seed, cfg.snr_db)
    else:
        with timers.phase("prepare"):
            ctx, carry, scan_xs = jax.block_until_ready(
                prepare(cfg.seed, cfg.snr_db))
    body = make_body(ctx)

    fn = jax.jit(lambda c, x: jax.lax.scan(body, c, x, unroll=_SCAN_UNROLL))
    if checkpoint_dir is not None:
        carry, out, _ = _run_scan_checkpointed(
            fn, carry, scan_xs, cfg.rounds, checkpoint_dir, checkpoint_every,
            resume=resume, resume_step=resume_step, stop_after=stop_after,
            cfg=cfg, scenario=scenario,
            strategy_name=get_strategy(cfg.strategy).name, stream=stream)
    elif timers is not None:
        with timers.phase("trace_compile"):
            fn = fn.lower(carry, scan_xs).compile()
        timers.compiled(fn)
        with timers.phase("execute"):
            carry, out = jax.block_until_ready(fn(carry, scan_xs))
    else:
        carry, out = fn(carry, scan_xs)
    if stream is not None:
        # The tap's callbacks are asynchronous; make sure every round
        # reached the host before the caller inspects the stream.
        jax.block_until_ready(out)
        jax.effects_barrier()
    return _trajectory_history(out, carry["consensus"], telemetry)


def run_monte_carlo(init_fn: Callable, apply_fn: Callable, loss_fn: Callable,
                    topology: Topology, xs: jnp.ndarray, ys: jnp.ndarray,
                    x_test: jnp.ndarray, y_test: jnp.ndarray, cfg: FLConfig,
                    scenario: Optional[Scenario] = None,
                    topo_cfg: Optional[TopologyConfig] = None,
                    seeds: int = 8,
                    snr_grid=None,
                    shard: Optional[str] = None,
                    mesh=None,
                    telemetry: bool = False,
                    timers=None,
                    stream=None) -> dict[str, Any]:
    """Monte-Carlo grid: ``seeds`` × ``snr_grid`` full trajectories in ONE
    jit (vmap over the seed axis, vmap over the scenario-scalar axis,
    `lax.scan` over rounds inside).

    ``snr_grid`` defaults to ``scenario.snr_grid`` when the scenario
    defines one (e.g. ``snr-sweep``); ``None``/empty sweeps only seeds.
    ``shard="mc"`` distributes the flattened seeds × SNR trajectory grid
    over the device mesh via ``shard_map`` (`repro.sim.sharded`) instead
    of batching it all onto one device; the metrics are identical (see
    the parity contract pinned by ``tests/test_sim_sharded.py``).
    Returns ``train_loss``/``test_acc`` of shape (S, T) or (S, G, T);
    with ``telemetry=True`` a trajectory-batched `RoundTelemetry` rides
    under ``history["telemetry"]`` (leading axes (S,[G,]T)).  ``timers``:
    optional `PhaseTimers` — see `run_rounds`.

    ``stream`` (STATIC; turns ``telemetry`` on): per-round records for
    every trajectory in the sweep.  The trajectory is vmapped, so the
    tap sits AFTER each trajectory's scan (`stream_trajectory_tap` on
    the round-stacked outputs — in-body taps either cannot batch
    (ordered) or re-fuse the vmapped loss reduction by a ulp
    (unordered); the post-scan tap reads materialized buffers and keeps
    the sweep bitwise) and the callback is unordered — consumers key on
    the explicit ``(seed, snr_db, round)`` tags, never arrival order.
    Under ``shard="mc"`` the stream is scoped to rank 0's trajectory
    chunk (rank-0 emit; see `repro.sim.sharded`).
    """
    scenario = scenario or Scenario()
    if snr_grid is None and scenario.snr_grid:
        snr_grid = scenario.snr_grid
    telemetry = _run_options(telemetry=telemetry, stream=stream)
    prepare, make_body = _build(init_fn, apply_fn, loss_fn, topology, xs, ys,
                                x_test, y_test, cfg, scenario, topo_cfg,
                                telemetry=telemetry)
    traj = make_trajectory_fn(prepare, make_body)
    if stream is not None:
        from repro.obs.stream import stream_trajectory_tap
        base_traj = traj

        def traj(seed, snr_db):
            loss, acc, tele = base_traj(seed, snr_db)
            stream_trajectory_tap(stream, seed=seed, snr=snr_db, loss=loss,
                                  acc=acc, telemetry=tele)
            return loss, acc, tele

    def _run(fn, *a):
        fn = jax.jit(fn)
        if timers is None:
            return fn(*a)
        with timers.phase("trace_compile"):
            fn = fn.lower(*a).compile()
        timers.compiled(fn)
        with timers.phase("execute"):
            return jax.block_until_ready(fn(*a))

    seed_arr = jnp.asarray(cfg.seed + np.arange(seeds))
    if shard is not None:
        if shard != "mc":
            raise ValueError(
                f"run_monte_carlo shards the trajectory grid only "
                f"(shard='mc'); got {shard!r} — client-axis sharding "
                "(shard='clients') lives in run_rounds")
        from repro.sim import sharded
        out = sharded.monte_carlo_sharded(
            traj, seed_arr, snr_grid, cfg.snr_db, cfg.rounds, mesh=mesh,
            telemetry=telemetry, stream=stream)
        grid, out = out[2], out[:2] + out[3:]
    elif snr_grid is None:
        grid = None
        out = _run(jax.vmap(traj, in_axes=(0, None)), seed_arr, cfg.snr_db)
    else:
        grid = jnp.asarray(snr_grid, jnp.float32)
        out = _run(jax.vmap(jax.vmap(traj, in_axes=(None, 0)),
                            in_axes=(0, None)), seed_arr, grid)
    loss, acc = out[0], out[1]
    if stream is not None:
        jax.block_until_ready(loss)
        jax.effects_barrier()
    history = {
        "train_loss": loss,
        "test_acc": acc,
        "final_acc": acc[..., -1],
        "seeds": seed_arr,
        "snr_grid": grid,
    }
    if telemetry:
        history["telemetry"] = out[2]
    return history
