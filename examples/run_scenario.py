"""Run a named `repro.sim` scenario: dynamic channels, scheduling, Monte-Carlo.

    PYTHONPATH=src python examples/run_scenario.py --scenario mobile-fading --seeds 8
    PYTHONPATH=src python examples/run_scenario.py --scenario snr-sweep --seeds 4
    PYTHONPATH=src python examples/run_scenario.py --seeds 8 --shard mc
    PYTHONPATH=src python examples/run_scenario.py --shard clients
    PYTHONPATH=src python examples/run_scenario.py --telemetry run.jsonl
    PYTHONPATH=src python examples/run_scenario.py --scenario head-failure \
        --checkpoint-dir ckpt --checkpoint-every 4 --stop-after 4   # "crash"
    PYTHONPATH=src python examples/run_scenario.py --scenario head-failure \
        --checkpoint-dir ckpt --checkpoint-every 4 --resume         # bitwise
    PYTHONPATH=src python examples/run_scenario.py --list

One seed runs a single scanned trajectory; ``--seeds N`` (N > 1) runs the
whole N-seed (× SNR-grid, for sweep scenarios) Monte-Carlo batch as ONE
jit via `repro.sim.run_monte_carlo` and reports mean ± std across seeds.

``--shard mc`` distributes the flattened trajectory grid over the device
mesh (`repro.sim.sharded`; pair with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` on CPU);
``--shard clients`` splits the stacked K-client axis of a single
trajectory instead.  ``--devices N`` caps the mesh; ``--assert-match-vmap``
re-runs the single-device vmap sweep and asserts the sharded metrics
match it (bitwise for seeds-only sweeps; ulp-level for SNR grids — see
DESIGN.md §Sharded-MC).

``--telemetry OUT.jsonl`` turns on the in-scan `repro.obs` round
telemetry (per-cluster loss, participation, consensus drift, the OTA
channel-use ledger, strategy internals) and writes the run — manifest,
per-round records, summary with phase wall timings — as a JSONL stream
`examples/obs_report.py` renders to markdown.  ``--profile-dir DIR``
additionally captures a TensorBoard-loadable ``jax.profiler`` trace.

``--stream OUT.jsonl`` goes LIVE instead of post-hoc: the scan body
drains every round to an append-mode JSONL while the run executes
(`repro.obs.stream`) — tail it with ``examples/watch_run.py --follow``.
``--alerts`` attaches the `repro.obs.monitor` rule engine (non-finite
loss, consensus-drift blowup, quarantine rate, eq. (5) power budget,
c/T convergence stall) whose alert records ride the same stream;
``--abort-on-alert`` escalates any alert to a checkpoint-then-stop
(requires ``--checkpoint-dir``; the aborted run resumes with
``--resume``, its stream appending where it left off).  ``--prom
OUT.prom`` additionally exports latest-round gauges as a
Prometheus-style textfile.

    PYTHONPATH=src python examples/run_scenario.py --stream live.jsonl \
        --alerts &
    PYTHONPATH=src python examples/watch_run.py live.jsonl --follow
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scenario", default="paper-static")
    ap.add_argument("--list", action="store_true",
                    help="list registered scenarios and exit")
    ap.add_argument("--seeds", type=int, default=1)
    ap.add_argument("--rounds", type=int, default=12)
    ap.add_argument("--clients", type=int, default=12)
    ap.add_argument("--clusters", type=int, default=3)
    ap.add_argument("--strategy", default=None,
                    help="aggregation strategy (repro.strategies registry; "
                         "--list shows the registered names). Default: the "
                         "scenario's pinned strategy, else cwfl")
    ap.add_argument("--snr-db", type=float, default=40.0,
                    help="overall SNR (ignored by snr-sweep's grid)")
    ap.add_argument("--hidden", type=int, default=64,
                    help="MLP hidden width (tiny default for CPU)")
    ap.add_argument("--train", type=int, default=4800)
    ap.add_argument("--test", type=int, default=1024)
    ap.add_argument("--out", default=None, help="optional JSON output path")
    ap.add_argument("--shard", choices=["mc", "clients"], default=None,
                    help="mc: shard the Monte-Carlo trajectory grid over "
                         "the device mesh; clients: shard the stacked "
                         "K-client axis of one trajectory")
    ap.add_argument("--devices", type=int, default=0,
                    help="mesh size for --shard (0 = all visible devices)")
    ap.add_argument("--assert-match-vmap", action="store_true",
                    help="with --shard mc: also run the single-device vmap "
                         "sweep and assert the metrics match")
    ap.add_argument("--telemetry", default=None, metavar="OUT.jsonl",
                    help="record in-scan round telemetry (repro.obs) and "
                         "write the run as a JSONL stream — manifest, one "
                         "record per (trajectory, round), summary with "
                         "phase timings; render with examples/obs_report.py")
    ap.add_argument("--stream", default=None, metavar="OUT.jsonl",
                    help="LIVE telemetry: drain every round to this JSONL "
                         "while the scan executes (repro.obs.stream); tail "
                         "with examples/watch_run.py --follow. Implies the "
                         "in-scan telemetry plane")
    ap.add_argument("--alerts", action="store_true",
                    help="attach the repro.obs.monitor rule engine to the "
                         "stream; alert records ride the same JSONL "
                         "(requires --stream)")
    ap.add_argument("--abort-on-alert", action="store_true",
                    help="escalate any alert to checkpoint-then-stop "
                         "(requires --stream and --checkpoint-dir; resume "
                         "with --resume). Implies --alerts")
    ap.add_argument("--prom", default=None, metavar="OUT.prom",
                    help="also export latest-round gauges as a "
                         "Prometheus-style textfile (requires --stream)")
    ap.add_argument("--alert-max-drift", type=float, default=100.0,
                    help="ConsensusDriftRule absolute ceiling (default "
                         "100.0; set tiny, e.g. 1e-9, to force an alert "
                         "for chaos/CI testing)")
    ap.add_argument("--profile-dir", default=None,
                    help="capture a jax.profiler trace into this directory "
                         "(TensorBoard-loadable)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="persist the trajectory carry + metrics for "
                         "crash-safe resume (single-trajectory runs; see "
                         "README 'Chaos & resume')")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="rounds per checkpoint segment (0 = one final "
                         "checkpoint)")
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest checkpoint from "
                         "--checkpoint-dir and continue — the resumed "
                         "history is bitwise identical to an uninterrupted "
                         "run")
    ap.add_argument("--resume-step", type=int, default=None,
                    help="resume from this specific checkpoint step "
                         "instead of the latest")
    ap.add_argument("--stop-after", type=int, default=None,
                    help="deliberately exit at the first checkpoint "
                         "boundary >= this round (crash simulation for "
                         "CI/chaos testing)")
    args = ap.parse_args()

    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    from repro.core import TopologyConfig, make_topology
    from repro.data import (SyntheticImageConfig, make_synthetic_images,
                            partition_iid)
    from repro.models import make_mnist_mlp, nll_loss
    from repro.obs import (PhaseTimers, build_manifest, profiler_trace,
                           write_history)
    from repro.sim import SCENARIOS, get_scenario, run_monte_carlo, run_rounds
    from repro.strategies import available_strategies, get_strategy
    from repro.training import FLConfig

    if args.list:
        for name, sc in sorted(SCENARIOS.items()):
            dyn = "dynamic" if not sc.is_static else "static"
            grid = f" snr_grid={list(sc.snr_grid)}" if sc.snr_grid else ""
            pin = f" strategy={sc.strategy}" if sc.strategy else ""
            print(f"{name:16s} [{dyn}]{grid}{pin}")
        print(f"strategies: {', '.join(available_strategies())}")
        return

    scenario = get_scenario(args.scenario)
    # Resolve through the ONE registry: an explicit --strategy wins, else
    # the scenario's pinned default, else cwfl.  Unknown names fail here
    # with the registry's own message listing every registered strategy.
    strategy = (get_strategy(args.strategy) if args.strategy is not None
                else scenario.default_strategy())
    tcfg = TopologyConfig(num_clients=args.clients, num_hotspots=3)
    topo = make_topology(jax.random.PRNGKey(7), tcfg)
    dcfg = SyntheticImageConfig.mnist_like(args.train, args.test)
    (xtr, ytr), (xte, yte) = make_synthetic_images(jax.random.PRNGKey(1), dcfg)
    xs, ys = partition_iid(jax.random.PRNGKey(2), xtr, ytr, args.clients)
    init, apply = make_mnist_mlp(hidden=(args.hidden,))
    loss = lambda p, x, y: nll_loss(apply(p, x), y)
    cfg = FLConfig(strategy=strategy.name, rounds=args.rounds,
                   num_clusters=args.clusters, snr_db=args.snr_db,
                   eval_samples=args.test)

    is_sweep = args.seeds > 1 or bool(scenario.snr_grid)
    if args.shard == "mc" and not is_sweep:
        ap.error("--shard mc distributes a Monte-Carlo sweep; pass "
                 "--seeds N > 1 or a grid scenario (e.g. snr-sweep), or "
                 "use --shard clients for a single trajectory")
    if args.assert_match_vmap and args.shard != "mc":
        ap.error("--assert-match-vmap compares a --shard mc sweep "
                 "against the vmap path; nothing to compare here")
    mesh = None
    if args.shard is not None:
        from repro.launch.mesh import make_client_mesh, make_mc_mesh
        make = make_mc_mesh if args.shard == "mc" else make_client_mesh
        mesh = make(args.devices or None)
        print(f"shard={args.shard} mesh={dict(mesh.shape)}")

    is_single = not (args.seeds > 1 or bool(scenario.snr_grid))
    if args.checkpoint_dir is not None and not is_single:
        ap.error("--checkpoint-dir checkpoints ONE trajectory; Monte-Carlo "
                 "sweeps re-run cheaply per seed — drop --seeds / the grid "
                 "scenario")
    if args.checkpoint_dir is None and (args.resume
                                        or args.stop_after is not None):
        ap.error("--resume/--stop-after need --checkpoint-dir")

    if (args.alerts or args.abort_on_alert or args.prom) and not args.stream:
        ap.error("--alerts/--abort-on-alert/--prom ride the live stream; "
                 "add --stream OUT.jsonl")
    if args.abort_on_alert and args.checkpoint_dir is None:
        ap.error("--abort-on-alert stops at a checkpoint boundary so the "
                 "run stays resumable; add --checkpoint-dir (single "
                 "trajectory only)")

    telemetry = args.telemetry is not None or args.stream is not None
    # Checkpointed runs are multi-segment: phase timers stop meaning
    # anything (run_rounds refuses the combination), so drop them.
    timers = (PhaseTimers()
              if args.telemetry is not None and args.checkpoint_dir is None
              else None)

    stream = None
    manifest = None
    if args.stream is not None:
        from repro.obs import (JsonlStreamSink, Monitor, PrometheusSink,
                               RoundStream, default_rules)
        monitor = None
        if args.alerts or args.abort_on_alert:
            monitor = Monitor(default_rules(max_drift=args.alert_max_drift),
                              abort_on_alert=args.abort_on_alert)
        # Manifest first: a tailer picking up the file mid-run knows the
        # config before the first round record lands.  --resume appends so
        # the resumed rounds continue the same file.
        jsonl = JsonlStreamSink(args.stream, append=args.resume)
        manifest = build_manifest(cfg=cfg, scenario=scenario,
                                  strategy=strategy, mesh=mesh,
                                  extra={"shard": args.shard,
                                         "seeds": args.seeds,
                                         "clients": args.clients})
        jsonl.write({"type": "manifest", **manifest})
        sinks = [jsonl]
        if args.prom:
            sinks.append(PrometheusSink(args.prom))
        stream = RoundStream(sinks, monitor=monitor)

    print(f"scenario={args.scenario} strategy={strategy.name} "
          f"K={args.clients} rounds={args.rounds} seeds={args.seeds}"
          + (f" telemetry={args.telemetry}" if args.telemetry else "")
          + (f" stream={args.stream}" if args.stream else ""))
    t0 = time.perf_counter()
    if args.seeds > 1 or scenario.snr_grid:
        if args.shard == "clients":
            ap.error("--shard clients runs ONE trajectory (the K-client "
                     "axis is the parallel axis); drop --seeds / pick a "
                     "grid-free scenario, or use --shard mc for sweeps")
        with profiler_trace(args.profile_dir):
            h = run_monte_carlo(init, apply, loss, topo, xs, ys, xte, yte,
                                cfg, scenario=scenario, topo_cfg=tcfg,
                                seeds=args.seeds, shard=args.shard,
                                mesh=mesh, telemetry=telemetry, timers=timers,
                                stream=stream)
        wall = time.perf_counter() - t0
        if args.assert_match_vmap and args.shard == "mc":
            h_ref = run_monte_carlo(init, apply, loss, topo, xs, ys, xte,
                                    yte, cfg, scenario=scenario,
                                    topo_cfg=tcfg, seeds=args.seeds)
            for key in ("train_loss", "test_acc"):
                a = np.asarray(h[key])
                b = np.asarray(h_ref[key])
                bit = bool(np.array_equal(a, b))
                # SNR-grid sweeps batch nested on the vmap path and
                # flattened on the sharded path: XLA's batching-dependent
                # fusion costs ~1 ulp/round, compounding through SGD
                # (DESIGN.md §Sharded-MC) — seeds-only sweeps are bitwise.
                np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-5)
                print(f"  sharded == vmap [{key}]: "
                      f"{'bitwise' if bit else 'allclose(2e-5)'} OK")
        if timers is not None:
            with timers.phase("gather"):
                h["train_loss"] = np.asarray(h["train_loss"])
                h["test_acc"] = np.asarray(h["test_acc"])
        acc = np.asarray(h["test_acc"])            # (S, T) or (S, G, T)
        n_traj = int(np.prod(acc.shape[:-1]))
        if h["snr_grid"] is not None:
            for gi, snr in enumerate(np.asarray(h["snr_grid"])):
                fin = acc[:, gi, -1]
                print(f"  SNR {snr:5.1f} dB: final acc "
                      f"{fin.mean():.3f} ± {fin.std():.3f}  (over "
                      f"{acc.shape[0]} seeds)")
        else:
            fin = acc[:, -1]
            print(f"  final acc {fin.mean():.3f} ± {fin.std():.3f} "
                  f"(over {acc.shape[0]} seeds)")
        payload = {
            "scenario": args.scenario,
            "strategy": strategy.name,
            "shard": args.shard,
            "seeds": int(acc.shape[0]),
            "snr_grid": (None if h["snr_grid"] is None
                         else np.asarray(h["snr_grid"]).tolist()),
            "test_acc": acc.tolist(),
            "train_loss": np.asarray(h["train_loss"]).tolist(),
            "wall_seconds": wall,
            "trajectories": n_traj,
        }
    else:
        with profiler_trace(args.profile_dir):
            h = run_rounds(init, apply, loss, topo, xs, ys, xte, yte, cfg,
                           scenario=scenario, topo_cfg=tcfg,
                           shard=args.shard, mesh=mesh,
                           telemetry=telemetry, timers=timers,
                           checkpoint_dir=args.checkpoint_dir,
                           checkpoint_every=args.checkpoint_every,
                           resume=args.resume, resume_step=args.resume_step,
                           stop_after=args.stop_after, stream=stream)
        wall = time.perf_counter() - t0
        if timers is not None:
            with timers.phase("gather"):
                h["train_loss"] = np.asarray(h["train_loss"])
                h["test_acc"] = np.asarray(h["test_acc"])
        acc = np.asarray(h["test_acc"])
        n_traj = 1
        for r, (l, a) in enumerate(zip(np.asarray(h["train_loss"]), acc)):
            print(f"  round {r + 1:2d}  loss={l:.3f}  acc={a:.3f}")
        payload = {
            "scenario": args.scenario,
            "strategy": strategy.name,
            "shard": args.shard,
            "seeds": 1,
            "test_acc": acc.tolist(),
            "train_loss": np.asarray(h["train_loss"]).tolist(),
            "wall_seconds": wall,
            "trajectories": 1,
        }
    total_rounds = n_traj * int(acc.shape[-1])   # may be < --rounds when
    # --stop-after killed a checkpointed run at a segment boundary
    print(f"  {total_rounds} rounds total in {wall:.1f}s "
          f"({total_rounds / wall:.2f} rounds/s incl. compile)")
    if stream is not None:
        abort = stream.should_abort
        print(f"  stream: {stream.emitted} records -> {args.stream}"
              + (f" ({stream.dropped} off-rank/off-scope dropped)"
                 if stream.dropped else "")
              + (f" [{len(stream.errors)} tap errors]"
                 if stream.errors else ""))
        if stream.monitor is not None:
            s = stream.monitor.summary()
            if s["alerts"]:
                by = ", ".join(f"{k}×{v}" for k, v in s["by_rule"].items())
                print(f"  ALERTS: {s['alerts']} ({by})"
                      + ("; run aborted at checkpoint boundary — resume "
                         "with --resume" if abort else ""))
            else:
                print("  alerts: none")
        stream.close()
    if manifest is None and (telemetry or args.out):
        manifest = build_manifest(cfg=cfg, scenario=scenario,
                                  strategy=strategy, mesh=mesh,
                                  extra={"shard": args.shard,
                                         "seeds": args.seeds,
                                         "clients": args.clients})
    if args.telemetry is not None:
        if timers is not None:
            for name, secs in timers.as_dict().items():
                print(f"  phase {name:14s} {secs:8.3f}s")
        n_rec = write_history(args.telemetry, h, manifest=manifest,
                              timings=timers.as_dict() if timers else None)
        print(f"  wrote {args.telemetry} ({n_rec} records); render with "
              f"examples/obs_report.py")
    if args.out:
        payload["run_manifest"] = manifest
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"  wrote {args.out}")
    if stream is not None and stream.errors:
        # The tap swallows host-side errors so the running computation
        # survives them; the run still failed, so the exit code says so.
        raise SystemExit(f"stream tap recorded {len(stream.errors)} "
                         f"error(s), first: {stream.errors[0]}")


if __name__ == "__main__":
    main()
