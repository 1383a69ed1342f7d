#!/usr/bin/env python3
"""Bring-up smoke test: the paper's K=50 CWFL deployment on a TPU.

    python3 chip_smoke.py              # one chip: the main path at full width
    python3 chip_smoke.py --chips 4    # four chips: the sharded executors only
    JAX_PLATFORMS=cpu python3 chip_smoke.py --rehearse [--chips 4]

One chip (the default) runs the MNIST deployment of ``repro/configs/
mnist_mlp.py`` -- the (200, 100, 64) MLP (flat d = 184,214), K=50 clients
on a label-sorted non-IID split, C=3 clusters, 40 dB, batch 64, lr 1e-3,
synthetic MNIST-shaped data from a seed -- through the entry points a
user calls, and checks:

* the fused ``cwfl_round`` kernel at K=50, C=3, d=184,214 agrees with
  ``kernels.ref.cwfl_round_ref`` computed at "highest" matmul precision
  (injected noise, guard off and on);
* ``run_rounds`` compiles the fused kernel into its round program
  (``tpu_custom_call`` in the HLO) and trains: every round's loss and
  accuracy are finite and the last round's loss is below the first's;
* a 2-seed ``run_monte_carlo`` sweep does the same per seed;
* one ``run_rounds(telemetry=True, stream=...)`` round drains its record
  to the host through the in-scan ``io_callback`` with no tap error.

``--chips 4`` runs only the multi-chip executors, each against its
single-device run in the same process: ``run_monte_carlo(shard="mc")`` at
4 seeds, and ``run_rounds(shard="clients")`` at K=48 (the client axis must
divide over the 4 chips).

Compile/execute wall times and peak device memory are printed for
information only.  Every check raises, and the script exits non-zero; on
success the last line of stdout is one JSON object naming the device.
Without a TPU it fails before any work.  ``--rehearse`` lets it run on the
CPU (Pallas in interpret mode, ``--chips 4`` on four virtual devices) at a
reduced K and data size, to find faults before a chip run; it then prints
no chip result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# The paper's MNIST deployment (configs/mnist_mlp.py, FLConfig defaults).
PAPER = dict(clients=50, train=60_000, test=10_000)
# --rehearse: the same model and protocol at a size the CPU runs quickly.
REHEARSAL = dict(clients=8, train=12_000, test=1_000)
CLUSTERS, SNR_DB = 3, 40.0
ROUNDS, SEED = 5, 0
# Fused kernel vs the f32 reference: f32 accumulation-order slack on
# unit-scale signals (|x| <= ~5) with convex phase-1/phase-2 weights.  A
# kernel whose matmuls drop to the TPU's one-pass bf16 default misses it
# by ~40x (4e-3 measured on a v5e).
KERNEL_ATOL = 1e-4
# Sharded vs single-device metrics, (rtol, atol) per metric.  The two
# programs batch the local SGD differently (1 vs 4 trajectories per chip,
# 12 vs 48 clients), so the TPU's one-pass bf16 matmuls in the MLP sum in
# another order and 90 SGD steps amplify it: on four v5e chips the
# losses differed by up to 5.6e-5 (2x examples/run_scenario.py's
# rtol 2e-5 / atol 1e-5) and accuracies by up to 5 of the 10,000 test
# samples.  On the CPU both comparisons are bitwise or within 1 ulp.
SHARD_TOL = {"train_loss": (2e-4, 1e-5), "test_acc": (0.0, 1e-3)}


def log(msg: str) -> None:
    print(msg, flush=True)


def parse_args():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the main path on one chip; 4: only the mc- "
                         "and client-sharded executors across four chips")
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU at a reduced size (no chip "
                         "result is printed)")
    return ap.parse_args()


def deployment(jax, num_clients: int, size: dict, seed: int):
    """Topology, non-IID client shards, test set and the MLP, all made
    from ``seed`` the way benchmarks/common.py builds the paper setting."""
    from repro.configs.mnist_mlp import config as mlp
    from repro.core import TopologyConfig, make_topology
    from repro.data import (SyntheticImageConfig, make_synthetic_images,
                            partition_noniid)
    from repro.models import make_mnist_mlp, nll_loss

    dcfg = SyntheticImageConfig.mnist_like(size["train"], size["test"])
    (xtr, ytr), (xte, yte) = make_synthetic_images(
        jax.random.PRNGKey(seed), dcfg)
    tcfg = TopologyConfig(num_clients=num_clients, num_hotspots=CLUSTERS)
    topo = make_topology(jax.random.PRNGKey(seed + 7), tcfg)
    xs, ys = partition_noniid(jax.random.PRNGKey(seed + 1), xtr, ytr,
                              num_clients, mlp["noniid_shards_per_client"])
    init, apply = make_mnist_mlp(hidden=mlp["hidden"])
    loss = lambda p, x, y: nll_loss(apply(p, x), y)
    jax.block_until_ready((xs, ys, xte, yte))
    return dict(init=init, apply=apply, loss=loss, topo=topo, tcfg=tcfg,
                xs=xs, ys=ys, xte=xte, yte=yte)


def fl_config(rounds: int, size: dict, seed: int):
    from repro.configs.mnist_mlp import config as mlp
    from repro.training import FLConfig
    return FLConfig(strategy="cwfl", rounds=rounds,
                    batch_size=mlp["batch_size"], lr=mlp["lr"],
                    num_clusters=CLUSTERS, snr_db=SNR_DB,
                    eval_samples=size["test"], seed=seed)


def flat_dim(jax, dep) -> int:
    params = jax.eval_shape(dep["init"], jax.random.PRNGKey(0))
    return sum(int(x.size) for x in jax.tree.leaves(params))


def peak_memory(jax) -> str:
    stats = jax.devices()[0].memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
        return "not reported"
    return f"{stats['peak_bytes_in_use'] / 2**20:.1f} MiB"


def check_kernel(jax, K: int, C: int, d: int, seed: int) -> None:
    """Fused round vs the three-pass reference at the deployment's shape."""
    import jax.numpy as jnp
    import numpy as np
    from functools import partial

    from repro.kernels.cwfl_round import cwfl_round
    from repro.kernels.ref import cwfl_round_ref

    ks = jax.random.split(jax.random.PRNGKey(seed + 101), 6)
    s = jax.random.normal(ks[0], (K, d), jnp.float32)
    a = jax.random.uniform(ks[1], (C, K), jnp.float32)
    b = jax.random.uniform(ks[2], (C, C), jnp.float32)
    a, b = a / a.sum(1, keepdims=True), b / b.sum(1, keepdims=True)
    m = jax.nn.one_hot(jax.random.randint(ks[3], (K,), 0, C), C)
    n1 = 0.1 * jax.random.normal(ks[4], (C, d), jnp.float32)
    n2 = 0.1 * jax.random.normal(ks[5], (C, d), jnp.float32)
    for guard in (False, True):
        if guard:
            # A poisoned client and an all-dead cluster: the guard's case.
            s, a = s.at[2].set(jnp.nan), a.at[1].set(0.0)
        new, cons = cwfl_round(s, a, n1, b, n2, m, guard=guard)
        with jax.default_matmul_precision("highest"):
            rnew, rcons = jax.jit(partial(cwfl_round_ref, guard=guard))(
                s, a, n1, b, n2, m)
        err = max(float(np.max(np.abs(np.asarray(x) - np.asarray(r))))
                  for x, r in ((new, rnew), (cons, rcons)))
        log(f"kernel cwfl_round K={K} C={C} d={d} guard={guard}: "
            f"max abs err vs f32 reference {err:.3e} (tol {KERNEL_ATOL:g})")
        if not err <= KERNEL_ATOL:
            raise SystemExit(f"cwfl_round (guard={guard}) disagrees with "
                             f"cwfl_round_ref: {err:.3e} > {KERNEL_ATOL:g}")


def check_fused(timers, on_tpu: bool, what: str) -> None:
    if not on_tpu:
        log(f"{what}: interpret mode off the chip, fused-kernel check "
            f"skipped")
        return
    if "tpu_custom_call" not in timers.executables[-1].as_text():
        raise SystemExit(f"{what}: compiled program has no tpu_custom_call "
                         f"- the fused cwfl_round kernel is not in it")
    log(f"{what}: compiled program contains the fused kernel "
        f"(tpu_custom_call)")


def check_training(np, loss, acc, what: str) -> None:
    loss, acc = np.asarray(loss), np.asarray(acc)
    for r, (l, a) in enumerate(zip(loss, acc)):
        log(f"  {what} round {r + 1}: train_loss={l:.6f} test_acc={a:.4f}")
    if not (np.isfinite(loss).all() and np.isfinite(acc).all()):
        raise SystemExit(f"{what}: non-finite train_loss/test_acc")
    if not loss[-1] < loss[0]:
        raise SystemExit(f"{what}: loss did not fall "
                         f"({loss[0]:.6f} -> {loss[-1]:.6f})")


def log_timers(jax, timers, what: str) -> None:
    t = timers.as_dict()
    log(f"{what}: compile {t['trace_compile']:.3f} s, execute "
        f"{t['execute']:.3f} s, peak device memory {peak_memory(jax)} "
        f"(informational)")


def one_chip(jax, size: dict, on_tpu: bool) -> None:
    import numpy as np

    from repro.obs import MemorySink, PhaseTimers, RoundStream
    from repro.sim import get_scenario, run_monte_carlo, run_rounds

    t0 = time.perf_counter()
    dep = deployment(jax, size["clients"], size, SEED)
    d = flat_dim(jax, dep)
    K = size["clients"]
    log(f"deployment: mnist_mlp d={d} K={K} C={CLUSTERS} "
        f"snr={SNR_DB:g} dB, {size['train']} train / {size['test']} test, "
        f"non-IID, set-up {time.perf_counter() - t0:.3f} s")

    check_kernel(jax, K, CLUSTERS, d, SEED)

    scenario = get_scenario("paper-static")
    cfg = fl_config(ROUNDS, size, SEED)
    run_args = (dep["init"], dep["apply"], dep["loss"], dep["topo"],
                dep["xs"], dep["ys"], dep["xte"], dep["yte"])

    timers = PhaseTimers()
    h = run_rounds(*run_args, cfg, scenario=scenario, topo_cfg=dep["tcfg"],
                   timers=timers)
    check_fused(timers, on_tpu, "run_rounds")
    check_training(np, h["train_loss"], h["test_acc"], "run_rounds")
    log_timers(jax, timers, "run_rounds")

    timers = PhaseTimers()
    mc = run_monte_carlo(*run_args, cfg, scenario=scenario,
                         topo_cfg=dep["tcfg"], seeds=2, timers=timers)
    check_fused(timers, on_tpu, "run_monte_carlo")
    if np.shape(mc["train_loss"]) != (2, ROUNDS):
        raise SystemExit(f"run_monte_carlo: shape "
                         f"{np.shape(mc['train_loss'])} != (2, {ROUNDS})")
    for i, seed in enumerate(np.asarray(mc["seeds"])):
        check_training(np, mc["train_loss"][i], mc["test_acc"][i],
                       f"run_monte_carlo seed {seed}")
    log_timers(jax, timers, "run_monte_carlo")

    sink = MemorySink()
    stream = RoundStream([sink])
    hs = run_rounds(*run_args, dataclasses.replace(cfg, rounds=1),
                    scenario=scenario, topo_cfg=dep["tcfg"], telemetry=True,
                    stream=stream)
    stream.close()
    if stream.errors:
        raise SystemExit(f"stream tap: {len(stream.errors)} error(s), "
                         f"first: {stream.errors[0]}")
    recs = sink.of_type("stream")
    if len(recs) != 1 or recs[0]["train_loss"] != np.asarray(
            hs["train_loss"])[0]:
        raise SystemExit(f"stream tap: expected one record equal to the "
                         f"history's round, got {len(recs)}")
    log(f"stream: 1 round record through io_callback, train_loss="
        f"{float(recs[0]['train_loss']):.6f}, no tap errors")


def assert_spans(jax, arr, n: int, what: str) -> None:
    devices = arr.sharding.device_set
    if len(devices) != n:
        raise SystemExit(f"{what}: result lives on {len(devices)} "
                         f"device(s), expected all {n}")


def compare(np, got, want, what: str, key: str) -> bool:
    """Log how far a sharded metric is from its single-device run, as
    max abs diff and as a multiple of the allclose bound; True if within."""
    got, want = np.asarray(got), np.asarray(want)
    diff = np.abs(got - want)
    rtol, atol = SHARD_TOL[key]
    of_bound = float(np.max(diff / (atol + rtol * np.abs(want))))
    log(f"  {what} vs single device [{key}]: max abs diff "
        f"{float(np.max(diff)):.3e}, {of_bound:.3g}x the bound (rtol "
        f"{rtol:g}, atol {atol:g})"
        f"{' (bitwise)' if np.array_equal(got, want) else ''}")
    return of_bound <= 1.0


def four_chips(jax, size: dict) -> None:
    import numpy as np

    from repro.launch.mesh import make_client_mesh, make_mc_mesh
    from repro.sim import get_scenario, run_monte_carlo, run_rounds

    n = 4
    scenario = get_scenario("paper-static")
    cfg = fl_config(ROUNDS, size, SEED)

    dep = deployment(jax, size["clients"], size, SEED)
    run_args = (dep["init"], dep["apply"], dep["loss"], dep["topo"],
                dep["xs"], dep["ys"], dep["xte"], dep["yte"])
    mesh = make_mc_mesh(n)
    log(f"mc mesh {dict(mesh.shape)} over {mesh.devices.size} devices; "
        f"K={size['clients']}, 4 seeds, {ROUNDS} rounds")
    t0 = time.perf_counter()
    h_s = run_monte_carlo(*run_args, cfg, scenario=scenario,
                          topo_cfg=dep["tcfg"], seeds=n, shard="mc",
                          mesh=mesh)
    jax.block_until_ready(h_s["train_loss"])
    log(f"  mc-sharded sweep {time.perf_counter() - t0:.3f} s incl. compile")
    assert_spans(jax, h_s["train_loss"], n, "mc-sharded sweep")
    t0 = time.perf_counter()
    h_v = run_monte_carlo(*run_args, cfg, scenario=scenario,
                          topo_cfg=dep["tcfg"], seeds=n)
    jax.block_until_ready(h_v["train_loss"])
    log(f"  single-device vmap sweep {time.perf_counter() - t0:.3f} s "
        f"incl. compile")
    # Both executors are compared before any mismatch fails the run, so
    # one four-chip run reports both.
    mismatches = [f"mc-sharded [{key}]" for key in ("train_loss", "test_acc")
                  if not compare(np, h_s[key], h_v[key], "mc-sharded", key)]
    for i in range(n):
        check_training(np, h_s["train_loss"][i], h_s["test_acc"][i],
                       f"mc-sharded seed {i}")

    # The client axis must divide over the mesh: the nearest multiple of 4
    # at or below the deployment's K (50 -> 48).
    K = size["clients"] - size["clients"] % n
    dep = deployment(jax, K, size, SEED)
    run_args = (dep["init"], dep["apply"], dep["loss"], dep["topo"],
                dep["xs"], dep["ys"], dep["xte"], dep["yte"])
    cmesh = make_client_mesh(n)
    log(f"clients mesh {dict(cmesh.shape)} over {cmesh.devices.size} "
        f"devices; K={K}, {ROUNDS} rounds")
    t0 = time.perf_counter()
    h_c = run_rounds(*run_args, cfg, scenario=scenario, shard="clients",
                     mesh=cmesh)
    jax.block_until_ready(h_c["train_loss"])
    log(f"  client-sharded run {time.perf_counter() - t0:.3f} s incl. "
        f"compile")
    for leaf in jax.tree.leaves(h_c["final_params"]):
        assert_spans(jax, leaf, n, "client-sharded consensus")
    t0 = time.perf_counter()
    h_u = run_rounds(*run_args, cfg, scenario=scenario, topo_cfg=dep["tcfg"])
    jax.block_until_ready(h_u["train_loss"])
    log(f"  single-device run {time.perf_counter() - t0:.3f} s incl. "
        f"compile")
    mismatches += [f"client-sharded [{key}]"
                   for key in ("train_loss", "test_acc")
                   if not compare(np, h_c[key], h_u[key], "client-sharded",
                                  key)]
    check_training(np, h_c["train_loss"], h_c["test_acc"], "client-sharded")
    if mismatches:
        raise SystemExit(f"sharded runs disagree with their single-device "
                         f"runs: {', '.join(mismatches)}")


def main() -> None:
    args = parse_args()
    if args.rehearse and args.chips > 1:
        # Virtual CPU devices; must be set before JAX initializes.
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.chips}")
    import jax

    from repro.utils.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.rehearse:
        raise SystemExit(f"no TPU: JAX found {len(devices)} {dev.platform} "
                         f"device(s); chip_smoke.py needs the chip "
                         f"(--rehearse runs it on the CPU)")
    if len(devices) < args.chips:
        raise SystemExit(f"--chips {args.chips}: JAX found only "
                         f"{len(devices)} device(s)")
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__}")
    log(f"compile cache: {enable_compile_cache()}")
    size = REHEARSAL if args.rehearse else PAPER

    t0 = time.perf_counter()
    if args.chips == 1:
        one_chip(jax, size, on_tpu)
    else:
        four_chips(jax, size)
    log(f"all checks passed in {time.perf_counter() - t0:.3f} s")
    if args.rehearse:
        log(f"rehearsal on {dev.platform}: no chip result")
        return
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
