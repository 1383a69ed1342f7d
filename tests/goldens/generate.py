"""Regenerate the committed golden-trajectory fixtures.

    PYTHONPATH=src python tests/goldens/generate.py

Runs the scenario engine's ``paper-static`` protocol (tiny T=4, K=8
synthetic-MNIST workload — the exact setup of ``tests/test_goldens.py``)
for all four strategies and stores the per-round train-loss/test-accuracy
histories as raw float32 BIT PATTERNS (uint32 hex), so the regression
test can assert bit-for-bit replay without a pre-refactor checkout.

Regenerate ONLY when a PR *intentionally* changes the trajectory bits
(e.g. a new key schedule) — the diff of the human-readable ``*_repr``
fields then documents the drift.  See DESIGN.md §Sharded-MC for the
platform caveat: the bits are pinned for CPU XLA; a different backend
or XLA version may legitimately re-fuse elementwise chains by a ulp, in
which case the test prints the ulp distance before failing.
"""
import json
import os
import sys

import jax
import numpy as np


GOLDEN_DIR = os.path.dirname(os.path.abspath(__file__))
# The four strategies the fixture pins (the paper's original comparison
# set).  Resolved through the repro.strategies registry like every other
# front door — prox variants share their base strategy's channel math, so
# at the fixture's 1-local-step-per-round protocol they replay the same
# bits and need no separate goldens.
STRATEGIES = ("cwfl", "cotaf", "fedavg", "decentralized")


def _check_registered():
    from repro.strategies import get_strategy
    for name in STRATEGIES:
        get_strategy(name)   # KeyError with the registry's listing if not


def workload():
    """The fixed tiny workload (shared with tests/test_goldens.py)."""
    from repro.core import TopologyConfig, make_topology
    from repro.data import (SyntheticImageConfig, make_synthetic_images,
                            partition_iid)
    from repro.models import make_mnist_mlp, nll_loss

    K = 8
    dcfg = SyntheticImageConfig.mnist_like(num_train=960, num_test=256)
    (xtr, ytr), (xte, yte) = make_synthetic_images(jax.random.PRNGKey(0),
                                                   dcfg)
    topo = make_topology(jax.random.PRNGKey(7),
                         TopologyConfig(num_clients=K, num_hotspots=3))
    xs, ys = partition_iid(jax.random.PRNGKey(1), xtr, ytr, K)
    init, apply = make_mnist_mlp(hidden=(32,))
    loss = lambda p, x, y: nll_loss(apply(p, x), y)
    return init, apply, loss, topo, xs, ys, xte, yte


def run_strategy(strategy: str):
    from repro.sim import run_rounds
    from repro.training import FLConfig

    init, apply, loss, topo, xs, ys, xte, yte = workload()
    cfg = FLConfig(strategy=strategy, rounds=4, snr_db=40.0,
                   eval_samples=256, seed=0)
    h = run_rounds(init, apply, loss, topo, xs, ys, xte, yte, cfg)
    return (np.asarray(h["train_loss"], np.float32),
            np.asarray(h["test_acc"], np.float32))


def bits(x: np.ndarray) -> list:
    return [format(v, "08x") for v in x.astype(np.float32).view(np.uint32)]


def main() -> None:
    _check_registered()
    payload = {
        "protocol": {
            "scenario": "paper-static", "rounds": 4, "clients": 8,
            "snr_db": 40.0, "seed": 0, "hidden": 32,
            "train": 960, "test": 256, "eval_samples": 256,
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            # CPU reductions tile by the host-platform device/thread
            # config, so the exact bits are pinned to the generating
            # layout; other configs get the ulp bound.
            "devices": len(jax.devices()),
        },
        "strategies": {},
    }
    for s in STRATEGIES:
        loss, acc = run_strategy(s)
        payload["strategies"][s] = {
            "train_loss_bits": bits(loss),
            "test_acc_bits": bits(acc),
            "train_loss_repr": [float(v) for v in loss],
            "test_acc_repr": [float(v) for v in acc],
        }
        print(f"{s:14s} loss={loss} acc={acc}")

    out = os.path.join(GOLDEN_DIR, "paper_static_T4_K8.json")
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {out}")


if __name__ == "__main__":
    sys.exit(main())
