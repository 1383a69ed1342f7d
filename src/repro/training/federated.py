"""The paper-protocol front door (`run_federated` + `FLConfig`).

One round = E local epochs at every client in parallel (vmap) followed by one
synchronization (t ∈ H) under the selected aggregation strategy.  The
rounds run in :mod:`repro.sim.engine` (a `lax.scan` over rounds,
vmap-able over seeds/scenario scalars); `run_federated` is the stable
paper-protocol entry point wrapping it.

Strategies are first-class objects now: ``FLConfig.strategy`` names an
entry in the :mod:`repro.strategies` registry (``get_strategy`` /
``register_strategy``).  The old ``STRATEGIES`` mapping of bare
``(setup, aggregate)`` tuples remains as a deprecated read-only view for
one release — see the README migration note.
"""
from __future__ import annotations

import dataclasses
import warnings
from collections.abc import Mapping
from typing import Any, Callable, Optional

import jax.numpy as jnp
import numpy as np

from repro.core.topology import Topology
from repro.strategies import available_strategies, get_strategy


class _DeprecatedStrategies(Mapping):
    """Read-only ``name -> (setup, aggregate)`` view of the strategy
    registry, kept for one release so pre-Strategy-API callers keep
    working.  Every *access* (not the import) warns — new code should
    resolve `repro.strategies.get_strategy` and call the `Strategy`
    object directly."""

    @staticmethod
    def _warn():
        warnings.warn(
            "repro.training.STRATEGIES is deprecated; use "
            "repro.strategies.get_strategy(name) and the Strategy object "
            "(init/aggregate) instead", DeprecationWarning, stacklevel=3)

    def __getitem__(self, name):
        self._warn()
        strategy = get_strategy(name)

        def setup(topology, key, *, num_clusters=3, snr_db=None, **_):
            cfg = FLConfig(strategy=strategy.name, num_clusters=num_clusters)
            return strategy.init(topology, key, cfg, snr_db=snr_db)

        def aggregate(params, state, key):
            return strategy.aggregate(params, state, key)

        return setup, aggregate

    def __iter__(self):
        self._warn()
        return iter(available_strategies())

    def __len__(self):
        self._warn()
        return len(available_strategies())


STRATEGIES = _DeprecatedStrategies()


@dataclasses.dataclass(frozen=True)
class FLConfig:
    strategy: str = "cwfl"           # resolved via repro.strategies registry
    rounds: int = 70                 # paper: 70-80 communication rounds
    local_epochs: int = 1            # E
    batch_size: int = 64             # paper: 64 (MNIST) / 32 (CIFAR)
    lr: float = 1e-3                 # paper: 0.001
    num_clusters: int = 3            # paper: 3 optimal
    snr_db: Optional[float] = 40.0   # paper: overall SNR 40 dB
    mu_prox: float = 0.0             # FedProx µ_p override (0 = use the
                                     # strategy's default, e.g. cwfl_prox)
    eval_samples: int = 2048
    seed: int = 0


def run_federated(init_fn: Callable, apply_fn: Callable, loss_fn: Callable,
                  topology: Topology, xs: jnp.ndarray, ys: jnp.ndarray,
                  x_test: jnp.ndarray, y_test: jnp.ndarray,
                  cfg: FLConfig, scenario=None,
                  topo_cfg=None) -> dict[str, Any]:
    """Run FL; returns history dict with per-round test accuracy/loss.

    ``xs, ys``: stacked client shards (K, N_k, ...).

    A plain wrapper over the scanned engine
    (:func:`repro.sim.engine.run_rounds`) that returns host floats.
    ``scenario``/``topo_cfg`` opt into `repro.sim` dynamics (time-varying
    channels, participation masks, re-clustering).
    """
    from repro.sim.engine import run_rounds  # deferred: sim imports us

    h = run_rounds(init_fn, apply_fn, loss_fn, topology, xs, ys,
                   x_test, y_test, cfg, scenario=scenario,
                   topo_cfg=topo_cfg)
    test_acc = [float(x) for x in np.asarray(h["test_acc"])]
    return {
        "round": [int(r) for r in h["round"]],
        "train_loss": [float(x) for x in np.asarray(h["train_loss"])],
        "test_acc": test_acc,
        "final_params": h["final_params"],
        "avg_acc": float(h["avg_acc"]),
        "final_acc": test_acc[-1],
    }
