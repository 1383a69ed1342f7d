"""Named scenario registry — the workloads the engine knows how to run.

A `Scenario` bundles a channel process, a participation schedule, a
re-clustering cadence and (optionally) an SNR grid for Monte-Carlo
sweeps.  `get_scenario(name)` resolves the registry; scenarios are plain
frozen dataclasses so CLIs / tests can also build ad-hoc ones.

Registry (see DESIGN.md §Sim for the math behind each knob):

* ``paper-static``    — the paper's §V protocol verbatim: stationary
  channel, full participation.  The engine's trajectory under this
  scenario is pinned bit for bit by the goldens (``tests/goldens``).
* ``mobile-fading``   — random-waypoint mobility + Gauss-Markov fading +
  log-normal shadowing + imperfect CSI (cf. arXiv 2207.09232's mobile
  hierarchical setting).
* ``straggler-heavy`` — 25% i.i.d. dropout plus three deterministic
  stragglers missing every third round, on the static channel.
* ``straggler-prox``  — the same harsh schedule with the scenario
  pinning the ``cwfl_prox`` strategy (paper §V's FedProx answer to
  partial participation / heterogeneity) as its registry-resolved
  default.
* ``snr-sweep``       — static channel, Monte-Carlo grid over overall
  SNR ξ ∈ {0, 10, 20, 30, 40} dB (the x-axis of the paper's noise-floor
  claims); `run_monte_carlo` vmaps the whole grid into one jit.
* ``cluster-churn``   — fading + mobility strong enough that the SNR
  landscape drifts, with periodic on-device re-clustering every 5 rounds
  (K-means + head election inside the scan, `lax.cond`-gated).
* ``head-failure``    — the paper's stated failure mode: Markov
  crash/recovery chains on every node (`repro.sim.faults`), so cluster
  heads / the COTAF server die mid-run and the strategy's
  ``on_head_failure`` handoff re-elects survivors.
* ``flaky-clients``   — the chaos kitchen sink: crashes, correlated
  dropout bursts, deep-fade blackouts AND scheduled i.i.d. dropout, with
  the divergence guard quarantining poisoned updates.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from repro.sim.faults import FaultConfig
from repro.sim.processes import ChannelProcessConfig
from repro.sim.scheduling import ScheduleConfig
from repro.strategies import get_strategy


@dataclasses.dataclass(frozen=True)
class Scenario:
    name: str = "paper-static"
    channel: ChannelProcessConfig = ChannelProcessConfig()
    schedule: ScheduleConfig = ScheduleConfig()
    faults: FaultConfig = FaultConfig()   # node crash/burst/blackout process
    recluster_every: int = 0              # re-run clustering every n rounds (0=never)
    snr_grid: Tuple[float, ...] = ()      # Monte-Carlo SNR axis (dB); () = cfg.snr_db
    #: Default strategy for this scenario, resolved through the
    #: `repro.strategies` registry (``None`` = caller's choice).  CLIs use
    #: it when no ``--strategy`` is given; ``FLConfig.strategy`` always
    #: wins inside the engine.
    strategy: Optional[str] = None

    @property
    def is_static(self) -> bool:
        """True ⇒ the engine takes the bit-exact paper-static fast path."""
        return (not self.channel.is_dynamic and self.schedule.is_trivial
                and self.faults.is_trivial and self.recluster_every <= 0)

    def default_strategy(self, fallback: str = "cwfl"):
        """The scenario's preferred `Strategy` object (registry-resolved),
        or ``fallback``'s when the scenario doesn't pin one."""
        return get_strategy(self.strategy or fallback)


SCENARIOS = {
    "paper-static": Scenario(),
    "mobile-fading": Scenario(
        name="mobile-fading",
        channel=ChannelProcessConfig(fading_rho=0.9, shadowing_std_db=4.0,
                                     shadowing_rho=0.9, speed=2.0,
                                     csi_error_std=0.1)),
    "straggler-heavy": Scenario(
        name="straggler-heavy",
        schedule=ScheduleConfig(dropout_prob=0.25, num_stragglers=3,
                                straggler_period=3)),
    "straggler-prox": Scenario(
        name="straggler-prox",
        schedule=ScheduleConfig(dropout_prob=0.25, num_stragglers=3,
                                straggler_period=3),
        strategy="cwfl_prox"),
    "snr-sweep": Scenario(
        name="snr-sweep",
        snr_grid=(0.0, 10.0, 20.0, 30.0, 40.0)),
    "cluster-churn": Scenario(
        name="cluster-churn",
        channel=ChannelProcessConfig(fading_rho=0.95, speed=4.0,
                                     shadowing_std_db=2.0),
        recluster_every=5),
    "head-failure": Scenario(
        name="head-failure",
        faults=FaultConfig(crash_prob=0.15, recover_prob=0.3)),
    "flaky-clients": Scenario(
        name="flaky-clients",
        schedule=ScheduleConfig(dropout_prob=0.1),
        faults=FaultConfig(crash_prob=0.05, recover_prob=0.5,
                           burst_prob=0.2, burst_recover_prob=0.5,
                           burst_frac=0.5, deep_fade_prob=0.05,
                           deep_fade_rounds=2, divergence_guard=True,
                           quarantine_norm=100.0)),
}


def get_scenario(name: str) -> Scenario:
    if name not in SCENARIOS:
        raise KeyError(f"unknown scenario {name!r}; "
                       f"choose from {sorted(SCENARIOS)}")
    return SCENARIOS[name]
