"""``run_rounds``: the window re-runs the program that
``run_rounds(timers=PhaseTimers())`` compiled, one T-round trajectory a
call.

The first call takes the carry and round keys of the engine's own
``prepare`` for the run's seed and is the one compared with the
reference; each later call continues from the carry the last one
returned, on fresh round keys.

A traffic mix names this file by its ``"executor": "run_rounds"`` and
gives the engine's ``scenario``.  The program is traced at the
configuration's matmul precision.
"""
from __future__ import annotations

import numpy as np


class Executor:

    def __init__(self, conf, traffic, model, inputs):
        from repro.obs import PhaseTimers
        from repro.sim import get_scenario, run_rounds
        from repro.sim.engine import _build

        from benchlib import harness

        self.conf, self.model, self.inputs = conf, model, inputs
        args, cfg, tcfg = harness.program_args(conf, model, inputs)
        scenario = get_scenario(traffic["scenario"])
        self.timers = PhaseTimers()
        self.precision = harness.matmul_precision(conf)
        with self.precision:
            run_rounds(*args, cfg, scenario=scenario, topo_cfg=tcfg,
                       timers=self.timers)
        self.exe = self.timers.executables[-1]
        self.prepare, _ = _build(*args, cfg, scenario, tcfg)
        self.snr = cfg.snr_db
        self.rounds_per_call = cfg.rounds
        self.feed = None

    def start(self, seed: int) -> None:
        """The first call from ``seed``: the one compared."""
        import jax
        with self.precision:
            _, carry0, xs0 = self.prepare(seed, self.snr)
        if self.feed is None:
            leaves, treedef = jax.tree.flatten(xs0)

            @jax.jit
            def feed(base, i):
                k = jax.random.fold_in(base, i + 1)
                return jax.tree.unflatten(treedef, [
                    jax.random.split(jax.random.fold_in(k, j), x.shape[0])
                    if x.dtype == np.uint32 and x.shape[1:] == (2,) else x
                    for j, x in enumerate(leaves)])

            self.feed = feed
        self.seed, self.base = seed, jax.random.PRNGKey(seed)
        jax.block_until_ready(self.feed(self.base, 0))
        self.carry, out = jax.block_until_ready(self.exe(carry0, xs0))
        take = lambda c: {"consensus": jax.device_get(c["consensus"]),
                          "stacked": jax.device_get(c["stacked"])}
        loss, acc = jax.device_get(out)
        self.first = {"loss": loss, "acc": acc, "state0": take(carry0),
                      "state1": take(self.carry)}
        self.calls = 1

    def call(self, i: int) -> None:
        import jax
        from jax.profiler import TraceAnnotation
        with TraceAnnotation("bench.feed"):
            xs = self.feed(self.base, i)
        with TraceAnnotation("bench.dispatch"):
            self.carry, out = self.exe(self.carry, xs)
        with TraceAnnotation("bench.wait"):
            jax.block_until_ready(out)
        self.calls += 1

    def free(self) -> None:
        self.exe = self.carry = self.feed = None
        self.timers.executables.clear()

    def check(self) -> tuple[dict, int, list]:
        """(numbers compared, trajectories found wrong, notes)."""
        from benchlib import checks
        numbers, note = checks.against_reference(
            self.first, self.conf, self.model, self.inputs,
            plan_seed=self.conf["fl"]["plan_seed"], seed=self.seed)
        failed = not checks.verdict(numbers, self.conf["check"]["limits"])[0]
        return numbers, int(failed), [f"trajectory seed {self.seed}: {note}"]
