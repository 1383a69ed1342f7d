"""Kernel micro-benchmarks: interpret-mode Pallas vs pure-jnp oracle.

NOTE: on this CPU-only container the Pallas kernels execute in interpret
mode (python), so wall-clock favors the jnp oracle — the numbers here are
correctness/latency bookkeeping, not TPU performance. The TPU-relevant
analysis is the VMEM/blocking design (DESIGN.md §4/§8), the roofline, and
the modeled HBM traffic of the fused round (``hbm_bytes_model``), which
``benchmarks/run.py`` persists to ``BENCH_kernels.json`` so the perf
trajectory stays machine-readable across PRs.

The XLA-compiled round variants also record the compiler's own
``cost_analysis()`` "bytes accessed" next to ``modeled_hbm_bytes`` with
a >20% model-vs-measured drift flag (informational on CPU — XLA fuses
and pads differently than the TPU HBM accounting the model targets; the
interpret-mode Pallas row has no XLA executable to measure).  Gate a
fresh file against the committed baseline with
``benchmarks/compare.py``.
"""
from __future__ import annotations

import statistics
import time

import jax
import jax.numpy as jnp

from repro.kernels.cwfl_round import cwfl_round, hbm_bytes_model
from repro.kernels.flash_attention import flash_attention as fa_kernel
from repro.kernels.ota_aggregate import ota_aggregate
from repro.kernels.ref import (cwfl_round_ref, flash_attention_ref,
                               ota_aggregate_ref)

# Paper-scale round: K=50 clients, C=3 clusters, d = MNIST-MLP params.
ROUND_K, ROUND_C, ROUND_D = 50, 3, 180000


def _time(f, *args, n: int = 5, warmup: int = 2) -> float:
    """Median wall time in µs over ``n`` timed calls after ``warmup``
    compile/cache runs (``time.perf_counter``: monotonic, high-res)."""
    for _ in range(warmup):
        jax.block_until_ready(f(*args))
    samples = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args))
        samples.append((time.perf_counter() - t0) * 1e6)
    return statistics.median(samples)


def _xla_bytes(fn, *args):
    """XLA's measured ``bytes accessed`` for ``jit(fn)(*args)`` via the
    compiled executable's ``cost_analysis()`` (None when the backend
    reports nothing).  The empirical cross-check on the analytic
    ``hbm_bytes_model``: same dataflow, counted by the compiler instead
    of by hand."""
    compiled = jax.jit(fn).lower(*args).compile()
    val = (compiled.cost_analysis() or {}).get("bytes accessed")
    return None if val is None else int(val)


def _drift_tag(modeled: int, measured) -> dict:
    """``xla_bytes_accessed`` next to the model, plus a >20% drift flag —
    informational on CPU, where XLA fuses/pads differently than the TPU
    HBM accounting the model targets."""
    if not measured:
        return {"xla_bytes_accessed": measured}
    drift = abs(measured - modeled) / modeled
    return {"xla_bytes_accessed": measured,
            "model_vs_xla_drift": round(drift, 4),
            "model_vs_xla_drift_over_20pct": bool(drift > 0.20)}


def _three_pass_round():
    """The unfused baseline: each phase a separate jitted call, so every
    intermediate (θ̃, θ̄) round-trips through device memory — the traffic
    pattern the fused kernel removes.  Broadcast and consensus are
    separate passes (θ̄ read twice), matching ``hbm_bytes_model``'s
    5·C·d accounting for the unfused round."""
    p1 = jax.jit(lambda a, s, n: a @ s + n)
    p2 = jax.jit(lambda b, tt, n: b @ tt + n)
    p3 = jax.jit(lambda m, tb: m @ tb)
    p4 = jax.jit(lambda tb: jnp.mean(tb, axis=0))

    def run(s, a, n1, b, n2, m):
        theta_tilde = p1(a, s, n1)
        theta_bar = p2(b, theta_tilde, n2)
        return p3(m, theta_bar), p4(theta_bar)

    return run


def run():
    """Returns a list of row dicts: name, us, derived, plus machine-
    readable extras (modeled HBM bytes for the round variants)."""
    rows = []
    key = jax.random.PRNGKey(0)
    K, C, d = ROUND_K, ROUND_C, ROUND_D

    s = jax.random.normal(key, (K, d))
    a = jax.random.uniform(jax.random.PRNGKey(1), (C, K))
    n1 = 0.1 * jax.random.normal(jax.random.PRNGKey(2), (C, d))
    b = jax.random.uniform(jax.random.PRNGKey(3), (C, C))
    n2 = 0.1 * jax.random.normal(jax.random.PRNGKey(4), (C, d))
    m = jax.random.uniform(jax.random.PRNGKey(5), (K, C))

    traffic = hbm_bytes_model(K, C, d)
    shape_tag = f"K{K}_C{C}_d{d}"

    fused_us = _time(lambda: cwfl_round(s, a, n1, b, n2, m, tile=2048))
    rows.append({
        "name": "cwfl_round_fused_pallas_interp", "us": fused_us,
        "derived": f"{shape_tag};interpret-mode",
        "modeled_hbm_bytes": traffic["fused_bytes"],
    })

    three_pass = _three_pass_round()
    unfused_us = _time(lambda: three_pass(s, a, n1, b, n2, m))
    # Measured counterpart of the 5·C·d unfused accounting: each pass is
    # its own XLA executable, so its intermediates round-trip through
    # memory exactly as the model assumes — sum the per-pass figures.
    theta_tilde = a @ s + n1
    theta_bar = b @ theta_tilde + n2
    unfused_xla = [
        _xla_bytes(lambda A, S, N: A @ S + N, a, s, n1),
        _xla_bytes(lambda B, TT, N: B @ TT + N, b, theta_tilde, n2),
        _xla_bytes(lambda M, TB: M @ TB, m, theta_bar),
        _xla_bytes(lambda TB: jnp.mean(TB, axis=0), theta_bar),
    ]
    unfused_meas = (None if any(v is None for v in unfused_xla)
                    else sum(unfused_xla))
    rows.append({
        "name": "cwfl_round_three_pass_baseline", "us": unfused_us,
        "derived": (f"{shape_tag};"
                    f"traffic_ratio={traffic['traffic_ratio']:.2f}x"),
        "modeled_hbm_bytes": traffic["unfused_bytes"],
        **_drift_tag(traffic["unfused_bytes"], unfused_meas),
    })

    fused_jnp_us = _time(lambda: cwfl_round_ref(s, a, n1, b, n2, m))
    rows.append({
        "name": "cwfl_round_jnp_ref", "us": fused_jnp_us,
        "derived": f"{shape_tag};single-jit",
        "modeled_hbm_bytes": traffic["fused_bytes"],
        **_drift_tag(traffic["fused_bytes"],
                     _xla_bytes(cwfl_round_ref, s, a, n1, b, n2, m)),
    })

    rows.append({
        "name": "ota_aggregate_pallas_interp",
        "us": _time(lambda: ota_aggregate(s, a, n1, tile=2048)),
        "derived": "interpret-mode"})
    rows.append({
        "name": "ota_aggregate_jnp_ref",
        "us": _time(lambda: ota_aggregate_ref(s, a, n1)),
        "derived": "-"})

    q = jax.random.normal(key, (1, 4, 512, 64))
    k = jax.random.normal(jax.random.PRNGKey(6), (1, 2, 512, 64))
    v = jax.random.normal(jax.random.PRNGKey(7), (1, 2, 512, 64))
    rows.append({
        "name": "flash_attention_pallas_interp",
        "us": _time(lambda: fa_kernel(q, k, v, block_q=128, block_k=128)),
        "derived": "interpret-mode"})
    rows.append({
        "name": "flash_attention_jnp_ref",
        "us": _time(lambda: flash_attention_ref(q, k, v)),
        "derived": "-"})
    return rows
