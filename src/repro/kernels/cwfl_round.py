"""Pallas TPU kernel: the fused single-pass CWFL sync round (Algorithm 1).

The unfused round executes eq. (8)/(9) + broadcast as three separate passes
over the ``d``-dimensional flattened parameter state:

    θ̃ = Ã·S + n₁          phase 1: intra-cluster OTA MAC      (C, d)
    θ̄ = B̃·θ̃ + n₂          phase 2: inter-head consensus mix   (C, d)
    new = Mᵀ·θ̄            phase 3: error-free broadcast        (K, d)
    consensus = mean_c θ̄                                        (d,)

which costs one HBM write + read of θ̃ and one write + two reads of θ̄ on
top of the unavoidable S read and new/consensus write.  This kernel runs
the whole round per ``d``-tile in VMEM: the tiny ``(C, K)``, ``(C, C)``
and ``(K, C)`` weight matrices stay fully VMEM-resident across the grid,
the ``(K, TILE)`` signal block is read once, and only the final
``new``/``consensus`` tiles are written back — the intermediate θ̃/θ̄
never touch HBM (see :func:`hbm_bytes_model` and DESIGN.md §Perf).

TPU-native notes (DESIGN.md §8): all three matmuls ride the MXU via
``dot_general`` at f32 precision (``SYNC_PRECISION``) with
``preferred_element_type=f32`` (bf16 signals accumulate in f32); tiles
are 128-lane aligned; ``d`` is padded to a tile multiple internally and
the pad sliced off (ragged last tile).  Validated
in interpret mode against :func:`repro.kernels.ref.cwfl_round_ref`.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ota_aggregate import (DEFAULT_TILE, SYNC_PRECISION,
                                         resolve_interpret)

# Below this flat dimension the round is a handful of tiny matmuls; the
# jnp reference is a single fused XLA computation and the kernel's tile
# machinery cannot pay off.
PALLAS_MIN_DIM = 512


def _cwfl_round_kernel(a_ref, b_ref, m_ref, s_ref, n1_ref, n2_ref,
                       new_ref, cons_ref):
    """Grid: (d // TILE,). Blocks: a (C, K), b (C, C), m (K, C) —
    VMEM-resident for the whole grid; s (K, TILE), n1/n2 (C, TILE)
    streamed; new (K, TILE) and cons (1, TILE) written once."""
    s = s_ref[...].astype(jnp.float32)                       # (K, T)
    a = a_ref[...].astype(jnp.float32)                       # (C, K)
    b = b_ref[...].astype(jnp.float32)                       # (C, C)
    m = m_ref[...].astype(jnp.float32)                       # (K, C)

    dims = (((1,), (0,)), ((), ()))
    theta_tilde = jax.lax.dot_general(
        a, s, dims, precision=SYNC_PRECISION,
        preferred_element_type=jnp.float32)
    theta_tilde = theta_tilde + n1_ref[...].astype(jnp.float32)   # (C, T)
    theta_bar = jax.lax.dot_general(
        b, theta_tilde, dims, precision=SYNC_PRECISION,
        preferred_element_type=jnp.float32)
    theta_bar = theta_bar + n2_ref[...].astype(jnp.float32)       # (C, T)
    new = jax.lax.dot_general(
        m, theta_bar, dims, precision=SYNC_PRECISION,
        preferred_element_type=jnp.float32)                       # (K, T)
    new_ref[...] = new.astype(new_ref.dtype)
    cons_ref[...] = jnp.mean(theta_bar, axis=0, keepdims=True)


def _fit_tile(tile: int, d: int) -> int:
    """Clamp the d-tile to the 128-lane-aligned cover of d (no point
    padding a 512-wide round out to a 2048 tile)."""
    return max(128, min(tile, -(-d // 128) * 128))


@functools.partial(jax.jit, static_argnames=("tile", "interpret", "guard"))
def cwfl_round(signals: jnp.ndarray, phase1: jnp.ndarray,
               noise1: jnp.ndarray, phase2: jnp.ndarray,
               noise2: jnp.ndarray, broadcast: jnp.ndarray, *,
               tile: int = DEFAULT_TILE,
               interpret: Optional[bool] = None, guard: bool = False):
    """One fused CWFL sync round over flat client signals.

    signals: (K, d) client parameter vectors (f32/bf16; f32 accumulate).
    phase1:  (C, K) OTA MAC amplitudes Ã (precoded/normalized by caller).
    noise1:  (C, d) phase-1 receiver AWGN (pre-generated).
    phase2:  (C, C) consensus mix B̃.
    noise2:  (C, d) phase-2 equivalent receiver noise.
    broadcast: (K, C) phase-3 downlink matrix (usually ``membership.T``).
    guard (static): in-kernel NaN/dead-Ã-row guard (fault scenarios).
    Returns ``(new (K, d) signals.dtype, consensus (d,) f32)``.
    """
    interpret = resolve_interpret(interpret)
    K, d = signals.shape
    C = phase1.shape[0]
    tile = _fit_tile(tile, d)
    dp = -(-d // tile) * tile
    if dp != d:
        signals = jnp.pad(signals, ((0, 0), (0, dp - d)))
        noise1 = jnp.pad(noise1, ((0, 0), (0, dp - d)))
        noise2 = jnp.pad(noise2, ((0, 0), (0, dp - d)))

    new, cons = pl.pallas_call(
        _cwfl_round_kernel_guard if guard else _cwfl_round_kernel,
        grid=(dp // tile,),
        in_specs=[
            pl.BlockSpec((C, K), lambda t: (0, 0)),
            pl.BlockSpec((C, C), lambda t: (0, 0)),
            pl.BlockSpec((K, C), lambda t: (0, 0)),
            pl.BlockSpec((K, tile), lambda t: (0, t)),
            pl.BlockSpec((C, tile), lambda t: (0, t)),
            pl.BlockSpec((C, tile), lambda t: (0, t)),
        ],
        out_specs=[
            pl.BlockSpec((K, tile), lambda t: (0, t)),
            pl.BlockSpec((1, tile), lambda t: (0, t)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((K, dp), signals.dtype),
            jax.ShapeDtypeStruct((1, dp), jnp.float32),
        ],
        interpret=interpret,
        # The compiled custom call (and its events in a device trace) is
        # named after this: ``cwfl_round.<n>`` under any scope or wrapper.
        name="cwfl_round",
    )(phase1.astype(jnp.float32), phase2.astype(jnp.float32),
      broadcast.astype(jnp.float32), signals, noise1.astype(jnp.float32),
      noise2.astype(jnp.float32))
    return new[:, :d], cons[0, :d]


def _cwfl_round_kernel_guard(a_ref, b_ref, m_ref, s_ref, n1_ref, n2_ref,
                             new_ref, cons_ref):
    """:func:`_cwfl_round_kernel` with the fault guard (mirrors
    ``repro.kernels.ref.cwfl_round_ref(..., guard=True)``): sanitize
    non-finite signals to 0 and zero all-dead Ã rows before the consensus
    mix.  Cheap VPU elementwise ops on the already-VMEM-resident blocks;
    the Ã row-sum reduction is (C, K)-tiny and grid-invariant.  Kept as a
    separate kernel so the faults-off trace is byte-identical to the
    unguarded round (origin names + source lines are baked into jaxprs).
    """
    s = s_ref[...].astype(jnp.float32)                       # (K, T)
    a = a_ref[...].astype(jnp.float32)                       # (C, K)
    b = b_ref[...].astype(jnp.float32)                       # (C, C)
    m = m_ref[...].astype(jnp.float32)                       # (K, C)
    s = jnp.where(jnp.isfinite(s), s, 0.0)

    dims = (((1,), (0,)), ((), ()))
    theta_tilde = jax.lax.dot_general(
        a, s, dims, precision=SYNC_PRECISION,
        preferred_element_type=jnp.float32)
    theta_tilde = theta_tilde + n1_ref[...].astype(jnp.float32)   # (C, T)
    dead = jnp.sum(jnp.abs(a), axis=1, keepdims=True) <= 0.0
    theta_tilde = jnp.where(dead, 0.0, theta_tilde)
    theta_bar = jax.lax.dot_general(
        b, theta_tilde, dims, precision=SYNC_PRECISION,
        preferred_element_type=jnp.float32)
    theta_bar = theta_bar + n2_ref[...].astype(jnp.float32)       # (C, T)
    new = jax.lax.dot_general(
        m, theta_bar, dims, precision=SYNC_PRECISION,
        preferred_element_type=jnp.float32)                       # (K, T)
    new_ref[...] = new.astype(new_ref.dtype)
    cons_ref[...] = jnp.mean(theta_bar, axis=0, keepdims=True)


def cwfl_round_auto(signals, phase1, noise1, phase2, noise2, broadcast, *,
                    tile: int = DEFAULT_TILE,
                    interpret: Optional[bool] = None,
                    use_pallas: Optional[bool] = None,
                    guard: bool = False):
    """Route one round through the fused kernel when the flat dimension is
    large enough to benefit (``d >= PALLAS_MIN_DIM``), else the jnp
    reference (a single fused XLA computation at small d).  ``guard``
    engages the NaN/dead-row guard on whichever route is taken."""
    from repro.kernels.ref import cwfl_round_ref

    if use_pallas is None:
        use_pallas = signals.shape[1] >= PALLAS_MIN_DIM
    if use_pallas:
        return cwfl_round(signals, phase1, noise1, phase2, noise2,
                          broadcast, tile=tile, interpret=interpret,
                          guard=guard)
    return cwfl_round_ref(signals, phase1, noise1, phase2, noise2, broadcast,
                          guard=guard)


def hbm_bytes_model(K: int, C: int, d: int, itemsize: int = 4) -> dict:
    """Modeled HBM traffic per sync round (weights are O(KC), negligible).

    Both variants must read S (K·d) + the two noise fields (2·C·d) and
    write new (K·d) + consensus (d).  The unfused three-pass round adds a
    write + read of θ̃ (2·C·d) and a write + two reads of θ̄ (3·C·d) —
    5·C·d extra scalars round-tripped through HBM.
    """
    base = d * (2 * K + 2 * C + 1)
    return {
        "fused_bytes": itemsize * base,
        "unfused_bytes": itemsize * (base + 5 * C * d),
        "traffic_ratio": (base + 5 * C * d) / base,
    }
