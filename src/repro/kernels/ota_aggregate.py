"""Pallas TPU kernel: fused OTA-MAC aggregation (CWFL phase 1).

The per-round hot-spot of the paper: for every cluster c, the head receives
    y_c = Σ_k W[c,k] · s_k + n_c            (eq. 7/8 after channel inversion)
over the d-dimensional flattened parameter vector. Unfused, this is three
HBM round-trips over (K, d) data (scale, reduce, add-noise); the kernel does
one pass with a VMEM-resident (K, TILE) block per grid step.

TPU-native design notes (DESIGN.md §8): the MAC superposition maps to an
MXU contraction over the K (client) dim; the grid runs over d-tiles only
(TILE a multiple of 128 lanes), each step writing all C cluster rows of its
tile; the weights matrix (C, K) stays fully resident in VMEM (tiny).
Validated in interpret mode against repro.kernels.ref.ota_aggregate_ref.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_TILE = 2048

# The OTA sync's matmuls run in f32 on every backend.  A TPU's default f32
# contraction is one bf16 pass: at K=50, C=3 it puts ~4e-3 of rounding
# error on unit-scale parameters, the size of the 40 dB channel noise the
# simulation studies.  On the CPU this setting changes no bit.
SYNC_PRECISION = jax.lax.Precision.HIGHEST


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` → interpret off-TPU (CPU validation), compiled on TPU.

    Shared by every kernel entry point so TPU callers get the compiled
    kernel by default instead of a silently deoptimized interpreter run.
    """
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _ota_kernel(w_ref, s_ref, n_ref, o_ref):
    """Grid: (d // TILE,). Blocks: w (C, K) weights, VMEM-resident for
    the whole grid; s (K, TILE) signals; n/o (C, TILE).  Every block
    spans its array's full leading dim, which the TPU's (8, 128) block
    tiling rule accepts for any C and K."""
    w = w_ref[...].astype(jnp.float32)          # (C, K)
    s = s_ref[...].astype(jnp.float32)          # (K, TILE)
    n = n_ref[...].astype(jnp.float32)          # (C, TILE)
    acc = jax.lax.dot_general(
        w, s, (((1,), (0,)), ((), ())), precision=SYNC_PRECISION,
        preferred_element_type=jnp.float32)      # (C, TILE)
    o_ref[...] = (acc + n).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def ota_aggregate(signals: jnp.ndarray, weights: jnp.ndarray,
                  noise: jnp.ndarray, *, tile: int = DEFAULT_TILE,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """y = weights @ signals + noise, fused.

    signals: (K, d); weights: (C, K); noise: (C, d). Returns (C, d).
    d is padded to a multiple of ``tile`` internally.  ``interpret=None``
    resolves backend-aware (interpret off-TPU, compiled on TPU).
    """
    interpret = resolve_interpret(interpret)
    K, d = signals.shape
    C = weights.shape[0]
    dp = -(-d // tile) * tile
    if dp != d:
        signals = jnp.pad(signals, ((0, 0), (0, dp - d)))
        noise = jnp.pad(noise, ((0, 0), (0, dp - d)))

    out = pl.pallas_call(
        _ota_kernel,
        grid=(dp // tile,),
        in_specs=[
            pl.BlockSpec((C, K), lambda t: (0, 0)),
            pl.BlockSpec((K, tile), lambda t: (0, t)),
            pl.BlockSpec((C, tile), lambda t: (0, t)),
        ],
        out_specs=pl.BlockSpec((C, tile), lambda t: (0, t)),
        out_shape=jax.ShapeDtypeStruct((C, dp), signals.dtype),
        interpret=interpret,
    )(weights, signals, noise)
    return out[:, :d]
