"""Pure-jnp oracles for the Pallas kernels (tests assert_allclose vs these)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.ota_aggregate import SYNC_PRECISION


def _mm(a, b):
    """f32 matmul at the sync's precision on every backend."""
    return jnp.matmul(a, b, precision=SYNC_PRECISION)


def ota_aggregate_ref(signals: jnp.ndarray, weights: jnp.ndarray,
                      noise: jnp.ndarray) -> jnp.ndarray:
    """Phase-1 OTA MAC for all clusters at once.

    signals: (K, d) channel-inverted client parameter vectors.
    weights: (C, K) per-(cluster, client) amplitudes (0 for non-members).
    noise:   (C, d) receiver AWGN (pre-generated; the MAC adds it).
    Returns: (C, d) received aggregates  y = W @ S + N.
    """
    return (_mm(weights.astype(jnp.float32), signals.astype(jnp.float32))
            + noise.astype(jnp.float32)).astype(signals.dtype)


def cwfl_round_ref(signals: jnp.ndarray, phase1: jnp.ndarray,
                   noise1: jnp.ndarray, phase2: jnp.ndarray,
                   noise2: jnp.ndarray, broadcast: jnp.ndarray,
                   guard: bool = False):
    """Three-pass CWFL sync round (the unfused baseline the fused
    ``cwfl_round`` kernel must match bit-for-bit in f32).

    signals: (K, d); phase1: (C, K) Ã; noise1: (C, d); phase2: (C, C) B̃;
    noise2: (C, d); broadcast: (K, C) downlink matrix (membership.T).
    Returns ``(new (K, d) signals.dtype, consensus (d,) f32)``.

    ``guard`` (STATIC flag, fault scenarios — DESIGN.md §Faults): the
    CWFL cousin of the flash-attention "fully-masked rows -> 0" rule
    below.  Non-finite signals are sanitized to 0 *before* the phase-1
    matmul (a quarantined client's zero amplitude still multiplies its
    NaN signal — 0 × NaN = NaN — so masking alone cannot contain it),
    and a fully-masked Ã row (an all-failed cluster) forces its θ̃ row —
    noise included — to exactly 0 instead of the renormalized noise
    blow-up.  Guard-off traces a byte-identical jaxpr.
    """
    s = signals.astype(jnp.float32)
    a = phase1.astype(jnp.float32)
    if guard:
        s = jnp.where(jnp.isfinite(s), s, 0.0)
    theta_tilde = _mm(a, s) + noise1.astype(jnp.float32)
    if guard:
        dead = jnp.sum(jnp.abs(a), axis=1, keepdims=True) <= 0.0
        theta_tilde = jnp.where(dead, 0.0, theta_tilde)
    theta_bar = (_mm(phase2.astype(jnp.float32), theta_tilde)
                 + noise2.astype(jnp.float32))
    new = _mm(broadcast.astype(jnp.float32), theta_bar).astype(signals.dtype)
    return new, jnp.mean(theta_bar, axis=0)


def flash_attention_ref(q, k, v, *, causal: bool = True, window: int = 0,
                        cap: float = 0.0):
    """Exact softmax attention. q: (B, H, Sq, D); k, v: (B, KV, Skv, D)."""
    B, H, Sq, D = q.shape
    KV = k.shape[1]
    G = H // KV
    qg = (q.astype(jnp.float32) * (D ** -0.5)).reshape(B, KV, G, Sq, D)
    s = jnp.einsum("bkgqd,bksd->bkgqs", qg, k.astype(jnp.float32))
    if cap > 0.0:
        s = cap * jnp.tanh(s / cap)
    Skv = k.shape[2]
    q_pos = jnp.arange(Sq)[:, None]
    k_pos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked rows -> 0
    o = jnp.einsum("bkgqs,bksd->bkgqd", p, v.astype(jnp.float32))
    return o.reshape(B, H, Sq, D).astype(q.dtype)
