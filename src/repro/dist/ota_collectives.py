"""Flat-vector / shard_map lowerings of the CWFL aggregation.

Two entry families:

* ``phase1_ota_flat`` / ``cwfl_aggregate_flat`` — Algorithm 1 on a flat
  ``(K, d)`` client-signal matrix.  The channel math (eq. 5 precoding,
  eq. 8 receiver scaling, lemma-2 noise) is the *same code* the reference
  operator :func:`repro.core.cwfl.aggregate` uses; the full sync round —
  OTA MAC → consensus mix → broadcast over the d-dimensional flattened
  parameters, the per-round hot spot — is routed through the fused
  single-pass Pallas kernel :func:`repro.kernels.cwfl_round.cwfl_round`
  when the vector is large enough to benefit (``d >= PALLAS_MIN_DIM``),
  keeping the intermediate θ̃/θ̄ states out of HBM entirely.
* ``ota_allreduce_tree`` / ``build_gradient_allreduce`` — the device
  collective: the hierarchical two-phase OTA all-reduce applied to
  gradient/parameter pytrees across the mesh's ``data`` axis (one client
  per data rank), either inside an existing ``jax.shard_map`` body or as a
  standalone jitted collective.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import cwfl
from repro.core.cwfl import CWFLState
from repro.dist.fl_integration import FLPlan, hierarchical_ota_allreduce
from repro.kernels.cwfl_round import PALLAS_MIN_DIM, cwfl_round_auto
from repro.kernels.ota_aggregate import DEFAULT_TILE
from repro.kernels.ota_aggregate import ota_aggregate as _pallas_ota
from repro.kernels.ref import ota_aggregate_ref
from repro.utils import tree_flatten_vector, tree_unflatten_vector


def phase1_ota_flat(signals: jnp.ndarray, state: CWFLState, key: jax.Array,
                    *, normalize: bool = True, precode: bool = True,
                    tile: int = DEFAULT_TILE,
                    interpret: Optional[bool] = None,
                    use_pallas: Optional[bool] = None) -> jnp.ndarray:
    """Phase-1 OTA MAC on flat vectors: ``(K, d) -> (C, d)`` (eq. 8).

    Matches :func:`repro.core.cwfl.aggregate`'s phase 1 leaf-for-leaf when
    the pytree is flattened to one vector per client.  ``interpret``
    defaults to the Pallas interpreter off-TPU (CPU validation) and the
    compiled kernel on TPU.
    """
    _, d = signals.shape
    sig32 = signals.astype(jnp.float32)
    # a flat (K, d) matrix is itself a K-stacked pytree, so the reference
    # operator's weight math applies verbatim (no twin copy to drift).
    a, eff_std, _, _, _ = cwfl.round_coefficients(
        state, sig32, normalize, precode)
    noise = eff_std[:, None] * jax.random.normal(
        key, (a.shape[0], d), jnp.float32)
    if use_pallas is None:
        use_pallas = d >= PALLAS_MIN_DIM
    if use_pallas:
        return _pallas_ota(sig32, a, noise, tile=tile, interpret=interpret)
    return ota_aggregate_ref(sig32, a, noise)


def cwfl_aggregate_flat(signals: jnp.ndarray, state: CWFLState,
                        key: jax.Array, *, normalize: bool = True,
                        precode: bool = True, tile: int = DEFAULT_TILE,
                        interpret: Optional[bool] = None,
                        use_pallas: Optional[bool] = None):
    """Full Algorithm 1 on a flat ``(K, d)`` matrix, single-pass fused.

    Returns ``(new_signals (K, d), consensus (d,))`` — the flat-vector twin
    of :func:`repro.core.cwfl.aggregate` (exactly equal in the noiseless
    case; noise keys are split differently per leaf in the pytree path).
    Above ``PALLAS_MIN_DIM`` the whole round (MAC, consensus mix,
    broadcast, consensus mean) runs in one Pallas pass per d-tile; below,
    the jnp three-matmul reference.
    """
    _, d = signals.shape
    k1, k2 = jax.random.split(key)
    sig32 = signals.astype(jnp.float32)

    a, eff_std, b, kappa, m_back = cwfl.round_coefficients(
        state, sig32, normalize, precode)
    n1 = eff_std[:, None] * jax.random.normal(
        k1, (a.shape[0], d), jnp.float32)
    n2 = kappa[:, None] * jax.random.normal(
        k2, (a.shape[0], d), jnp.float32)

    new32, consensus = cwfl_round_auto(
        sig32, a, n1, b, n2, m_back, tile=tile,
        interpret=interpret, use_pallas=use_pallas)
    return new32.astype(signals.dtype), consensus


# ---------------------------------------------------------------------------
# Device collectives (shard_map over the data axis).
# ---------------------------------------------------------------------------

def ota_allreduce_tree(tree, plan: FLPlan, key: jax.Array,
                       axis_name: str = "data"):
    """Aggregate a local gradient/parameter pytree across ``axis_name`` with
    the hierarchical OTA collective.  Call INSIDE a ``jax.shard_map`` body;
    every rank returns the identical consensus tree."""
    flat = tree_flatten_vector(tree)
    out = hierarchical_ota_allreduce(flat, plan, key, axis_name)
    return tree_unflatten_vector(out, tree)


def build_gradient_allreduce(mesh, plan: FLPlan, axis_name: str = "data"):
    """Standalone jitted collective over K-stacked client pytrees.

    The returned ``agg(stacked_tree, key)`` maps leaves ``(K, ...)`` (client
    axis sharded over ``axis_name``; K must equal the axis size) to the
    same-shaped tree where every client slice holds the OTA consensus.
    """
    axis_size = dict(mesh.shape)[axis_name]
    if axis_size != plan.num_clients:
        # the per-rank weight-column lookup clamps out-of-range indices —
        # a silent wrong answer without this check.
        raise ValueError(
            f"plan has {plan.num_clients} clients but mesh axis "
            f"{axis_name!r} has {axis_size} ranks; one client per rank")

    def agg(stacked_tree, key):
        def body(local_tree, key):
            local = jax.tree.map(lambda x: x[0], local_tree)
            out = ota_allreduce_tree(local, plan, key, axis_name)
            return jax.tree.map(lambda x: x[None], out)

        specs = jax.tree.map(lambda _: P(axis_name), stacked_tree)
        f = shard_map(body, mesh=mesh, in_specs=(specs, P()),
                      out_specs=specs)
        return f(stacked_tree, key)

    return jax.jit(agg)
