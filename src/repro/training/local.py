"""Per-client local training between sync rounds (eq. 2 top row).

``make_local_runner`` builds a jit-able function that runs E epochs of
mini-batch SGD on ONE client's shard; the federated engine vmaps it over the
stacked K-client axis.  FedProx (paper §V) wraps the loss with the proximal
term  f_k^p(θ) = f_k(θ) + (µ_p/2)‖θ − θ_g‖²  against the latest global sync.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.obs.profiling import SCOPE_BATCH


def fedprox_wrap(loss_fn: Callable, mu_prox: float) -> Callable:
    """loss(params, x, y) -> loss + (µ_p/2)·‖params − global‖² (paper §V)."""

    def prox_loss(params, x, y, global_params):
        base = loss_fn(params, x, y)
        sq = sum(jnp.sum(jnp.square(p.astype(jnp.float32) -
                                    g.astype(jnp.float32)))
                 for p, g in zip(jax.tree.leaves(params),
                                 jax.tree.leaves(global_params)))
        return base + 0.5 * mu_prox * sq

    return prox_loss


def draw_minibatch(x, y, key, batch_size: int, sample_shape=None):
    """One minibatch of ``batch_size`` samples drawn uniformly with
    replacement from a client's shard ``x`` (``(n_k, *sample)``, or
    ``(n_k, F)`` flat rows when ``sample_shape`` is given) and labels
    ``y``.  Returns ``(xb, yb)`` with ``xb`` shaped ``(batch_size,
    *sample_shape)``: the rows are gathered, then given the sample shape
    back, so the bits are those of ``x[idx]`` on the sample-shaped shard."""
    shape = x.shape[1:] if sample_shape is None else tuple(sample_shape)
    idx = jax.random.randint(key, (batch_size,), 0, x.shape[0])
    return x[idx].reshape((batch_size, *shape)), y[idx]


def make_local_runner(loss_fn: Callable, optimizer, batch_size: int,
                      local_steps: int, mu_prox: float = 0.0,
                      sample_shape=None):
    """Returns ``run(params, opt_state, x, y, key) -> (params, opt_state, loss)``
    performing ``local_steps`` minibatch-SGD steps on one client's shard.

    ``local_steps`` = E · (N_k // batch_size) for E epochs. Batches are drawn
    by random index sampling (with replacement across steps — standard for
    vmapped FL simulators; per-epoch permutation costs O(N log N) per client).

    Layout contract: the engine hands ``x`` as flat rows ``(n_k, F)``,
    flattened once and eagerly from ``(n_k, *sample_shape)`` before the
    client data is embedded in the round program (`repro.sim.engine.
    client_rows`), and ``loss_fn`` still sees ``(batch, *sample_shape)``.
    A sample such as (28, 28, 1) cannot be fetched as one row under the
    TPU's (8, 128) tiling, so with sample-shaped shards XLA puts the
    sample index in the lanes: the draw becomes a lane gather and the
    whole shard set is relaid out once a round.  ``sample_shape=None``
    takes ``x`` as already sample-shaped.
    """
    base_loss = loss_fn
    prox = mu_prox > 0.0
    if prox:
        prox_loss = fedprox_wrap(loss_fn, mu_prox)
        grad_fn = jax.value_and_grad(prox_loss)
    else:
        grad_fn = jax.value_and_grad(base_loss)

    def run(params, opt_state, x, y, key):
        global_params = params  # snapshot at sync = θ_g for FedProx

        def step(carry, k):
            p, s = carry
            with jax.named_scope(SCOPE_BATCH):
                xb, yb = draw_minibatch(x, y, k, batch_size, sample_shape)
            if prox:
                loss, grads = grad_fn(p, xb, yb, global_params)
            else:
                loss, grads = grad_fn(p, xb, yb)
            updates, s = optimizer.update(grads, s, p)
            p = jax.tree.map(jnp.add, p, updates)
            return (p, s), loss

        keys = jax.random.split(key, local_steps)
        (params, opt_state), losses = jax.lax.scan(step, (params, opt_state),
                                                   keys)
        return params, opt_state, jnp.mean(losses)

    return run
