"""A tiny causal token model, as ``tiny_lm_k6.json`` runs it: a token
embedding plus a frozen positional matrix, one causal self-attention layer
with a residual, and an output head with a log-softmax over the
vocabulary, in plain ``jax.numpy``.

It is a fixture of the benchmark's tests, not a configuration of
``BENCHMARK.json``: it drives token data with per-token targets through
the harness, the program's engine and the reference.  The program's model
is this same plain model (``program_model``); what the comparison checks
is the engine's round, draw and sync against the reference's.

The positional matrix is drawn from a fixed seed and closed over by
``reference_apply``: neither trained nor synced, and not a leaf of
``reference_init``, as a frozen base is in federated fine-tuning.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

CONF = json.loads(Path(__file__).with_suffix(".json").read_text())
MODEL = CONF["model"]
V, S, D, H = (MODEL["vocab_size"], MODEL["seq_len"], MODEL["d_model"],
              MODEL["num_heads"])
FROZEN = (np.random.default_rng(1234).standard_normal((S, D))
          / math.sqrt(D)).astype(np.float32)


def program_model():
    """(init, apply, loss) of the system under test: the plain model and
    the mean next-token NLL over every position."""
    def loss(params, x, y):
        logp = reference_apply(params, x)
        return -jnp.mean(jnp.take_along_axis(logp, y[..., None],
                                             axis=-1)[..., 0])
    return reference_init, reference_apply, loss


def reference_init(key):
    k = jax.random.split(key, 6)
    scale = 1.0 / math.sqrt(D)
    params = {"embed": jax.random.normal(k[0], (V, D), jnp.float32)}
    for i, name in enumerate(("wq", "wk", "wv", "wo")):
        params[name] = scale * jax.random.normal(k[1 + i], (D, D),
                                                 jnp.float32)
    params["head"] = {"w": scale * jax.random.normal(k[5], (D, V),
                                                     jnp.float32),
                      "b": jnp.zeros((V,), jnp.float32)}
    return params


def reference_apply(params, x):
    """Log-probabilities ``(B, S, V)`` of the next token at each position
    of the int32 tokens ``x`` ``(B, S)``."""
    h = params["embed"][x] + jnp.asarray(FROZEN, params["embed"].dtype)
    B = x.shape[0]

    def heads(w):
        return jnp.dot(h, w).reshape(B, S, H, D // H)

    q, k, v = heads(params["wq"]), heads(params["wk"]), heads(params["wv"])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(D // H)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    h = h + jnp.dot(o.reshape(B, S, D), params["wo"])
    logits = jnp.dot(h, params["head"]["w"]) + params["head"]["b"]
    return jax.nn.log_softmax(logits, axis=-1)


def sample_flops() -> dict:
    """Matmul FLOPs per sequence: ``forward`` (the four projections, the
    scores and the weighted values over all S x S pairs, the head), and
    ``train`` = three times that: every matmul's two operands are trained
    or come from the trained embedding, so the backward pass computes both
    gradients of each.  The embedding lookup is a gather (no FLOPs)."""
    forward = 4 * 2 * S * D * D + 2 * 2 * S * S * D + 2 * S * D * V
    return {"forward": forward, "train": 3 * forward}
