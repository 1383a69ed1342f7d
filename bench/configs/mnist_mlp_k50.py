"""The paper's MNIST model (arXiv 2211.03363 §V): a 784→200→100→64→10 MLP
with ReLU and a log-softmax head, as ``mnist_mlp_k50.json`` runs it.

``program_model`` hands the benchmark the program's own model
(``repro.models.make_mnist_mlp``).  ``reference_init``/``reference_apply``
are the plain float32 model the reference trains, written from the paper
(its matmul precision is the reference's to set):
He-normal weights, zero biases, drawn with the program's key schedule so
that both start from the same parameters.  ``sample_flops`` counts the
matmul operations one sample needs, from the widths alone.
"""
from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp

CONF = json.loads(Path(__file__).with_suffix(".json").read_text())
MODEL = CONF["model"]
DIMS = [MODEL["input_hw"][0] * MODEL["input_hw"][1] * MODEL["input_hw"][2],
        *MODEL["hidden"], MODEL["num_classes"]]


def program_model():
    """(init, apply, loss) of the system under test."""
    from repro.models import make_mnist_mlp, nll_loss
    init, apply = make_mnist_mlp(input_hw=tuple(MODEL["input_hw"]),
                                 hidden=tuple(MODEL["hidden"]),
                                 num_classes=MODEL["num_classes"])
    return init, apply, lambda p, x, y: nll_loss(apply(p, x), y)


def reference_init(key):
    params = {}
    for i, k in enumerate(jax.random.split(key, len(DIMS) - 1)):
        w_key, _ = jax.random.split(k)
        params[f"fc{i}"] = {
            "w": jnp.sqrt(2.0 / DIMS[i]) * jax.random.normal(
                w_key, (DIMS[i], DIMS[i + 1]), jnp.float32),
            "b": jnp.zeros((DIMS[i + 1],), jnp.float32)}
    return params


def reference_apply(params, x):
    h = x.reshape(x.shape[0], -1)
    for i in range(len(DIMS) - 1):
        p = params[f"fc{i}"]
        h = jnp.dot(h, p["w"]) + p["b"]
        if i < len(DIMS) - 2:
            h = jax.nn.relu(h)
    return jax.nn.log_softmax(h, axis=-1)


def sample_flops() -> dict:
    """Matmul FLOPs per sample: ``forward``, and ``train`` = forward plus
    the backward pass's weight gradients of every layer and input
    gradients of every layer but the first (whose input is data)."""
    mm = [2 * a * b for a, b in zip(DIMS[:-1], DIMS[1:])]
    return {"forward": sum(mm), "train": 2 * sum(mm) + sum(mm[1:])}
