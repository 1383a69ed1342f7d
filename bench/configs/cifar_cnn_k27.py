"""The paper's CIFAR model (arXiv 2211.03363 §V): three 3×3 SAME convs
3→64→120→200, each with ReLU and a 2×2 max-pool, then 3200→128→10 dense
layers and a log-softmax head, as ``cifar_cnn_k27.json`` runs it.

``program_model`` hands the benchmark the program's own model
(``repro.models.make_cifar_cnn``).  ``reference_init``/``reference_apply``
are the plain float32 model the reference trains, written from the paper
(its matmul precision is the reference's to set):
He-normal weights, zero biases, drawn with the program's key schedule so
that both start from the same parameters.  ``sample_flops`` counts the
conv and matmul operations one sample needs, from the widths alone.
"""
from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp

CONF = json.loads(Path(__file__).with_suffix(".json").read_text())
MODEL = CONF["model"]
H, W, CIN = MODEL["input_hw"]
CONVS = [CIN, *MODEL["conv_channels"]]            # 3 → 64 → 120 → 200
FLAT = (H // 8) * (W // 8) * CONVS[-1]             # three 2×2 pools
DENSE = [FLAT, *MODEL["hidden"], MODEL["num_classes"]]


def program_model():
    """(init, apply, loss) of the system under test."""
    from repro.models import make_cifar_cnn, nll_loss
    init, apply = make_cifar_cnn(input_hw=tuple(MODEL["input_hw"]),
                                 num_classes=MODEL["num_classes"])
    return init, apply, lambda p, x, y: nll_loss(apply(p, x), y)


def reference_init(key):
    k = jax.random.split(key, 6)
    params = {}
    for i, (ci, co) in enumerate(zip(CONVS[:-1], CONVS[1:])):
        params[f"conv{i}"] = {
            "w": jnp.sqrt(2.0 / (9 * ci)) * jax.random.normal(
                k[i], (3, 3, ci, co), jnp.float32),
            "b": jnp.zeros((co,), jnp.float32)}
    for i, (di, do) in enumerate(zip(DENSE[:-1], DENSE[1:])):
        w_key, _ = jax.random.split(k[3 + i])
        params[f"fc{i}"] = {
            "w": jnp.sqrt(2.0 / di) * jax.random.normal(w_key, (di, do),
                                                        jnp.float32),
            "b": jnp.zeros((do,), jnp.float32)}
    return params


def reference_apply(params, x):
    h = x
    for i in range(len(CONVS) - 1):
        p = params[f"conv{i}"]
        h = jax.lax.conv_general_dilated(
            h, p["w"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + p["b"]
        h = jax.lax.reduce_window(jax.nn.relu(h), -jnp.inf, jax.lax.max,
                                  (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    h = h.reshape(h.shape[0], -1)
    for i in range(len(DENSE) - 1):
        p = params[f"fc{i}"]
        h = jnp.dot(h, p["w"]) + p["b"]
        if i < len(DENSE) - 2:
            h = jax.nn.relu(h)
    return jax.nn.log_softmax(h, axis=-1)


def sample_flops() -> dict:
    """Conv/matmul FLOPs per sample: ``forward``, and ``train`` = forward
    plus the backward pass's weight gradients of every layer and input
    gradients of every layer but the first (whose input is data).  A
    conv counts the products with real inputs, not with its zero
    padding."""
    mm, h, w = [], H, W
    for ci, co in zip(CONVS[:-1], CONVS[1:]):
        # (output position, kernel tap) pairs that fall inside the
        # unpadded input: 3n - 2 along a side of n for a 3x3 SAME conv.
        mm.append(2 * (3 * h - 2) * (3 * w - 2) * ci * co)
        h, w = h // 2, w // 2
    mm += [2 * a * b for a, b in zip(DENSE[:-1], DENSE[1:])]
    return {"forward": sum(mm), "train": 2 * sum(mm) + sum(mm[1:])}
