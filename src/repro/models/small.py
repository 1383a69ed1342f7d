"""The paper's experiment models (§V): a 4-layer MLP (MNIST) and a 6-layer
CNN (CIFAR), pure-JAX init/apply pairs (NLL loss via log-softmax outputs).

MNIST net: 4 fully-connected layers with ReLU, log-softmax head.
CIFAR net: conv 3→64, 64→120, 120→200 (each followed by 2×2 max-pool) then
two FC layers — "6 layers" counting conv+fc — log-softmax head.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp


def _dense_init(key, d_in, d_out):
    k1, _ = jax.random.split(key)
    scale = jnp.sqrt(2.0 / d_in)
    return {"w": scale * jax.random.normal(k1, (d_in, d_out), jnp.float32),
            "b": jnp.zeros((d_out,), jnp.float32)}


def _conv_init(key, kh, kw, c_in, c_out):
    scale = jnp.sqrt(2.0 / (kh * kw * c_in))
    return {"w": scale * jax.random.normal(key, (kh, kw, c_in, c_out),
                                           jnp.float32),
            "b": jnp.zeros((c_out,), jnp.float32)}


def _conv(x, p):
    y = jax.lax.conv_general_dilated(
        x, p["w"], window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["b"]


def relu_maxpool2(x):
    """``reduce_window(relu(x), max, 2×2, VALID)`` on NHWC, bit for bit,
    and its VJP; an odd last row or column is cropped, as VALID does."""
    _, h, w, _ = x.shape
    return _relu_maxpool2(x[:, :h // 2 * 2, :w // 2 * 2])


def _corners(x):
    """The four corners of each 2×2 window, in row-major window order,
    each (B, H/2, W/2, C).  H and W are major dims in the TPU layout, so
    the reshape is a bitcast and each corner a strided slice of tiles."""
    b, h, w, c = x.shape
    xw = x.reshape(b, h // 2, 2, w // 2, 2, c)
    return [xw[:, :, i, :, j] for i in range(2) for j in range(2)]


@jax.custom_vjp
def _relu_maxpool2(x):
    return _relu_maxpool2_fwd(x)[0]


def _relu_maxpool2_fwd(x):
    a, b, c, d = _corners(x)
    m = jnp.maximum(jnp.maximum(a, b), jnp.maximum(c, d))
    # ReLU is monotone and max exact: pooling first gives the same bits,
    # with the ReLU on a quarter of the elements.  The residual is the
    # window position that takes the cotangent: the first maximum in
    # row-major order, as reduce_window's `ge` select picks it, or 4
    # (none) where the ReLU is off.
    first = jnp.where(a == m, 0, jnp.where(b == m, 1, jnp.where(c == m, 2, 3)))
    return jax.nn.relu(m), jnp.where(m > 0, first, 4).astype(jnp.int8)


def _relu_maxpool2_bwd(first, g):
    b, h, w, c = g.shape
    window = jnp.arange(4, dtype=jnp.int8).reshape(1, 1, 2, 1, 2, 1)
    dx = jnp.where(window == first[:, :, None, :, None],
                   g[:, :, None, :, None], 0)
    return (dx.reshape(b, 2 * h, 2 * w, c),)


_relu_maxpool2.defvjp(_relu_maxpool2_fwd, _relu_maxpool2_bwd)


# ---------------------------------------------------------------------------
# MNIST MLP (4 layers).
# ---------------------------------------------------------------------------

def make_mnist_mlp(input_hw=(28, 28, 1), hidden: Sequence[int] = (200, 100, 64),
                   num_classes: int = 10):
    d_in = input_hw[0] * input_hw[1] * input_hw[2]
    dims = [d_in, *hidden, num_classes]

    def init(key):
        keys = jax.random.split(key, len(dims) - 1)
        return {f"fc{i}": _dense_init(k, dims[i], dims[i + 1])
                for i, k in enumerate(keys)}

    def apply(params, x):
        h = x.reshape(x.shape[0], -1)
        n = len(dims) - 1
        for i in range(n):
            p = params[f"fc{i}"]
            h = h @ p["w"] + p["b"]
            if i < n - 1:
                h = jax.nn.relu(h)
        return jax.nn.log_softmax(h, axis=-1)

    return init, apply


# ---------------------------------------------------------------------------
# CIFAR CNN (6 layers: 3 conv + pools, 2 hidden fc + head).
# ---------------------------------------------------------------------------

def make_cifar_cnn(input_hw=(32, 32, 3), num_classes: int = 10):
    h, w, c = input_hw
    # three 2x2 pools: spatial /8
    flat = (h // 8) * (w // 8) * 200

    def init(key):
        k = jax.random.split(key, 6)
        return {
            "conv0": _conv_init(k[0], 3, 3, c, 64),
            "conv1": _conv_init(k[1], 3, 3, 64, 120),
            "conv2": _conv_init(k[2], 3, 3, 120, 200),
            "fc0": _dense_init(k[3], flat, 128),
            "fc1": _dense_init(k[4], 128, num_classes),
        }

    def apply(params, x):
        h = x
        for name in ("conv0", "conv1", "conv2"):
            h = relu_maxpool2(_conv(h, params[name]))
        h = h.reshape(h.shape[0], -1)
        h = jax.nn.relu(h @ params["fc0"]["w"] + params["fc0"]["b"])
        h = h @ params["fc1"]["w"] + params["fc1"]["b"]
        return jax.nn.log_softmax(h, axis=-1)

    return init, apply


def nll_loss(log_probs: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Paper's NLL loss on log-softmax outputs."""
    return -jnp.mean(jnp.take_along_axis(log_probs, labels[:, None],
                                         axis=1)[:, 0])


def accuracy(log_probs: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    return jnp.mean(jnp.argmax(log_probs, axis=-1) == labels)
