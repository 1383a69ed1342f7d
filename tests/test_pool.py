"""The CNN's fused ReLU and 2x2 max-pool against ``reduce_window``.

``relu_maxpool2`` pools before the ReLU and routes the cotangent by
compare-and-select; both must give the bits of ``reduce_window(relu(x))``
and of its VJP, ties and all-negative windows included, so that the CNN
trains the same trajectory without a select-and-scatter.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import make_cifar_cnn, nll_loss
from repro.models.small import relu_maxpool2


def _reference_pool(x):
    return jax.lax.reduce_window(jax.nn.relu(x), -jnp.inf, jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def _bits(tree):
    return [np.asarray(a).tobytes() for a in jax.tree.leaves(tree)]


def _normal(shape):
    return jax.random.normal(jax.random.PRNGKey(0), shape)


def _ties(shape):
    return jax.random.randint(jax.random.PRNGKey(1), shape, -2,
                              3).astype(jnp.float32)


@pytest.mark.parametrize("make,shape,clients", [
    (_normal, (4, 8, 8, 6), None),
    (_ties, (4, 8, 8, 6), None),
    (_ties, (3, 7, 9, 5), None),
    (_normal, (3, 7, 9, 5), None),
    (_ties, (4, 8, 8, 6), 3),
], ids=["normal", "ties", "odd-ties", "odd-normal", "vmap-clients"])
def test_relu_maxpool2_matches_reduce_window_bitwise(make, shape, clients):
    x = make(shape if clients is None else (clients, *shape))
    fused, ref = relu_maxpool2, _reference_pool
    if clients is not None:
        fused, ref = jax.vmap(fused), jax.vmap(ref)
    y, vjp = jax.vjp(fused, x)
    y_ref, vjp_ref = jax.vjp(ref, x)
    assert y.shape == y_ref.shape
    g = jax.random.normal(jax.random.PRNGKey(2), y.shape)
    assert _bits(y) == _bits(y_ref)
    assert _bits(vjp(g)) == _bits(vjp_ref(g))


def _reduce_window_cnn_apply(params, x):
    """``make_cifar_cnn``'s apply with the pool written as
    ``reduce_window(relu(conv))``."""
    h = x
    for name in ("conv0", "conv1", "conv2"):
        p = params[name]
        h = jax.lax.conv_general_dilated(
            h, p["w"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + p["b"]
        h = _reference_pool(h)
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(h @ params["fc0"]["w"] + params["fc0"]["b"])
    h = h @ params["fc1"]["w"] + params["fc1"]["b"]
    return jax.nn.log_softmax(h, axis=-1)


def test_cnn_gradient_has_no_select_and_scatter():
    """The vmapped CNN gradient lowers without a select-and-scatter and
    computes, bit for bit, the gradient through ``reduce_window``."""
    clients, batch = 3, 4
    init, apply = make_cifar_cnn()
    params = jax.vmap(init)(jax.random.split(jax.random.PRNGKey(3), clients))
    x = _normal((clients, batch, 32, 32, 3))
    y = jax.random.randint(jax.random.PRNGKey(4), (clients, batch), 0, 10)

    def grads(apply_fn):
        loss = lambda p, xb, yb: nll_loss(apply_fn(p, xb), yb)
        return jax.jit(jax.vmap(jax.grad(loss)))

    fused = grads(apply)
    lowered = fused.lower(params, x, y)
    # the ops, not names in the location metadata
    assert "stablehlo.select_and_scatter" not in lowered.as_text()
    assert " select-and-scatter(" not in lowered.compile().as_text()
    assert "stablehlo.select_and_scatter" in grads(
        _reduce_window_cnn_apply).lower(params, x, y).as_text()
    # Op by op: one jitted program may sum a bias gradient in another order.
    with jax.disable_jit():
        assert _bits(fused(params, x, y)) == _bits(
            grads(_reduce_window_cnn_apply)(params, x, y))
