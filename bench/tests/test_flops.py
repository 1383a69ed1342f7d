"""Each configuration's FLOP count, and the token fixture's, from its widths
against XLA's count of one unrolled round at a small K, and the sync's
byte count against the program's own model of it."""
import math

import jax
import jax.numpy as jnp
import pytest

from benchlib import harness

CONFIGS = ["mnist_mlp_k50", "cifar_cnn_k27"]
FIXTURES = {"tiny_lm_k6": harness.BENCH / "tests" / "fixtures"
            / "tiny_lm_k6.py"}


def load(name: str):
    return harness.load_module(FIXTURES.get(
        name, harness.BENCH / "configs" / f"{name}.py"))


def sample_spec(model):
    """(sample shape, sample dtype, target shape): an image and its label,
    or a token sequence and its next tokens."""
    m = model.CONF["model"]
    if "input_hw" in m:
        return tuple(m["input_hw"]), jnp.float32, ()
    return (m["seq_len"],), jnp.int32, (m["seq_len"],)


def unrolled_round(model, K: int, steps: int, batch: int, n_eval: int,
                   C: int):
    """One round as the engine runs it, with every loop unrolled so that
    XLA's cost analysis counts each step: ``steps`` vmapped SGD steps on K
    clients, the sync's three matmuls, and the eval's forward pass."""
    init, apply, loss = model.program_model()
    params = init(jax.random.PRNGKey(0))
    leaves, treedef = jax.tree.flatten(params)
    d = sum(x.size for x in leaves)
    sample, dtype, target = sample_spec(model)

    def round_(stacked, xs, ys, A, B, M, xe, ye):
        for s in range(steps):
            g = jax.vmap(jax.grad(loss))(stacked, xs[:, s], ys[:, s])
            stacked = jax.tree.map(lambda p, g: p - 1e-3 * g, stacked, g)
        S = jnp.concatenate([x.reshape(K, -1)
                             for x in jax.tree.leaves(stacked)], axis=1)
        bar = B @ (A @ S)
        new = M @ bar
        cons = jax.tree.map(lambda x: x[0], stacked)
        return new, jnp.mean(jnp.argmax(apply(cons, xe), -1) == ye)

    f32 = jnp.float32
    args = (jax.tree.map(lambda x: jax.ShapeDtypeStruct((K,) + x.shape, f32),
                         params),
            jax.ShapeDtypeStruct((K, steps, batch, *sample), dtype),
            jax.ShapeDtypeStruct((K, steps, batch, *target), jnp.int32),
            jax.ShapeDtypeStruct((C, K), f32),
            jax.ShapeDtypeStruct((C, C), f32),
            jax.ShapeDtypeStruct((K, C), f32),
            jax.ShapeDtypeStruct((n_eval, *sample), dtype),
            jax.ShapeDtypeStruct((n_eval, *target), jnp.int32))
    cost = jax.jit(round_).lower(*args).compile().cost_analysis()
    return cost["flops"], d


@pytest.mark.parametrize("name", CONFIGS + list(FIXTURES))
def test_round_flops_match_xla(name):
    model = load(name)
    K, steps, n_eval, C = 2, 2, 16, 3
    batch = model.CONF["fl"]["batch_size"]
    xla, d = unrolled_round(model, K, steps, batch, n_eval, C)
    f = model.sample_flops()
    ours = (K * steps * batch * f["train"] + n_eval * f["forward"]
            + 2 * C * K * d + 2 * C * C * d + 2 * K * C * d)
    # XLA also counts the elementwise work (bias, ReLU, softmax, pooling,
    # the SGD update), which the model FLOPs leave out: a few percent.
    assert ours <= xla <= 1.05 * ours, (ours, xla, xla / ours)


@pytest.mark.parametrize("name,d", [("mnist_mlp_k50", 184_214),
                                    ("cifar_cnn_k27", 698_250)])
def test_flat_dim_is_published(name, d):
    model = harness.load_module(harness.BENCH / "configs" / f"{name}.py")
    shapes = jax.eval_shape(model.reference_init, jax.random.PRNGKey(0))
    assert sum(math.prod(x.shape) for x in jax.tree.leaves(shapes)) == d


@pytest.mark.parametrize("K,C,d", [(50, 3, 184_214), (27, 3, 698_250),
                                   (8, 2, 2049)])
def test_sync_bytes_match_the_program_model(K, C, d):
    from repro.kernels.cwfl_round import hbm_bytes_model
    roofline = harness.load_module(harness.BENCH / "metrics"
                                   / "sync_kernel_roofline.py")
    nbytes, flops = roofline.work(K, C, d)
    assert nbytes == hbm_bytes_model(K, C, d)["fused_bytes"]
    assert flops == 2 * d * C * (2 * K + C)
