"""The main path's Pallas kernels compile for a TPU v5e chip.

Nothing here runs: each case compiles a kernel at real width for a
*described* v5e (no chip attached) with ``interpret=False`` and asserts the
compiled HLO holds the Mosaic kernel (``tpu_custom_call``).  The TPU
compiler refuses what interpret mode accepts — block shapes off the (8, 128)
tiling, too much VMEM — so these compiles guard every kernel change at no
chip time.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and the test workers all import
this file.
"""
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cwfl_round import cwfl_round
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ota_aggregate import ota_aggregate

# The paper's MNIST deployment: K=50 clients, C=3 clusters, and the flat
# dimension of the (200, 100, 64) MLP (configs/mnist_mlp.py).
K, C, D_MLP = 50, 3, 184_214


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip; keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


def _compiled_text(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _round_shapes(d):
    f32 = jnp.float32
    return [((K, d), f32), ((C, K), f32), ((C, d), f32), ((C, C), f32),
            ((C, d), f32), ((K, C), f32)]


@pytest.mark.parametrize("d,guard", [(D_MLP, False), (D_MLP, True),
                                     (2049, False)])
def test_cwfl_round_compiles_for_v5e(one_chip, d, guard):
    fn = partial(cwfl_round, interpret=False, guard=guard)
    assert "tpu_custom_call" in _compiled_text(fn, one_chip,
                                               *_round_shapes(d))


def test_ota_aggregate_compiles_for_v5e(one_chip):
    fn = partial(ota_aggregate, interpret=False)
    f32 = jnp.float32
    text = _compiled_text(fn, one_chip, ((K, D_MLP), f32), ((C, K), f32),
                          ((C, D_MLP), f32))
    assert "tpu_custom_call" in text


def test_flash_attention_compiles_for_v5e(one_chip):
    fn = partial(flash_attention, interpret=False)
    qkv = ((1, 8, 1024, 128), jnp.bfloat16)
    assert "tpu_custom_call" in _compiled_text(fn, one_chip, qkv, qkv, qkv)


def _round_text(one_chip, monkeypatch, k, n_k, hidden, precision=None):
    """The HLO text of an MNIST-shaped round scan (``k`` clients of ``n_k``
    samples, C=2, batch 64, an MLP of ``hidden`` widths) built through the
    engine's own `_build` and compiled for the described v5e, at the
    given default matmul precision."""
    import repro.kernels.cwfl_round as kernel
    from repro.core import TopologyConfig, make_topology
    from repro.data import (SyntheticImageConfig, make_synthetic_images,
                            partition_iid)
    from repro.models import make_mnist_mlp, nll_loss
    from repro.sim import get_scenario
    from repro.sim.engine import _SCAN_UNROLL, _build
    from repro.training import FLConfig

    (xtr, ytr), (xte, yte) = make_synthetic_images(
        jax.random.PRNGKey(0),
        SyntheticImageConfig.mnist_like(num_train=k * n_k, num_test=128))
    tcfg = TopologyConfig(num_clients=k, num_hotspots=2)
    topo = make_topology(jax.random.PRNGKey(7), tcfg)
    xs, ys = partition_iid(jax.random.PRNGKey(1), xtr, ytr, k)
    init, apply = make_mnist_mlp(hidden=hidden)
    loss = lambda p, x, y: nll_loss(apply(p, x), y)
    cfg = FLConfig(strategy="cwfl", rounds=2, batch_size=64, num_clusters=2,
                   snr_db=40.0, eval_samples=128, seed=0)
    with jax.default_matmul_precision(precision):
        prepare, make_body = _build(init, apply, loss, topo, xs, ys, xte,
                                    yte, cfg, get_scenario("paper-static"),
                                    tcfg)
        ctx, carry, scan_xs = prepare(cfg.seed, cfg.snr_db)
    body = make_body(ctx)
    # The CPU backend would pick interpret mode; compile the kernel itself.
    monkeypatch.setattr(kernel, "resolve_interpret",
                        lambda i: False if i is None else i)
    jax.clear_caches()
    try:
        fn = jax.jit(lambda c, x: jax.lax.scan(body, c, x,
                                               unroll=_SCAN_UNROLL))
        spec = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                              sharding=one_chip)
        with jax.default_matmul_precision(precision):
            return fn.lower(jax.tree.map(spec, carry),
                            jax.tree.map(spec, scan_xs)).compile().as_text()
    finally:
        jax.clear_caches()


def test_scoped_round_keeps_kernel_name_for_v5e(one_chip, monkeypatch):
    """The whole scoped MNIST-shaped round (K=4, C=2, one hidden layer)
    compiled for a v5e: the fused sync's custom call keeps the name the
    trace metrics match (``cwfl_round.<n>``) and lies under ``fl_sync``."""
    import re

    from repro.obs.profiling import hlo_op_scopes

    text = _round_text(one_chip, monkeypatch, 4, 128, (32,))
    assert "tpu_custom_call" in text
    scopes = hlo_op_scopes(text)
    kernels = [n for n in re.findall(r"%([\w.-]+) = ", text)
               if re.match(r"cwfl_round(\.\d+)?$", n)]
    assert kernels and all(scopes.get(n) == "fl_sync" for n in kernels)


def test_round_draws_rows_without_shard_copy_for_v5e(one_chip, monkeypatch):
    """The MNIST-shaped round at the paper's K=50 (256 samples a client,
    float32 at ``highest``) compiled for a v5e: no copy of the embedded
    shard set, and the ``fl_batch`` gather fusion gives row-major 2-D
    ``[K·B, 784]`` rows.  With sample-shaped (28, 28, 1) shards XLA puts
    the sample index in the lanes and gathers ``[K·B, 28, 28]``."""
    import re

    from repro.obs.profiling import hlo_op_scopes

    k, n_k, b = 50, 256, 64
    text = _round_text(one_chip, monkeypatch, k, n_k, (32,), "highest")
    scopes = hlo_op_scopes(text)
    # Fused computations that hold a gather, by name.
    gathering, comp = set(), None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.-]+) .*\{$", line)
        if head:
            comp = head.group(1)
        elif " gather(" in line and comp is not None:
            gathering.add(comp)
    ops = re.findall(r"^\s*(?:ROOT )?%([\w.-]+) = (\w+)\[([\d,]*)\]"
                     r"(\{[^}]*\})? ([\w-]+)\((.*)$", text, re.M)
    copies = [name for name, _, shape, _, op, _ in ops
              if op.startswith("copy") and shape.startswith(f"{k},{n_k},")]
    assert not copies, copies
    # The sample gathers (float32; the labels' gather is s32).
    draws = [(shape, layout.split(":")[0])
             for name, dtype, shape, layout, op, rest in ops
             if op == "fusion" and dtype == "f32"
             and scopes.get(name) == "fl_batch"
             and re.search(r"calls=%([\w.-]+)", rest).group(1) in gathering]
    assert draws and set(draws) == {(f"{k * b},784", "{1,0")}, draws
