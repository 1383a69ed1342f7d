"""Golden-trajectory regression: replay the engine against committed bits.

Three PRs of refactors (fused kernel, scan engine, sharded execution)
each proved bit-identity against the code they replaced — but only by
re-running the pre-refactor code in the same process.  This fixture
commits the ``paper-static`` T=4/K=8 histories for all four strategies
as raw float32 bit patterns (``tests/goldens/paper_static_T4_K8.json``),
so every future refactor gets a parity check against TODAY's bits
without a pre-refactor checkout.

Regenerate intentionally with ``PYTHONPATH=src python
tests/goldens/generate.py`` — a diff of the ``*_repr`` fields documents
the drift.  The exact bits are pinned to the config that generated them
(backend, device count and jax version, recorded in the fixture's
``protocol``): XLA CPU tiles reductions by the device/thread config,
which legally re-associates a mean by 1 ulp.  Under any other config the test enforces a 2-ulp bound
instead — still tight enough that any real regression (wrong key
schedule, changed math) fails loudly.
"""
import json
import os

import jax
import numpy as np
import pytest

from goldens.generate import GOLDEN_DIR, STRATEGIES, run_strategy

GOLDEN_PATH = os.path.join(GOLDEN_DIR, "paper_static_T4_K8.json")


def _from_bits(hexes):
    return np.asarray([int(h, 16) for h in hexes],
                      np.uint32).view(np.float32)


def _ulp_dist(a: np.ndarray, b: np.ndarray) -> int:
    ia = a.astype(np.float32).view(np.int32).astype(np.int64)
    ib = b.astype(np.float32).view(np.int32).astype(np.int64)
    return int(np.max(np.abs(ia - ib)))


@pytest.fixture(scope="module")
def golden():
    with open(GOLDEN_PATH) as f:
        return json.load(f)


def _pinned_config(golden) -> bool:
    p = golden["protocol"]
    return (jax.default_backend() == p["backend"]
            and len(jax.devices()) == p["devices"]
            # an XLA upgrade may legitimately re-fuse by a ulp — route
            # version drift to the 2-ulp bound, not the bitwise pin
            and jax.__version__ == p["jax"])


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_golden_trajectory_replay(golden, strategy):
    loss, acc = run_strategy(strategy)
    g = golden["strategies"][strategy]
    g_loss = _from_bits(g["train_loss_bits"])
    g_acc = _from_bits(g["test_acc_bits"])
    max_ulp = 0 if _pinned_config(golden) else 2
    for name, got, want in (("train_loss", loss, g_loss),
                            ("test_acc", acc, g_acc)):
        ulp = _ulp_dist(got, want)
        assert ulp <= max_ulp, (
            f"{strategy} {name} drifted from the golden by {ulp} ulp "
            f"(bound {max_ulp}): {got} vs {want}")


def test_golden_fixture_is_self_consistent(golden):
    """The human-readable repr fields decode to the same floats as the
    bit patterns (guards against hand-editing one but not the other)."""
    for s, g in golden["strategies"].items():
        np.testing.assert_array_equal(
            _from_bits(g["train_loss_bits"]),
            np.asarray(g["train_loss_repr"], np.float32), err_msg=s)
        np.testing.assert_array_equal(
            _from_bits(g["test_acc_bits"]),
            np.asarray(g["test_acc_repr"], np.float32), err_msg=s)
