"""The control: the reference computed one precision below what the
configuration states (``reference.control``) and put in the program's
place must fail the comparison, for every configuration.

On the TPU the test runs at the configuration's own size; elsewhere at
its reduced ``rehearsal`` size.  A control that lowers the matmul
precision of float32 (``high`` for ``highest``) can only be read on the
TPU: the CPU computes every float32 matmul alike, whatever its
precision."""
import jax
import pytest

from benchlib import checks, gen, harness, reference

CONFIGS = ["mnist_mlp_k50", "cifar_cnn_k27"]
ON_TPU = jax.devices()[0].platform == "tpu"


@pytest.mark.parametrize("name", CONFIGS)
def test_control_fails(name):
    model = harness.load_module(harness.BENCH / "configs" / f"{name}.py")
    conf = model.CONF if ON_TPU else harness.rehearsal_conf(model.CONF)
    control = reference.control(conf)
    if "precision" in control and not ON_TPU:
        pytest.skip(f"{name}: the {control['precision']!r} control needs "
                    f"the TPU")
    inputs = gen.make_inputs(conf)
    T = conf["fl"]["rounds"]
    plan_key = reference.program_keys(conf["fl"]["plan_seed"], T)[0]
    _, init_key, rkeys = reference.program_keys(12345, T)
    ref = reference.trajectory(model, conf, inputs, plan_key, init_key,
                               rkeys)
    ctl = reference.trajectory(model, conf, inputs, plan_key, init_key,
                               rkeys, **control)
    numbers, _ = checks.compare(ctl, ref)
    ok, record = checks.verdict(numbers, conf["check"]["limits"])
    assert not ok, record
    same, _ = checks.compare(reference.trajectory(
        model, conf, inputs, plan_key, init_key, rkeys), ref)
    assert checks.verdict(same, conf["check"]["limits"])[0]
