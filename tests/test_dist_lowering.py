"""Small-mesh integration: the distributed train/prefill/decode steps must
lower and compile on an 8-device fake mesh (subprocess — device count must be
set before jax initializes, and the main test process keeps 1 device)."""
import json
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch.mesh import make_local_mesh
    from repro.models.config import InputShape
    from repro.training import dist_steps as ds
    from repro.dist.fl_integration import make_fl_plan

    mesh = make_local_mesh(4, 2)

    def flops(c):
        return c.cost_analysis().get("flops", 0.0)

    out = {}
    for arch in %(archs)s:
        cfg = get_config(arch, reduced=True).replace(moe_shards=4)
        shape = InputShape("t", 64, 8, "train")
        plan = make_fl_plan(4, 2, jax.random.PRNGKey(0))
        fn, args, sh = ds.make_train_step(cfg, shape, mesh, plan=plan)
        with mesh:
            c = jax.jit(fn, in_shardings=ds.sr.named(sh, mesh)).lower(*args).compile()
        out[arch + ":train"] = flops(c)

        shape_d = InputShape("d", 128, 8, "decode")
        fn, args, sh = ds.make_decode_step(cfg, shape_d, mesh)
        with mesh:
            c = jax.jit(fn, in_shardings=ds.sr.named(sh, mesh)).lower(*args).compile()
        out[arch + ":decode"] = flops(c)

        shape_p = InputShape("p", 64, 8, "prefill")
        fn, args, sh, osp = ds.make_prefill_step(cfg, shape_p, mesh)
        with mesh:
            c = jax.jit(fn, in_shardings=ds.sr.named(sh, mesh),
                        out_shardings=ds.sr.named(osp, mesh)).lower(*args).compile()
        out[arch + ":prefill"] = flops(c)
    print("RESULT::" + json.dumps(out))
""")


@pytest.mark.slow
def test_dist_steps_lower_on_8_devices():
    archs = ["qwen2.5-3b", "jamba-v0.1-52b", "xlstm-125m"]
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT % {"archs": repr(archs)}],
        capture_output=True, text=True, timeout=1200,
        env={**__import__("os").environ, "PYTHONPATH": "src"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT::")]
    assert line, proc.stdout
    out = json.loads(line[0][len("RESULT::"):])
    assert len(out) == 9
    assert all(v > 0 for v in out.values())
