"""Where the persistent compilation cache goes (repro.utils.compile_cache).

Each case runs in a fresh interpreter: JAX reads
``JAX_COMPILATION_CACHE_DIR`` once, as it is imported, and the cache
initializes once per process.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]

SCRIPT = textwrap.dedent("""
    import json, os
    import jax
    import repro, repro.dist, repro.kernels, repro.obs, repro.sim
    import repro.training, repro.utils
    after_import = jax.config.jax_compilation_cache_dir
    from repro.utils.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: x * 2 + 1)(jax.numpy.ones(3)).block_until_ready()
    print("RESULT::" + json.dumps({
        "after_import": after_import, "returned": path,
        "config": jax.config.jax_compilation_cache_dir}))
""")


def _run(env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT::")]
    return json.loads(line[0][len("RESULT::"):])


@pytest.mark.parametrize("env_set", [False, True])
def test_compile_cache_location(tmp_path, env_set):
    """Unset: importing the library sets no cache, and the entry-point
    helper points it at the fixed, gitignored ``<checkout>/.jax_cache``.
    Set: the variable wins, the helper changes nothing, and compiled
    programs land there."""
    env_dir = tmp_path / "cache" if env_set else None
    out = _run(env_dir)
    if env_set:
        assert out["after_import"] == out["returned"] == out["config"] \
            == str(env_dir)
        assert any(env_dir.iterdir()), "nothing was written to the cache"
    else:
        fixed = str(REPO / ".jax_cache")
        assert out["after_import"] is None
        assert out["returned"] == out["config"] == fixed
        ignored = (REPO / ".gitignore").read_text().splitlines()
        assert ".jax_cache/" in ignored
