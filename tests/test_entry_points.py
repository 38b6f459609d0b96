"""Entry-point plumbing, checked in child processes on the CPU
(``JAX_PLATFORMS=cpu`` loads no TPU library).

* ``chip_smoke.py`` is the repo's proof that the OSCAR round runs on the
  chip; a run that found no TPU must not pass for one.  It has to exit
  non-zero without printing its ``"ok": true`` line, both from the
  checkout and copied alone into an empty directory.
* ``repro.utils.enable_compile_cache`` puts JAX's persistent compilation
  cache where ``JAX_COMPILATION_CACHE_DIR`` says, or else under the
  checkout's ``.jax_cache``, and nowhere else."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


@pytest.mark.parametrize("alone", [False, True])
def test_chip_smoke_fails_without_tpu(tmp_path, alone):
    script = SCRIPT
    if alone:
        script = tmp_path / SCRIPT.name
        shutil.copy(SCRIPT, script)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, str(script)], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "no TPU" in proc.stderr


CACHE_PROBE = """
import sys
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp
from repro.utils import enable_compile_cache
print(enable_compile_cache(sys.argv[2]))
jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((8, 8))).block_until_ready()
"""


@pytest.mark.parametrize("from_env", [False, True])
def test_compile_cache_placement(tmp_path, from_env):
    checkout, env_dir = tmp_path / "checkout", tmp_path / "env_cache"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    proc = subprocess.run(
        [sys.executable, "-c", CACHE_PROBE, str(ROOT / "src"), str(checkout)],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    want = env_dir if from_env else checkout / ".jax_cache"
    assert proc.stdout.split() == [str(want)]
    assert any(want.iterdir())
    assert sorted(p.name for p in tmp_path.iterdir()) == sorted(
        ["env_cache"] if from_env else ["checkout"])
