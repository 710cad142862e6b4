"""The persistent compilation cache: JAX_COMPILATION_CACHE_DIR when set,
else one fixed directory inside the checkout. Each case runs in its own
process, since the cache directory is process-wide JAX state."""

import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]

PROBE = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
used = enable_compile_cache()
print(used)
print(jax.config.jax_compilation_cache_dir)
if {compile}:
    jax.jit(lambda x: x * 3 + 1)(jnp.ones(4)).block_until_ready()
"""


def _run(env_extra: dict, compile_: bool) -> list[str]:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0", **env_extra)
    out = subprocess.run([sys.executable, "-c",
                          PROBE.format(compile=compile_)],
                         env=env, capture_output=True, text=True, check=True)
    return out.stdout.split()


def test_cache_dir_from_environment(tmp_path):
    used, configured = _run({"JAX_COMPILATION_CACHE_DIR": str(tmp_path)},
                            compile_=True)
    assert used == configured == str(tmp_path)
    assert any(p.name.endswith("-cache") for p in tmp_path.iterdir())


def test_cache_dir_defaults_to_fixed_checkout_path():
    used, configured = _run({}, compile_=False)
    assert used == configured == str(REPO / ".jax_cache")
