"""The entry points' compilation cache: ``JAX_COMPILATION_CACHE_DIR``
where set, else one fixed directory of the checkout."""
import os
import subprocess
import sys

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

# turn the cache on, compile one program, list what was written where
CHILD = """
import os, sys
import jax
from repro.launch import compile_cache
compile_cache.DEFAULT_CACHE_DIR = sys.argv[1]
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
print("DIR=" + compile_cache.enable_compilation_cache())
jax.block_until_ready(jax.jit(lambda x: x * 2 + 1)(jax.numpy.arange(8.0)))
"""


def _run(tmp_path, env_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=SRC)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    default = tmp_path / "checkout_cache"
    out = subprocess.run([sys.executable, "-c", CHILD, str(default)],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr
    return out.stdout, default


def test_cache_goes_only_to_the_environment_directory(tmp_path):
    env_dir = tmp_path / "from_env"
    stdout, default = _run(tmp_path, str(env_dir))
    assert f"DIR={env_dir}" in stdout
    assert os.listdir(env_dir)
    assert not default.exists()


def test_cache_defaults_to_the_fixed_checkout_directory(tmp_path):
    stdout, default = _run(tmp_path, None)
    assert f"DIR={default}" in stdout
    assert os.listdir(default)


def test_default_cache_directory_is_fixed_inside_the_checkout():
    from repro.launch import compile_cache
    checkout = os.path.dirname(SRC)
    assert compile_cache.REPO_ROOT == checkout
    assert compile_cache.DEFAULT_CACHE_DIR == os.path.join(checkout,
                                                           ".jax_cache")
