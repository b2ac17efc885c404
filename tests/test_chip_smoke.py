"""``chip_smoke.py`` fails, and prints no result, wherever it finds no
TPU or no repository around it."""
import os
import shutil
import subprocess
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(args, cwd, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_refuses_cpu_before_building_model(tmp_path):
    # the model builder raises if reached: the platform check must come
    # first and end the run with its own exit code
    code = ("import runpy, sys\n"
            f"sys.path.insert(0, {os.path.join(ROOT, 'src')!r})\n"
            "from repro.launch import serve\n"
            "def build_engine(*a, **k):\n"
            "    raise AssertionError('model built without a TPU')\n"
            "serve.build_engine = build_engine\n"
            f"sys.argv = [{SMOKE!r}]\n"
            f"runpy.run_path({SMOKE!r}, run_name='__main__')\n")
    out = _run(["-c", code], ROOT, tmp_path)
    assert out.returncode == 2, out.stdout + out.stderr
    assert "JAX found no TPU (platform 'cpu')" in out.stderr
    assert "model built" not in out.stderr
    assert '"ok"' not in out.stdout


def test_chip_smoke_four_chips_refuses_cpu(tmp_path):
    out = _run([SMOKE, "--four-chips"], ROOT, tmp_path)
    assert out.returncode == 2, out.stdout + out.stderr
    assert '"ok"' not in out.stdout


def test_chip_smoke_alone_fails_without_the_repo(tmp_path):
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(SMOKE, alone / "chip_smoke.py")
    out = _run([str(alone / "chip_smoke.py")], str(alone), tmp_path)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
