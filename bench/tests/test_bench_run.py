"""Whole runs of ``bench/run.py``: refused without a chip, and, past the
look for a chip, driven on the CPU at a small size with the timed path
sound and then broken underneath, where ``correct`` must come out
false."""
import ast
import inspect
import json
import os
import re
import shutil
import subprocess
import sys
import time

import jax
import pytest

from bench import harness, ring, serving
from bench.run import execute
from bench.tests import tiny

RUN = os.path.join(harness.BENCH_DIR, "run.py")


def cpu_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(harness.ROOT, "src"), **extra)
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    return env


def last_json(stdout: str):
    lines = [l for l in stdout.splitlines() if l.startswith("{")]
    return json.loads(lines[-1]) if lines else None


def test_refuses_without_a_tpu():
    p = subprocess.run([sys.executable, RUN, "--workload", "qwen2-0.5b.chat",
                        "--seed", "1", "--seconds", "1"], cwd=harness.ROOT,
                       env=cpu_env(), capture_output=True, text=True,
                       timeout=120)
    assert p.returncode == 2
    assert last_json(p.stdout) is None
    assert "no TPU" in p.stderr


def test_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "qwen2-0.5b.chat", "--seed", "1", "--seconds", "1"],
                       cwd=tmp_path, env=dict(cpu_env(), PYTHONPATH=""),
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert last_json(p.stdout) is None


def serve_once(workload, seed):
    cell = tiny.serving_cell(*workload)
    line = execute(cell, jax.devices()[:1], seed, 1.5, False,
                   {"logit_gap": 0.05}, time.perf_counter())
    return json.loads(line)


@pytest.fixture
def no_cache(monkeypatch, tmp_path):
    # JAX read its cache settings when it started: naming a directory
    # now keeps the helper from switching the cache on for these runs
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


SERVED = [("qwen2-0.5b", "chat"), ("qwen2-0.5b", "batch"),
          ("starcoder2-7b", "code")]


@pytest.mark.parametrize("workload", SERVED)
def test_sound_run_is_correct(no_cache, workload):
    res = serve_once(workload, 2**33 + 1)
    assert res["correct"] is True and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert res["checks"]["logit_gap"]["value"] <= 0.05
    assert "setup_s" in res["metrics"] and "itl_p95_ms" in res["metrics"]
    assert res["device"]["platform"] == "cpu"


@pytest.mark.parametrize("workload", SERVED)
def test_altered_token_is_caught(no_cache, monkeypatch, workload):
    """A token altered where the engine produces it."""
    import repro.serving.engine as engine
    sample = engine.sample_token

    def altered(logits, temperature, key):
        return (sample(logits, temperature, key) + 1) % logits.shape[-1]

    monkeypatch.setattr(engine, "sample_token", altered)
    res = serve_once(workload, 3)
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > \
        res["checks"]["logit_gap"]["limit"]


@pytest.mark.parametrize("fault", ["no arch", "unknown arch",
                                   "unknown kind"])
def test_config_without_a_driver_is_refused_by_its_file(no_cache, fault):
    """A served configuration that names no architecture, or one that
    ``bench/archs/`` does not hold, or a kind no driver serves."""
    cell = tiny.serving_cell("qwen2-0.5b", "chat")
    if fault == "no arch":
        del cell.config["arch"]
    elif fault == "unknown arch":
        cell.config["arch"] = "gqa2"
    else:
        cell.config["kind"] = "causal-lm"
    with pytest.raises(ValueError) as e:
        execute(cell, jax.devices()[:1], 1, 1.5, False, {"logit_gap": 0.05},
                time.perf_counter())
    assert cell.config_file == "bench/configs/qwen2-0.5b.json"
    assert cell.config_file in str(e.value)
    if fault != "unknown kind":
        assert "bench/archs/" in str(e.value)


PROBE = '''"""GQA under another name, with a cut of its own for CPU tests."""
from bench.archs import gqa
from bench.archs.gqa import *  # noqa: F401,F403


def small(config):
    return dict(gqa.small(config), num_hidden_layers=1)
'''

PLUGIN = """
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
from bench import serving
from bench.tests import tiny
from bench.tests.test_bench_run import serve_once
cell = tiny.serving_cell("probe-0.5b", "chat")
arch, _ = serving.load_arch(cell.config, cell.config_file)
print(json.dumps({{"arch_file": arch.__file__,
                  "layers": cell.config["num_hidden_layers"]}}))
print(json.dumps(serve_once(("probe-0.5b", "chat"), 2**33 + 21)))
"""


def test_a_new_architecture_needs_only_new_files(tmp_path):
    """A copy of the benchmark, with one architecture module and one
    configuration that names it added and nothing else changed, serves
    a small cell correctly."""
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "bench" / "archs" / "probe.py").write_text(PROBE)
    conf = harness.load_json(os.path.join(harness.BENCH_DIR, "configs",
                                          "qwen2-0.5b.json"))
    conf.update(arch="probe", serve=dict(conf["serve"], name="probe-0.5b"))
    (tmp_path / "bench" / "configs" / "probe-0.5b.json").write_text(
        json.dumps(conf))
    script = tmp_path / "plugin.py"
    script.write_text(PLUGIN.format(root=str(tmp_path),
                                    src=os.path.join(harness.ROOT, "src")))
    p = subprocess.run(
        [sys.executable, str(script)], cwd=tmp_path,
        env=cpu_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache")),
        capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = [json.loads(l) for l in p.stdout.splitlines()
             if l.startswith("{")]
    assert lines[0] == {"arch_file": str(tmp_path / "bench" / "archs" /
                                         "probe.py"), "layers": 1}
    res = lines[-1]
    assert res["correct"] is True and res["failed"] == 0
    assert res["checks"]["logit_gap"]["value"] <= 0.05


RING = """
import json, sys, time
sys.path[:0] = [{root!r}, {src!r}]
import jax
from bench import harness
from bench.run import execute
import repro.core as lcx
if sys.argv[1] == "broken":
    # the exchange between chips left out: every put goes to itself
    shift = lcx.Perm.shift
    lcx.Perm.shift = staticmethod(lambda k: shift(0))
elif sys.argv[1] == "altered":
    # a payload altered where the exchange produces it
    from repro.core import ops
    permute = ops._permute
    ops._permute = lambda value, dev, perm: permute(value, dev, perm) ^ 1
cell = harness.Cell(
    "lcx-ring4.pingpong", 4, "bench/configs/lcx-ring4.json",
    harness.load_json(harness.BENCH_DIR + "/configs/lcx-ring4.json"),
    harness.load_json(harness.BENCH_DIR + "/traffic/pingpong.json"),
    [{{"name": "msg_rate", "unit": "msgs/s"}}])
print(execute(cell, jax.devices()[:4], 2**34 + 9, 1.0, False,
              {{"wrong_payloads": 0}}, time.perf_counter()))
"""


@pytest.mark.parametrize("mode", ["sound", "broken", "altered"])
def test_ring_exchange_left_out_is_caught(tmp_path, mode):
    script = tmp_path / "ring.py"
    script.write_text(RING.format(root=harness.ROOT,
                                  src=os.path.join(harness.ROOT, "src")))
    p = subprocess.run(
        [sys.executable, str(script), mode], cwd=tmp_path,
        env=cpu_env(XLA_FLAGS="--xla_force_host_platform_device_count=4",
                    JAX_COMPILATION_CACHE_DIR=str(tmp_path)),
        capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    res = last_json(p.stdout)
    assert res["correct"] is (mode == "sound")
    assert res["metrics"]["msg_rate"]["value"] > 0
    assert (res["failed"] == 0) is (mode == "sound")


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_every_entry_is_found_by_name():
    """What BENCHMARK.json names exists as a file of its own."""
    b = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    e2e = {m["name"]: m for m in b["end_to_end"]}
    for c in b["configs"]:
        assert NAME.match(c["name"])
        assert os.path.exists(os.path.join(harness.ROOT, c["file"]))
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert os.path.exists(os.path.join(
            harness.BENCH_DIR, "traffic", w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(
            harness.BENCH_DIR, "limits", w["name"] + ".json"))
        reported = harness.cell_metrics(b, w["name"], False)
        names = {m["name"] for m in reported}
        assert "setup_s" in names and len(names) >= 2
        per_layer = harness.cell_metrics(b, w["name"], True)
        assert per_layer and all(m["moves"] in names for m in per_layer)
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"])
        harness.load_reader(m["name"])
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        # every per-layer metric lists its cells, and each reports the
        # end-to-end metric it moves
        assert m["workloads"]
        for w in m["workloads"]:
            reported = harness.cell_metrics(b, w, False)
            assert m["moves"] in {x["name"] for x in reported}


BENCH = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
# the module that runs each kind of cell, as bench/run.py picks it, and
# the checks it compares with a limit from the cell's limits file
RUNS = {"causal_lm": serving, "deployment": ring}
CHECKS = {"causal_lm": {"logit_gap"}, "deployment": {"wrong_payloads"}}


def limits_read(module) -> set:
    """The keys that ``module.run_cell`` reads from its limits."""
    tree = ast.parse(inspect.getsource(module.run_cell))
    return {n.slice.value for n in ast.walk(tree)
            if isinstance(n, ast.Subscript) and isinstance(n.value, ast.Name)
            and n.value.id == "limits" and isinstance(n.slice, ast.Constant)}


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_limits_name_the_checks_of_their_kind(workload):
    """A cell's limits file holds a number for each check that the
    module running its kind compares with a limit from the file, and
    nothing else."""
    cell = harness.find_cell(workload, False, BENCH)
    kind = cell.config["kind"]
    limits = harness.load_json(os.path.join(harness.BENCH_DIR, "limits",
                                            workload + ".json"))
    assert limits_read(harness.by_kind(cell, RUNS)) == CHECKS[kind]
    assert set(limits) == CHECKS[kind]
    assert all(isinstance(v, (int, float)) and v >= 0
               for v in limits.values())
