"""The tools that set a cell's rate and limit: the knee of a sweep, and
the control judged by the cell's own comparison."""
import importlib.util
import os
import time

import jax
import pytest

from bench import harness
from bench.tests import tiny


def tool(name):
    path = os.path.join(harness.BENCH_DIR, "tools", name + ".py")
    spec = importlib.util.spec_from_file_location("bench_tool_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def row(rate, share=1.0, mid=5, close=5, failed=0):
    return {"rate_per_s": rate, "finish_rate_share": share,
            "in_flight_mid": mid, "in_flight_close": close,
            "failed": failed}


@pytest.mark.parametrize("rows,want", [
    ([row(2), row(3), row(4, share=0.9), row(5)], 3),
    ([row(2), row(3, close=30), row(4)], 2),
    ([row(2, failed=1), row(3)], None),
    ([row(4), row(2), row(3)], 4),
])
def test_knee_is_the_last_sustained_rate(rows, want):
    assert tool("sweep").knee(rows) == want


@pytest.fixture
def no_cache(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


# limits between the two readings at this small size
@pytest.mark.parametrize("workload,limit", [(("qwen2-0.5b", "chat"), 0.01),
                                            (("starcoder2-7b", "code"), 0.06)])
def test_control_is_judged_not_correct(no_cache, workload, limit):
    """The cell's comparison passes the program and fails the float8
    control on the same tokens."""
    cell = tiny.serving_cell(*workload)
    compiles = harness.CompileLog()
    r = tool("control").served_row(cell, 2**33 + 5, 1.5, jax.devices()[:1],
                                   {"logit_gap": limit}, "[cpu]", compiles)
    assert r["program"] < limit < r["control"]
    assert r["program_correct"] is True and r["control_correct"] is False
