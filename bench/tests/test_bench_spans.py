"""The reduction by the program's own spans (``bench/tools/spans.py``)
and the KV-cache fill reader, on traces and ticks made by hand, and on
one tiny served window on the CPU."""
import importlib.util
import os
import time

import jax
import pytest

from bench import harness, tracing
from bench.harness import Run, Tick
from bench.tests import tiny
from bench.tracing import Device

MS = 1e6     # ns


def spans_tool():
    path = os.path.join(harness.BENCH_DIR, "tools", "spans.py")
    spec = importlib.util.spec_from_file_location("bench_tool_spans", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def trace_of(devices, spans):
    return tracing.from_events(devices, spans +
                               [("bench.traced", 0.0, 100 * MS)])


def test_gap_under_nested_spans_takes_the_innermost():
    dev = Device([], [("fusion", 0.0, 10 * MS), ("fusion", 20 * MS, 30 * MS)])
    tr = trace_of({"d": dev}, [
        ("bench.tick", 0.0, 40 * MS), ("serve.tick", 1 * MS, 39 * MS),
        ("amt.run", 2 * MS, 38 * MS), ("amt.task", 3 * MS, 37 * MS),
        ("serve.decode", 4 * MS, 36 * MS), ("serve.sync", 12 * MS, 19 * MS)])
    # idle 10-20 (mid 15: inside the sync) and 30-100 (mid 65: nothing)
    assert spans_tool().idle_gaps(tr, "d") == pytest.approx(
        {"serve.sync": 0.01, "none": 0.07})


def test_gap_after_many_closed_short_spans_takes_the_long_one():
    short = [("serve.sync", (10 + k) * MS, (10.5 + k) * MS)
             for k in range(12)]
    tr = trace_of({"d": Device([], [("fusion", 0.0, 30 * MS)])},
                  [("serve.tick", 5 * MS, 90 * MS)] + short)
    # idle 30-100, mid 65: the tick is open, every short span has closed
    assert spans_tool().idle_gaps(tr, "d") == pytest.approx(
        {"serve.tick": 0.07})


def test_innermost_at_points_in_any_order():
    spans = [("a", 0, 100), ("b", 10, 20), ("c", 30, 90), ("d", 40, 50)]
    assert spans_tool().innermost(spans, [45, 15, 95, 100, 60, 5, 20]) == \
        ["d", "b", "a", "none", "c", "a", "a"]


def test_idle_gaps_agree_with_the_harness_on_its_own_spans():
    dev = Device(
        modules=[("jit_decode_slots", 10 * MS, 30 * MS),
                 ("jit_prefill_slot", 50 * MS, 60 * MS),
                 ("jit_decode_slots", 95 * MS, 120 * MS)],
        ops=[("fusion.1", 10 * MS, 20 * MS), ("fusion.2", 15 * MS, 28 * MS),
             ("flash_attention", 50 * MS, 60 * MS),
             ("fusion.1", 95 * MS, 120 * MS)])
    tr = trace_of({"d": dev}, [("bench.tick", 5 * MS, 35 * MS),
                               ("bench.wait_arrival", 35 * MS, 48 * MS),
                               ("bench.tick", 48 * MS, 62 * MS),
                               ("bench.tick", 90 * MS, 130 * MS)])
    assert spans_tool().idle_gaps(tr, "d") == \
        pytest.approx(tracing.idle_gaps(tr, "d"))


def served_ticks():
    """Two ticks of 10 ms: the first admits (its task holds the
    admission) and decodes, the second only decodes."""
    sp = []
    for t0 in (0, 10):
        sp += [("serve.tick", t0 * MS, (t0 + 10) * MS),
               ("amt.run", (t0 + 1) * MS, (t0 + 9) * MS),
               ("amt.task", (t0 + 5) * MS, (t0 + 8) * MS),
               ("serve.decode", (t0 + 5) * MS, (t0 + 8) * MS),
               ("serve.sync", (t0 + 6) * MS, (t0 + 7.5) * MS)]
    sp += [("amt.task", 1.5 * MS, 4.5 * MS),
           ("serve.admit", 1.5 * MS, 4.5 * MS),
           ("serve.sync", 4 * MS, 4.5 * MS)]
    return sp


def test_program_numbers_on_made_ticks():
    got = spans_tool().program_numbers(trace_of({}, served_ticks()))
    # amt.run 8 ms each, less its tasks (3 + 3 and 3): 2 + 5 over 2 ticks
    assert got == {"serve_ticks": 2, "sched_ms": pytest.approx(3.5),
                   "admit_ms": pytest.approx(3.0),
                   # 10 - (1.5 + 0.5) and 10 - 1.5
                   "tick_host_ms": pytest.approx(8.25),
                   "serve_tick_ms": pytest.approx(10.0)}


@pytest.mark.parametrize("drop,nulls", [
    ("serve.", {"sched_ms", "admit_ms", "tick_host_ms", "serve_tick_ms"}),
    ("amt.", {"sched_ms"}),
    ("serve.admit", {"admit_ms"}),
])
def test_program_numbers_without_their_spans_are_null(drop, nulls):
    tr = trace_of({}, [s for s in served_ticks()
                       if not s[0].startswith(drop)])
    got = spans_tool().program_numbers(tr)
    assert {k for k, v in got.items() if v is None} == nulls


def test_program_spans_widen_the_kept_names_only_meanwhile():
    tool = spans_tool()
    with tool.program_spans():
        assert "serve.tick".startswith(tracing.SPAN_PREFIX)
        assert "amt.run".startswith(tracing.SPAN_PREFIX)
        assert "bench.tick".startswith(tracing.SPAN_PREFIX)
    assert tracing.SPAN_PREFIX == "bench."


def test_kv_fill_is_over_the_traced_decode_steps():
    run = Run("w", {"serve": {"n_slots": 4, "max_seq": 100}},
              {"kind": "open_loop"}, "TPU v5 lite", 1)
    run.ticks = [Tick(0.0, 0.01, [64], 0, 0, traced=True),
                 Tick(0.01, 0.02, [], 3, 120, traced=True),
                 Tick(0.02, 0.03, [], 2, 80, traced=True),
                 Tick(0.03, 0.04, [], 4, 400, traced=False)]
    read = harness.load_reader("kv_fill_pct")
    # (120 + 80) rows over 2 steps of a 400-row pool
    assert read(run) == pytest.approx(25.0)
    for t in run.ticks:
        t.traced = False
    assert read(run) is None


@pytest.fixture
def no_cache(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


def test_measure_a_tiny_served_window(no_cache):
    cell = tiny.serving_cell("qwen2-0.5b", "chat")
    cell.metrics = [{"name": n, "unit": u} for n, u in
                    (("tick_ms", "ms"), ("kv_fill_pct", "%"))]
    cell.mix.update(trace_lead_s=0.0, trace_s=1.5)   # the whole window
    got = spans_tool().measure(cell, 2**33 + 5, 1.5, jax.devices()[:1],
                               {"logit_gap": 0.05}, time.perf_counter())
    assert got["correct"] is True
    assert got["serve_ticks"] > 0
    for k in ("sched_ms", "admit_ms", "tick_host_ms", "serve_tick_ms"):
        assert got[k] > 0, k
    assert got["tick_host_ms"] <= got["serve_tick_ms"]
    # the program's tick lies inside the harness's, on another clock
    assert got["serve_tick_ms"] <= 1.05 * got["metrics"]["tick_ms"]
    assert 0 < got["metrics"]["kv_fill_pct"] <= 100
    assert tracing.SPAN_PREFIX == "bench."
    assert got["idle_gaps"] is None        # the CPU has no device plane
