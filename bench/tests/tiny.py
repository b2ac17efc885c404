"""Cells of the benchmark cut to a size a CPU test can run: the same
configuration and traffic files, with the model cut by its
architecture's ``small`` and the slots and lengths made small."""
import copy
import os

from bench import harness, serving

METRICS = [{"name": n, "unit": u} for n, u in
           (("ttft_p95_ms", "ms"), ("itl_p95_ms", "ms"),
            ("tokens_per_s", "tokens/s"), ("setup_s", "s"))]


def serving_cell(config: str, traffic: str) -> harness.Cell:
    path = os.path.join(harness.BENCH_DIR, "configs", config + ".json")
    source = os.path.relpath(path, harness.ROOT)
    c = harness.load_json(path)
    m = harness.load_json(os.path.join(harness.BENCH_DIR, "traffic",
                                       traffic + ".json"))
    c = serving.load_arch(c, source)[0].small(c)
    c["serve"] = dict(c["serve"], n_slots=4, max_seq=512)
    m = copy.deepcopy(m)
    if m["kind"] == "open_loop":
        m.update(rate_per_s=6.0, drain_s=30,
                 prompt=dict(m["prompt"], min=32, max=128, median=64,
                             grid=32),
                 output=dict(m["output"], min=4, max=16, median=8))
    else:
        m.update(backlog=4, pool=16,
                 output=dict(m["output"], min=8, max=32, median=16))
    m.update(trace_lead_s=0.2, trace_s=0.4, check_tokens=64)
    return harness.Cell(f"{config}.{traffic}", 1, source, c, m, METRICS)
