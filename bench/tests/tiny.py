"""Cells of the benchmark cut to a size a CPU test can run: the same
configuration and traffic files, with the widths, slots and lengths made
small."""
import copy
import os

from bench import harness

METRICS = [{"name": n, "unit": u} for n, u in
           (("ttft_p95_ms", "ms"), ("itl_p95_ms", "ms"),
            ("tokens_per_s", "tokens/s"), ("setup_s", "s"))]


def serving_cell(config: str, traffic: str) -> harness.Cell:
    c = harness.load_json(os.path.join(harness.BENCH_DIR, "configs",
                                       config + ".json"))
    m = harness.load_json(os.path.join(harness.BENCH_DIR, "traffic",
                                       traffic + ".json"))
    c.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=2, num_hidden_layers=2, vocab_size=256)
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
    return harness.Cell(f"{config}.{traffic}", 1, c, m, METRICS)
