"""What every cell shares: finding the cell's files by name, the device
check, compile counting, the traced window, the metric readers and the
result line.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(Exception):
    """JAX found no accelerator, or fewer chips than the cell needs."""


# ---------------------------------------------------------------------------
# the cell's files
# ---------------------------------------------------------------------------
def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_file: str              # bench/configs/<config>.json
    config: Dict[str, Any]        # what that file holds
    mix: Dict[str, Any]           # bench/traffic/<traffic>.json
    metrics: List[Dict[str, Any]]  # BENCHMARK.json entries it reports


def cell_metrics(bench: Dict[str, Any], workload: str,
                 per_layer: bool) -> List[Dict[str, Any]]:
    """The end-to-end or the per-layer metrics this cell reports."""
    if per_layer:                 # every per-layer metric lists its cells
        return [m for m in bench["per_layer"] if workload in m["workloads"]]
    return [m for m in bench["end_to_end"]
            if workload in m.get("workloads", [workload])]


def find_cell(workload: str, per_layer: bool,
              bench: Optional[Dict[str, Any]] = None) -> Cell:
    bench = bench or load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(workload, int(w["chips"]), conf["file"],
                load_json(os.path.join(ROOT, conf["file"])),
                load_json(os.path.join(BENCH_DIR, "traffic",
                                       w["traffic"] + ".json")),
                cell_metrics(bench, workload, per_layer))


def by_kind(cell: Cell, table: Dict[str, Any]) -> Any:
    """The entry of ``table`` for the ``"kind"`` that the cell's
    configuration file states."""
    kind = cell.config.get("kind")
    if kind not in table:
        raise ValueError(f"{cell.config_file}: \"kind\" is {kind!r}; "
                         f"known: {sorted(table)}")
    return table[kind]


def load_reader(name: str) -> Callable[["Run"], Optional[float]]:
    """``read`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# device and compiles
# ---------------------------------------------------------------------------
def chips(n: int) -> List[Any]:
    """The first ``n`` TPU devices; raises :class:`NoChip` otherwise."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r}); "
                     f"the benchmark runs only on the chip")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def device_tag(devs: List[Any]) -> str:
    return f"[{devs[0].platform} {devs[0].device_kind} x{len(devs)}]"


class CompileLog:
    """Every XLA compile request of the process, from JAX's monitoring
    events, and the persistent cache's hits."""

    def __init__(self) -> None:
        import jax
        self.compiles = 0
        self.cache_hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw) -> None:
        if event == COMPILE_EVENT:
            self.compiles += 1
            self.seconds += secs

    def _event(self, event, **kw) -> None:
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1


def memory_peak(devs: List[Any]) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


# ---------------------------------------------------------------------------
# the record of a run, which the metric readers read
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Req:
    """One request of the window, timed from outside the engine."""
    arrival: float                # scheduled, host perf_counter seconds
    prompt_len: int
    max_new: int
    token_times: List[float] = dataclasses.field(default_factory=list)
    n_out: int = 0
    done: bool = False
    failed: bool = False


@dataclasses.dataclass
class Tick:
    t0: float
    t1: float
    prefill_lens: List[int]       # prompts admitted in this tick
    decoded: int                  # slots the decode advanced
    kv_rows: int                  # cache rows those slots attend
    traced: bool = False


@dataclasses.dataclass
class Run:
    workload: str
    config: Dict[str, Any]
    mix: Dict[str, Any]
    device_kind: str
    n_devices: int
    setup_s: float = 0.0
    window_t0: float = 0.0
    window_t1: float = 0.0
    spec: Any = None              # arch.Spec of a served model
    arch: Any = None              # its module, bench/archs/<arch>.py
    requests: List[Req] = dataclasses.field(default_factory=list)
    ticks: List[Tick] = dataclasses.field(default_factory=list)
    rounds: int = 0
    messages_per_round: int = 0
    trace: Any = None             # tracing.Trace of the traced window

    @property
    def window_s(self) -> float:
        return self.window_t1 - self.window_t0


@dataclasses.dataclass
class Outcome:
    """What a cell's run hands back: its record, the numbers compared
    with their limits, the counts, the memory peak, and what the check
    compared (for the tools that read a control on the same inputs)."""
    run: Run
    checks: Dict[str, Dict[str, float]]
    attempted: int
    failed: int
    memory_peak: int
    checked: Any = None


def percentile(values: List[float], q: float) -> Optional[float]:
    """The ``q``-th percentile of all values (linear interpolation)."""
    import numpy as np
    return float(np.percentile(np.asarray(values, float), q)) \
        if len(values) else None


# ---------------------------------------------------------------------------
# spans and the traced window
# ---------------------------------------------------------------------------
def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation("bench." + name)


class Tracer:
    """Traces ``length`` seconds from ``lead`` seconds into the window,
    when tracing is on.  ``poll`` is called between ticks or rounds, so
    no tick straddles an edge of the traced window."""

    def __init__(self, on: bool, lead: float, length: float) -> None:
        self.on, self.lead, self.length = on, lead, length
        self.active = False
        self.done = not on
        self.dir: Optional[str] = None
        self._ann = None

    def poll(self, elapsed: float) -> bool:
        """Start or stop as the window's ``elapsed`` seconds say; returns
        whether the trace is running."""
        import jax
        if self.done:
            return False
        if not self.active and elapsed >= self.lead:
            self.dir = tempfile.mkdtemp(prefix="bench-trace-")
            # no Python call tracing: it slows the host several times over
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._ann = span("traced")
            self._ann.__enter__()
            self.active = True
        elif self.active and elapsed >= self.lead + self.length:
            self.stop()
        return self.active

    def stop(self) -> None:
        import jax
        if self.active:
            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.active, self.done = False, True

    def read(self):
        from bench import tracing
        if self.dir is None:
            return None
        try:
            return tracing.read(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


# ---------------------------------------------------------------------------
# the result
# ---------------------------------------------------------------------------
def metric_values(run: Run, metrics: List[Dict[str, Any]]
                  ) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in metrics:
        v = load_reader(m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: Dict[str, Any], device: Dict[str, Any],
                checks: Dict[str, Dict[str, float]],
                breakdown: Optional[Dict[str, Any]] = None) -> str:
    res: Dict[str, Any] = {"correct": correct, "attempted": attempted,
                           "failed": failed, "metrics": metrics,
                           "device": device}
    if breakdown is not None:
        res["breakdown"] = breakdown
    res["checks"] = checks                # last, as the contract asks
    return json.dumps(res)


def judge(checks: Dict[str, Dict[str, float]]) -> bool:
    """``correct``: every number compared within its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())


def print_checks(checks: Dict[str, Dict[str, float]]) -> None:
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)


def log(tag: str, msg: str) -> None:
    print(f"{tag} {msg}", flush=True)


def now() -> float:
    return time.perf_counter()
