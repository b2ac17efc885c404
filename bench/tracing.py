"""Reduction of a profiler trace to the numbers the metrics read.

``jax.profiler`` writes an ``.xplane.pb``; ``ProfileData`` reads it.  A
TPU's plane is ``/device:TPU:<n>``: its line ``XLA Modules`` holds one
event per execution of a compiled program (``jit_decode_slots(<id>)``,
``jit_prefill_slot(<id>)``, ...) and ``XLA Ops`` one per operation
inside it, named by its HLO text (``%fusion.12 = ...``,
``%collective-permute-start.3 = ...``, the Pallas kernels' custom
calls); an operation inside a loop appears once per iteration, nested
in the loop's own event.
The host plane ``/host:CPU`` holds the harness's own spans
(``jax.profiler.TraceAnnotation``, all named ``bench.*``) on the same
clock.  Everything is clipped to the ``bench.traced`` span, the traced
window.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start ns, end ns
WINDOW_SPAN = "bench.traced"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Device:
    modules: List[Event]
    ops: List[Event]


@dataclasses.dataclass
class Trace:
    start_ns: float
    end_ns: float
    devices: Dict[str, Device]
    spans: List[Event]                    # harness spans, bench.*

    @property
    def window_s(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


def stable_name(name: str) -> str:
    """A program's name without the run-specific id JAX appends."""
    return re.sub(r"\(\d+\)$", "", name)


def _clip(evs: Sequence[Event], lo: float, hi: float) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in evs
            if e > lo and s < hi]


def read(trace_dir: str) -> Trace:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    spans: List[Event] = []
    devices: Dict[str, Device] = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.end_ns) for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
        elif DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            mods = [(stable_name(e.name), e.start_ns, e.end_ns)
                    for e in lines["XLA Modules"].events] \
                if "XLA Modules" in lines else []
            ops = [(e.name, e.start_ns, e.end_ns)
                   for e in lines["XLA Ops"].events] \
                if "XLA Ops" in lines else []
            devices[plane.name] = Device(mods, ops)
    return from_events(devices, spans)


def from_events(devices: Dict[str, Device], spans: List[Event]) -> Trace:
    """A trace clipped to its ``bench.traced`` span."""
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    if not win:
        raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
    _, lo, hi = win[0]
    devices = {k: Device(_clip(d.modules, lo, hi), _clip(d.ops, lo, hi))
               for k, d in devices.items()}
    spans = [s for s in _clip(spans, lo, hi) if s[0] != WINDOW_SPAN]
    return Trace(lo, hi, devices, spans)


def merged(evs: Sequence[Event]) -> List[Tuple[float, float]]:
    """The union of the events' intervals, in order."""
    out: List[List[float]] = []
    for _, s, e in sorted(evs, key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_events(dev: Device) -> List[Event]:
    return dev.ops or dev.modules


def busy_s(trace: Trace, name: str) -> float:
    return sum(e - s for s, e in merged(busy_events(trace.devices[name]))) \
        * 1e-9


def idle_share(trace: Trace, name: str) -> float:
    """1 - (union of the device's operation intervals / traced window)."""
    return 1.0 - busy_s(trace, name) / trace.window_s


def worst_idle_pct(run) -> Optional[float]:
    """Device idle share of a run's traced window in %, the highest over
    the cell's chips; None without a trace."""
    if run.trace is None or not run.trace.devices:
        return None
    return 100.0 * max(idle_share(run.trace, d) for d in run.trace.devices)


def program_time(trace: Trace, name: str,
                 prefix: str) -> Tuple[float, int]:
    """(seconds, executions) of the programs whose name starts with
    ``prefix`` on one device."""
    evs = [e for e in trace.devices[name].modules if e[0].startswith(prefix)]
    return sum(e - s for _, s, e in evs) * 1e-9, len(evs)


def op_time(trace: Trace, name: str,
            match: Callable[[str], bool]) -> Tuple[float, int]:
    """(seconds, count) of the operations whose name ``match`` accepts."""
    evs = [e for e in trace.devices[name].ops if match(e[0])]
    return sum(e - s for _, s, e in evs) * 1e-9, len(evs)


def idle_gaps(trace: Trace, name: str) -> Dict[str, float]:
    """Seconds the device sat idle, by the innermost harness span open on
    the host at the middle of each gap ("none" where none was)."""
    busy = merged(busy_events(trace.devices[name]))
    edges = [trace.start_ns] + [t for iv in busy for t in iv] + \
        [trace.end_ns]
    spans = sorted(trace.spans, key=lambda s: s[1])
    starts = [s[1] for s in spans]
    out: Dict[str, float] = collections.defaultdict(float)
    for lo, hi in zip(edges[::2], edges[1::2]):
        if hi <= lo:
            continue
        mid = (lo + hi) / 2
        # the harness's spans nest at most a few deep: the open ones are
        # among the last few that started before ``mid``
        i = bisect.bisect_right(starts, mid)
        open_ = [s for s in spans[max(0, i - 8):i] if mid < s[2]]
        label = min(open_, key=lambda s: s[2] - s[1])[0] if open_ \
            else "none"
        out[label] += (hi - lo) * 1e-9
    return dict(out)


def top(items: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(items.items(),
                                      key=lambda kv: -kv[1])[:n]]


def breakdown(trace: Trace) -> Dict[str, List[List]]:
    """The compiled programs that took most device time, and the idle
    time by host span, each averaged over the traced devices."""
    n = max(1, len(trace.devices))
    ops: Dict[str, float] = collections.defaultdict(float)
    gaps: Dict[str, float] = collections.defaultdict(float)
    for name, dev in trace.devices.items():
        for op, s, e in dev.modules:
            ops[op] += (e - s) * 1e-9 / n
        for label, secs in idle_gaps(trace, name).items():
            gaps[label] += secs / n
    return {"device_ops": top(ops), "idle_gaps": top(gaps)}


def mean_busy_s(trace: Trace) -> Optional[float]:
    if not trace.devices:
        return None
    return sum(busy_s(trace, d) for d in trace.devices) / len(trace.devices)
