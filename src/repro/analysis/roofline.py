"""Roofline analysis from compiled dry-run artifacts.

Per (arch × shape × mesh) we derive three per-device time terms:

    compute    = HLO_FLOPs / peak_FLOP/s            (197e12 bf16, v5e)
    memory     = HLO_bytes / HBM_bw                 (819e9 B/s)
    collective = wire_bytes / ICI_axis_bw           (2 × 50e9 B/s)

``HLO_FLOPs``/``HLO_bytes`` come from ``compiled.cost_analysis()`` of the
SPMD-partitioned per-device module.  ``collective`` is NOT in
cost_analysis: we parse the optimized HLO text and sum the wire bytes of
every collective op, using standard ring/all-to-all cost models:

    all-gather      out_bytes × (g-1)/g
    reduce-scatter  in_bytes  × (g-1)/g
    all-reduce      2 × bytes × (g-1)/g
    all-to-all      bytes × (g-1)/g
    collective-permute  bytes

where g is the replica-group size parsed from the op's
``replica_groups`` attribute (iota `[a,b]<=[n]` or explicit braces).

The dominant term is the bottleneck; ``MODEL_FLOPS / HLO_FLOPs`` exposes
remat/redundancy waste (< 1/3 for fwd+bwd means heavy recompute).
"""
from __future__ import annotations

import dataclasses
import json
import math
import re
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro.launch.mesh import DRYRUN_DEVICE_KIND, peaks

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1,
    "f8e5m2": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"(\w[\w.\-]*)\s*=\s*([a-z0-9]+)\[([\d,]*)\][^=]*?"
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)\b", re.I)

_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


@dataclasses.dataclass
class CollectiveOp:
    kind: str
    dtype: str
    shape: Tuple[int, ...]
    bytes: int           # tensor bytes (per device output/input)
    group: int           # replica group size
    wire_bytes: float    # estimated bytes over ICI per device


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_BRACE_RE.search(line)
    if m:
        inner = m.group(1).strip()
        return len([t for t in inner.split(",") if t.strip() != ""])
    return 1


def _wire(kind: str, nbytes: int, g: int) -> float:
    if g <= 1:
        return 0.0
    frac = (g - 1) / g
    kind = kind.lower()
    if kind == "all-gather":
        return nbytes * frac            # nbytes = gathered (output) size
    if kind == "reduce-scatter":
        return nbytes * frac            # nbytes = input size (per device)
    if kind == "all-reduce":
        return 2.0 * nbytes * frac
    if kind == "all-to-all":
        return nbytes * frac
    if kind == "collective-permute":
        return float(nbytes)
    return float(nbytes)


def parse_collectives(hlo_text: str) -> List[CollectiveOp]:
    ops: List[CollectiveOp] = []
    for line in hlo_text.splitlines():
        if "-start" in line and (" = " in line):
            # avoid double counting start/done pairs: count -start only,
            # skip matching "-done"
            pass
        m = _COLL_RE.search(line)
        if not m:
            continue
        name, dtype, dims, kind = m.groups()
        if name.endswith("-done") or ".done" in name:
            continue
        if dtype not in _DTYPE_BYTES:
            continue
        shape = tuple(int(x) for x in dims.split(",") if x) if dims \
            else ()
        elems = int(np.prod(shape)) if shape else 1
        nbytes = elems * _DTYPE_BYTES[dtype]
        g = _group_size(line)
        ops.append(CollectiveOp(kind=kind.lower(), dtype=dtype,
                                shape=shape, bytes=nbytes, group=g,
                                wire_bytes=_wire(kind, nbytes, g)))
    return ops


def collective_bytes(hlo_text: str) -> float:
    return sum(op.wire_bytes for op in parse_collectives(hlo_text))


# ---------------------------------------------------------------------------
# model flops (the "useful work" yardstick)
# ---------------------------------------------------------------------------
def active_params(cfg: Any, params_proto: Any) -> Tuple[int, int]:
    """(total, active-per-token) parameter counts, embeddings excluded
    from the 6ND convention."""
    import jax
    total = 0
    active = 0
    flat = jax.tree_util.tree_flatten_with_path(params_proto)[0]
    for kp, leaf in flat:
        n = int(np.prod(leaf.shape))
        name = jax.tree_util.keystr(kp)
        total += n
        if "embed" in name or "head" in name and "['head']" in name:
            continue
        if "ffn" in name and ("w_gate" in name or "w_up" in name
                              or "w_down" in name):
            # routed experts: only top-k of E active
            if cfg.n_experts:
                active += n * cfg.n_experts_per_tok // cfg.n_experts
            else:
                active += n
        else:
            active += n
    return total, active


def model_flops(cfg: Any, params_proto: Any, kind: str, seq_len: int,
                global_batch: int) -> float:
    """6·N_active·D for train, 2·N_active·D for inference (global)."""
    _, n_active = active_params(cfg, params_proto)
    if kind == "train":
        tokens = seq_len * global_batch
        return 6.0 * n_active * tokens
    if kind == "prefill":
        tokens = seq_len * global_batch
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * global_batch


# ---------------------------------------------------------------------------
# report
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float            # per device
    hlo_bytes: float            # per device
    wire_bytes: float           # per device
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops_global: float
    useful_ratio: float         # model_flops / (hlo_flops * chips)
    roofline_frac: float        # max-term lower bound vs dominant
    n_collectives: int
    collectives_by_kind: Dict[str, float]
    memory_analysis: Dict[str, float]

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    def summary(self) -> str:
        return (f"{self.arch:28s} {self.shape:12s} {self.mesh:9s} "
                f"compute={self.compute_s*1e3:9.3f}ms "
                f"memory={self.memory_s*1e3:9.3f}ms "
                f"coll={self.collective_s*1e3:9.3f}ms "
                f"bound={self.bottleneck:10s} "
                f"useful={self.useful_ratio:6.3f} "
                f"frac={self.roofline_frac:5.3f}")


def _mem_dict(compiled: Any) -> Dict[str, float]:
    out: Dict[str, float] = {}
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return out
    for field in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes", "peak_memory_in_bytes"):
        v = getattr(ma, field, None)
        if v is not None:
            out[field] = float(v)
    return out


def analyze_compiled(compiled: Any, *, arch: str, shape: str, mesh_name: str,
                     chips: int, cfg: Any = None,
                     params_proto: Any = None, kind: str = "train",
                     seq_len: int = 0, global_batch: int = 0
                     ) -> RooflineReport:
    from .hlo_walk import walk
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    hlo = compiled.as_text()
    totals = walk(hlo)
    # loop-aware dot flops (cost_analysis counts while bodies once);
    # keep the larger of the two so elementwise-dominated graphs are not
    # undercounted either.
    flops = max(totals.flops, float(cost.get("flops", 0.0)))
    # HBM bytes: loop-aware dot operand/result traffic vs cost_analysis's
    # single-pass "bytes accessed"
    nbytes = max(totals.dot_bytes, float(cost.get("bytes accessed", 0.0)))
    wire = totals.coll_wire
    by_kind: Dict[str, float] = dict(totals.coll_by_kind)
    n_coll = int(totals.n_coll)

    peak = peaks(DRYRUN_DEVICE_KIND)
    compute_s = flops / peak["flops_bf16"]
    memory_s = nbytes / peak["hbm_bw"]
    # a ring uses both directions of an axis
    collective_s = wire / (2 * peak["ici_link_bw"])
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    bottleneck = max(terms, key=terms.get)

    mf = (model_flops(cfg, params_proto, kind, seq_len, global_batch)
          if cfg is not None and params_proto is not None else 0.0)
    useful = mf / (flops * chips) if flops else 0.0
    # roofline fraction: time the dominant term says we need vs the sum —
    # a schedule that perfectly overlaps the other two terms achieves
    # max(terms)/sum(terms)=1; we report dominant/sum as the structural
    # overlap headroom, and the per-term seconds for iteration.
    tot = sum(terms.values())
    frac = terms[bottleneck] / tot if tot else 0.0

    return RooflineReport(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        hlo_flops=flops, hlo_bytes=nbytes, wire_bytes=wire,
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        bottleneck=bottleneck, model_flops_global=mf,
        useful_ratio=useful, roofline_frac=frac,
        n_collectives=n_coll, collectives_by_kind=by_kind,
        memory_analysis=_mem_dict(compiled),
    )
