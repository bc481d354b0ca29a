"""Roofline-term extraction from compiled dry-run artifacts.

Hardware model (TPU v5e, per chip):
  peak bf16 compute 197 TFLOP/s; HBM bandwidth 819 GB/s; ICI ~50 GB/s/link.

``compiled.cost_analysis()`` yields HLO FLOPs and bytes for the *per-device*
(post-SPMD) module; collective traffic is not in cost_analysis, so we parse
the partitioned HLO text and sum the output bytes of every collective op
(shapes in that module are already per-device, so the resulting byte count
is per-chip traffic):

  compute term    = device_flops / peak_flops
  memory term     = device_bytes / hbm_bw
  collective term = device_collective_bytes / ici_bw
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional

PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"=\s*(\([^)]*\)|\S+)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")


def _shape_bytes(type_str: str) -> int:
    tot = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        tot += n * _DTYPE_BYTES[dt]
    return tot


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-collective-kind output bytes from a partitioned HLO module."""
    out: Dict[str, int] = {k: 0 for k in COLLECTIVES}
    for m in _OP_RE.finditer(hlo_text):
        type_str, kind, start = m.group(1), m.group(2), m.group(3)
        # `-done` ops would double-count their `-start`
        out[kind] += _shape_bytes(type_str)
    return out


@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    device_flops: float = 0.0
    device_bytes: float = 0.0
    coll_bytes: Dict[str, int] = field(default_factory=dict)
    bytes_per_device: Optional[float] = None      # memory_analysis temp+args
    model_flops: float = 0.0                      # 6*N*D useful flops (global)
    xla_flops: float = 0.0                        # raw cost_analysis (no trips)

    @property
    def compute_s(self) -> float:
        return self.device_flops / PEAK_FLOPS

    @property
    def memory_s(self) -> float:
        return self.device_bytes / HBM_BW

    @property
    def collective_s(self) -> float:
        return sum(self.coll_bytes.values()) / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_flops_frac(self) -> float:
        tot = self.device_flops * self.chips
        return self.model_flops / tot if tot else 0.0

    def to_dict(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips,
            "device_flops": self.device_flops,
            "device_bytes": self.device_bytes,
            "coll_bytes": dict(self.coll_bytes),
            "bytes_per_device": self.bytes_per_device,
            "model_flops": self.model_flops,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bottleneck": self.bottleneck,
            "useful_flops_frac": self.useful_flops_frac,
            "xla_flops": self.xla_flops,
        }


def model_flops(cfg, shape) -> float:
    """6*N*D (train) / 2*N*D (inference) useful-FLOP model; N = active
    params for MoE."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch          # decode: one token/seq


def xla_cost_dict(compiled) -> dict:
    """``compiled.cost_analysis()`` as a dict (empty when XLA has none)."""
    try:
        c = compiled.cost_analysis()
    except Exception:
        return {}
    return c or {}


def analyse(compiled, cfg, shape, mesh_name: str, chips: int) -> Roofline:
    """Roofline terms from the partitioned module via the trip-count-aware
    HLO cost model (launch/hlo_cost.py).  ``compiled.cost_analysis()`` is
    recorded too, but it counts while bodies once — a 28-60 layer scan
    under-reports by ~L (verified; EXPERIMENTS.md §Roofline methodology)."""
    from repro.launch import hlo_cost

    xla_cost = xla_cost_dict(compiled)
    try:
        mem = compiled.memory_analysis()
        bpd = (getattr(mem, "temp_size_in_bytes", 0)
               + getattr(mem, "argument_size_in_bytes", 0)
               + getattr(mem, "output_size_in_bytes", 0)
               - getattr(mem, "alias_size_in_bytes", 0))
    except Exception:
        bpd = None
    c = hlo_cost.analyse_text(compiled.as_text())
    roof = Roofline(
        arch=cfg.arch_id, shape=shape.name, mesh=mesh_name, chips=chips,
        device_flops=c.flops,
        device_bytes=c.bytes,
        coll_bytes={k: int(v) for k, v in c.coll.items()},
        bytes_per_device=bpd,
        model_flops=model_flops(cfg, shape),
    )
    roof.xla_flops = float(xla_cost.get("flops", 0.0))
    return roof
