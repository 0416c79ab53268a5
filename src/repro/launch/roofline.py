"""Roofline-term extraction from compiled dry-run artifacts.

  compute term    = HLO_FLOPs_per_device / peak_FLOP/s            [s]
  memory term     = HLO_bytes_per_device / HBM_bw                 [s]
  collective term = collective_bytes_per_device / link_bw         [s]

``compiled.cost_analysis()`` on the SPMD-partitioned module reports *per
device* flops/bytes (verified empirically: a (32,256)x(256,512) matmul on 8
devices reports total/8).  Collective bytes are parsed from the compiled HLO
text: for each all-reduce / all-gather / reduce-scatter / all-to-all /
collective-permute, the result shapes (per-device shards) are converted to
per-device link traffic with the standard algorithmic factors.

Caveat recorded in EXPERIMENTS.md: Pallas custom-calls are invisible to
cost_analysis, so cells lowered through kernels add their analytic flops.
"""
from __future__ import annotations

import re
from dataclasses import asdict, dataclass, field

from repro.launch.mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLL_KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


@dataclass
class CollectiveStats:
    counts: dict = field(default_factory=dict)
    bytes_by_kind: dict = field(default_factory=dict)
    effective_bytes: float = 0.0  # per device, algorithmic-factor adjusted
    raw_bytes: float = 0.0

    def add(self, kind: str, nbytes: float, group: int):
        self.counts[kind] = self.counts.get(kind, 0) + 1
        self.bytes_by_kind[kind] = self.bytes_by_kind.get(kind, 0.0) + nbytes
        g = max(group, 2)
        if kind == "all-reduce":
            eff = 2.0 * nbytes * (g - 1) / g
        elif kind == "all-gather":
            eff = nbytes * (g - 1) / g
        elif kind == "reduce-scatter":
            eff = nbytes * (g - 1)
        elif kind == "all-to-all":
            eff = nbytes * (g - 1) / g
        else:  # collective-permute
            eff = nbytes
        self.effective_bytes += eff
        self.raw_bytes += nbytes


def _line_result_bytes(line: str, op_pos: int) -> float:
    """Sum the dtype[shape] result tokens on the LHS of the op keyword."""
    lhs = line[:op_pos]
    if "=" in lhs:
        lhs = lhs.split("=", 1)[1]
    total = 0.0
    for m in _SHAPE_RE.finditer(lhs):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str, num_partitions: int) -> CollectiveStats:
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if not stripped.startswith("%") and not stripped.startswith("ROOT"):
            continue
        for kind in _COLL_KINDS:
            # match "<kind>(" or "<kind>-start(" as the op; skip -done/other refs
            idx = -1
            for suffix in ("(", "-start("):
                probe = f" {kind}{suffix}"
                idx = stripped.find(probe)
                if idx >= 0:
                    break
            if idx < 0:
                continue
            nbytes = _line_result_bytes(stripped, idx)
            g = num_partitions
            m = _GROUPS_RE.search(stripped)
            if m:
                g = int(m.group(2))
            else:
                m2 = _GROUPS_BRACE_RE.search(stripped)
                if m2:
                    g = len([x for x in m2.group(1).split(",") if x.strip() != ""])
            stats.add(kind, nbytes, g)
            break
    return stats


@dataclass
class Roofline:
    label: str
    mesh: str
    chips: int
    flops_per_device: float
    bytes_per_device: float
    collective_bytes_eff: float
    collective_counts: dict
    model_flops_total: float
    memory: dict
    compile_s: float = 0.0
    notes: str = ""

    @property
    def compute_term_s(self) -> float:
        return self.flops_per_device / PEAK_FLOPS_BF16

    @property
    def memory_term_s(self) -> float:
        return self.bytes_per_device / HBM_BW

    @property
    def collective_term_s(self) -> float:
        return self.collective_bytes_eff / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.compute_term_s,
            "memory": self.memory_term_s,
            "collective": self.collective_term_s,
        }
        return max(terms, key=terms.get)

    @property
    def useful_flops_fraction(self) -> float:
        hlo_total = self.flops_per_device * self.chips
        return self.model_flops_total / hlo_total if hlo_total else 0.0

    @property
    def step_time_bound_s(self) -> float:
        return max(self.compute_term_s, self.memory_term_s, self.collective_term_s)

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the chips' peak-FLOPs roofline achieved at the modeled
        step time, counting only useful (MODEL) flops."""
        t = self.step_time_bound_s
        if t <= 0:
            return 0.0
        achieved = self.model_flops_total / t
        peak = self.chips * PEAK_FLOPS_BF16
        return achieved / peak

    def to_dict(self) -> dict:
        d = asdict(self)
        d.update(
            compute_term_s=self.compute_term_s,
            memory_term_s=self.memory_term_s,
            collective_term_s=self.collective_term_s,
            bottleneck=self.bottleneck,
            useful_flops_fraction=self.useful_flops_fraction,
            step_time_bound_s=self.step_time_bound_s,
            roofline_fraction=self.roofline_fraction,
        )
        return d


def analyze_compiled(label: str, mesh_name: str, chips: int, compiled,
                     model_flops: float, compile_s: float, notes: str = "") -> Roofline:
    ca = compiled.cost_analysis()
    ma = compiled.memory_analysis()
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    txt = compiled.as_text()
    coll = parse_collectives(txt, chips)
    memory = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "peak_bytes_est": int(ma.argument_size_in_bytes + ma.temp_size_in_bytes
                              + ma.output_size_in_bytes - ma.alias_size_in_bytes),
    }
    return Roofline(
        label=label,
        mesh=mesh_name,
        chips=chips,
        flops_per_device=flops,
        bytes_per_device=byts,
        collective_bytes_eff=coll.effective_bytes,
        collective_counts={k: [coll.counts[k], coll.bytes_by_kind[k]] for k in coll.counts},
        model_flops_total=model_flops,
        memory=memory,
        compile_s=compile_s,
        notes=notes,
    )
