"""Roofline terms from compiled dry-run artifacts.

Three terms per (arch × shape × mesh), all in seconds-per-step:

    compute    = HLO_FLOPs_per_device / PEAK_FLOPS_BF16
    memory     = HLO_bytes_per_device / HBM_BW
    collective = collective_wire_bytes_per_device / ICI_LINK_BW

``cost_analysis()`` reports **per-device** FLOPs/bytes but counts
while-loop (lax.scan) bodies ONCE, so production configs (scan over
layers, scan over microbatches) are costed via an *unrolled depth probe*:
compile the same step with 1 and 2 unrolled superblocks, take the delta as
per-superblock cost, and scale analytically (see ``extrapolate``).

Collective bytes are not in cost_analysis at all — we parse the optimized
HLO text and convert each collective's result shape + replica-group size
into ring-algorithm wire bytes per device.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Optional

from repro.roofline import hw

_COLL_KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

# e.g.:  %all-gather.3 = bf16[16,4096,512]{2,1,0} all-gather(%x), ...
#        %ar = (f32[128]{0}, f32[64]{0}) all-reduce(%a, %b), ...
_INSTR_RE = re.compile(
    r"=\s*(\(?[a-z0-9_]+\[[0-9,]*\][^)]*?\)?)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9_]+)\[([0-9,]*)\]")
_GROUPS_BRACED_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}


def _shape_bytes(shapes_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shapes_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d.strip():
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))  # [num_groups, group_size]<=[N]
    m = _GROUPS_BRACED_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return default


@dataclasses.dataclass
class CollectiveStats:
    # per-device bytes by kind: result bytes and ring wire bytes
    result_bytes: dict
    wire_bytes: dict
    counts: dict

    @property
    def total_wire_bytes(self) -> float:
        return sum(self.wire_bytes.values())

    @property
    def total_count(self) -> int:
        return sum(self.counts.values())


def parse_collectives(hlo_text: str, num_devices: int) -> CollectiveStats:
    result_bytes = {k: 0.0 for k in _COLL_KINDS}
    wire = {k: 0.0 for k in _COLL_KINDS}
    counts = {k: 0 for k in _COLL_KINDS}
    for line in hlo_text.splitlines():
        m = _INSTR_RE.search(line)
        if not m:
            continue
        shapes_str, kind, startdone = m.group(1), m.group(2), m.group(3)
        if startdone == "-done":
            continue  # counted at -start
        size = _shape_bytes(shapes_str)
        g = _group_size(line, num_devices)
        if g <= 1:
            continue
        # Ring-algorithm wire bytes per participating device. HLO shapes
        # are already per-device (SPMD-partitioned).
        if kind == "all-reduce":
            w = 2.0 * size * (g - 1) / g
        elif kind == "all-gather":
            w = size * (g - 1) / g            # size = gathered result
        elif kind == "reduce-scatter":
            w = size * (g - 1)                # size = scattered result shard
        elif kind == "all-to-all":
            w = size * (g - 1) / g
        else:  # collective-permute
            w = size
        result_bytes[kind] += size
        wire[kind] += w
        counts[kind] += 1
    return CollectiveStats(result_bytes, wire, counts)


@dataclasses.dataclass
class CellCost:
    """Per-device cost of one compiled step."""
    flops: float
    bytes_accessed: float
    wire_bytes: float
    collective_counts: dict

    def __sub__(self, other: "CellCost") -> "CellCost":
        return CellCost(
            self.flops - other.flops,
            self.bytes_accessed - other.bytes_accessed,
            self.wire_bytes - other.wire_bytes,
            {k: self.collective_counts.get(k, 0) - other.collective_counts.get(k, 0)
             for k in set(self.collective_counts) | set(other.collective_counts)})

    def scaled(self, f: float) -> "CellCost":
        return CellCost(self.flops * f, self.bytes_accessed * f,
                        self.wire_bytes * f,
                        {k: v * f for k, v in self.collective_counts.items()})

    def __add__(self, other: "CellCost") -> "CellCost":
        return CellCost(
            self.flops + other.flops,
            self.bytes_accessed + other.bytes_accessed,
            self.wire_bytes + other.wire_bytes,
            {k: self.collective_counts.get(k, 0) + other.collective_counts.get(k, 0)
             for k in set(self.collective_counts) | set(other.collective_counts)})


def cost_from_compiled(compiled, num_devices: int) -> CellCost:
    ca = compiled.cost_analysis()
    coll = parse_collectives(compiled.as_text(), num_devices)
    return CellCost(
        flops=float(ca.get("flops", 0.0)),
        bytes_accessed=float(ca.get("bytes accessed", 0.0)),
        wire_bytes=coll.total_wire_bytes,
        collective_counts=coll.counts)


def extrapolate(probe1: CellCost, probe2: CellCost, num_superblocks: float,
                micro_scale: float = 1.0) -> CellCost:
    """Depth extrapolation: per-superblock = probe2 - probe1 (probes are
    compiled with 1 and 2 unrolled superblocks and one microbatch);
    total = base + num_superblocks·per_sb, with the per-microbatch portion
    of the *base* FLOPs/bytes also scaled by ``micro_scale`` (the embedding
    + head compute runs once per microbatch, collectives for grads once per
    step — we approximate by scaling everything except the gradient
    all-reduce uniformly; exact for micro_scale=1)."""
    per_sb = probe2 - probe1
    base = probe1 - per_sb
    total = base.scaled(micro_scale) + per_sb.scaled(num_superblocks * micro_scale)
    # Gradient/optimizer collectives in `base` already happen once per step;
    # scaling them by micro_scale over-counts, but micro_scale corrections
    # only matter for FLOPs-dominated terms. Recorded as methodology note.
    return total


@dataclasses.dataclass
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops: float
    hlo_flops: float

    @property
    def bound(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> float:
        return self.model_flops / self.hlo_flops if self.hlo_flops else 0.0

    @property
    def mfu(self) -> float:
        """MODEL_FLOPS / (chips × peak × step_time) — roofline-model MFU."""
        if self.step_s <= 0:
            return 0.0
        return (self.model_flops / hw.PEAK_FLOPS_BF16) / self.step_s


def roofline_from_cost(cost: CellCost, model_flops_per_device: float) -> Roofline:
    return Roofline(
        compute_s=cost.flops / hw.PEAK_FLOPS_BF16,
        memory_s=cost.bytes_accessed / hw.HBM_BW,
        collective_s=cost.wire_bytes / hw.ICI_LINK_BW,
        model_flops=model_flops_per_device,
        hlo_flops=cost.flops)
