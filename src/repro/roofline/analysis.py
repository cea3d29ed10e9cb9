"""Roofline analysis from compiled XLA artifacts (no hardware needed).

Terms (per device, seconds), against the peaks of the target device:
    compute    = HLO_FLOPs / peak_FLOPs
    memory     = HLO_bytes_accessed / HBM_bw
    collective = collective_operand_bytes / ICI_bw (per link)

``cost_analysis()`` reports per-device FLOPs/bytes for SPMD executables
(verified empirically — a (64,128)x(128,256) matmul over 8 devices reports
~matmul_flops/8).  Collective bytes are NOT in cost_analysis: we parse the
post-partitioning HLO and sum *operand* sizes of every collective op,
deriving operand size from the printed output shape and the replica-group
size where they differ (all-gather, reduce-scatter).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any



@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Per-chip peak rates of one device kind."""

    flops: float      # bf16 MXU FLOP/s
    vpu_flops: float  # vector-unit (elementwise) FLOP/s
    hbm_bw: float     # HBM bytes/s
    ici_bw: float     # chip-to-chip bytes/s per link


# Keyed by ``jax.Device.device_kind``.  TPU v5e (Google Cloud documentation,
# "TPU v5e"): 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s of ICI over four
# links (50 GB/s each).  The vector-unit rate is not published; it is
# derived from the VPU's 8x128x4 ALUs at 0.94 GHz.
PEAKS = {
    "TPU v5 lite": DevicePeaks(flops=197e12, vpu_flops=4e12, hbm_bw=819e9,
                               ici_bw=50e9),
}


def peaks_for(device_kind: str) -> DevicePeaks:
    """The peak table's entry for ``device_kind``; an unknown kind raises
    rather than borrowing another chip's numbers."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"=\s*(\(?[^=]*?\)?)\s+"
    r"(all-gather-start|all-gather|all-reduce-start|all-reduce|"
    r"reduce-scatter|all-to-all|collective-permute-start|collective-permute)"
    r"(\.\d+)?\(")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(shape_str: str) -> int:
    """Total bytes of possibly-tuple shape string like '(bf16[8,4], u32[])'."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))  # [num_groups, group_size]<=[N]
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 1


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_kind: dict[str, int]
    count_by_kind: dict[str, int]

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Sum per-device *operand* bytes of every collective in the HLO."""
    bytes_by: dict[str, int] = {}
    count_by: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        out_shape, kind = m.group(1), m.group(2)
        kind = kind.replace("-start", "")
        out_bytes = _shape_bytes(out_shape)
        g = _group_size(line)
        if kind == "all-gather":
            operand = out_bytes // max(g, 1)   # output is g x operand
        elif kind == "reduce-scatter":
            operand = out_bytes * max(g, 1)    # operand is g x output
        else:  # all-reduce, all-to-all, collective-permute: operand == output
            operand = out_bytes
        bytes_by[kind] = bytes_by.get(kind, 0) + operand
        count_by[kind] = count_by.get(kind, 0) + 1
    return CollectiveStats(bytes_by, count_by)


@dataclasses.dataclass
class Roofline:
    """Per-device roofline terms.  FLOPs/collective bytes come from the
    trip-count-aware HLO walker (repro.roofline.hlo_cost) — XLA's own
    cost_analysis counts while-loop bodies once and is kept only as
    ``xla_raw_*`` for reference.  Memory traffic is max(dot stream bytes,
    live-buffer traffic): the former models weight/activation streaming
    through fused matmuls, the latter models params+opt read/write and
    remat-stash traffic (argument + output + 2*temp)."""

    peaks: DevicePeaks
    dot_flops: float
    ew_flops: float
    dot_bytes: float
    buffer_bytes: float
    collective_bytes_per_device: float
    collective_breakdown: dict[str, int]
    collective_counts: dict[str, int]
    xla_raw_flops: float = 0.0
    xla_raw_bytes: float = 0.0

    @property
    def flops_per_device(self) -> float:
        return self.dot_flops + self.ew_flops

    @property
    def bytes_per_device(self) -> float:
        return max(self.dot_bytes, self.buffer_bytes)

    @property
    def compute_s(self) -> float:
        # MXU for dots, VPU for elementwise — SSM/recurrent archs are
        # elementwise-heavy and would look free at MXU speed
        return (self.dot_flops / self.peaks.flops
                + self.ew_flops / self.peaks.vpu_flops)

    @property
    def memory_s(self) -> float:
        return self.bytes_per_device / self.peaks.hbm_bw

    @property
    def collective_s(self) -> float:
        return self.collective_bytes_per_device / self.peaks.ici_bw

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        """Step time lower bound assuming perfect overlap: max of terms."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def compute_fraction(self) -> float:
        """Roofline fraction: useful-compute time / achievable step time."""
        return self.compute_s / max(self.bound_s, 1e-30)

    def to_dict(self) -> dict[str, Any]:
        return {
            "dot_flops": self.dot_flops,
            "ew_flops": self.ew_flops,
            "flops_per_device": self.flops_per_device,
            "dot_bytes": self.dot_bytes,
            "buffer_bytes": self.buffer_bytes,
            "bytes_per_device": self.bytes_per_device,
            "collective_bytes_per_device": self.collective_bytes_per_device,
            "collective_breakdown": self.collective_breakdown,
            "collective_counts": self.collective_counts,
            "xla_raw_flops": self.xla_raw_flops,
            "xla_raw_bytes": self.xla_raw_bytes,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "bound_s": self.bound_s,
            "compute_fraction": self.compute_fraction,
        }


def analyze_compiled(compiled, device_kind: str) -> Roofline:
    """Roofline of a compiled program against ``device_kind``'s peaks."""
    from repro.roofline.hlo_cost import hlo_cost
    peaks = peaks_for(device_kind)
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    cost = hlo_cost(hlo)
    mem = memory_summary(compiled)
    buffer_bytes = (mem.get("argument_size_in_bytes", 0.0)
                    + mem.get("output_size_in_bytes", 0.0)
                    + 2.0 * mem.get("temp_size_in_bytes", 0.0))
    return Roofline(
        peaks=peaks,
        dot_flops=cost.dot_flops,
        ew_flops=cost.ew_flops,
        dot_bytes=cost.dot_bytes,
        buffer_bytes=buffer_bytes,
        collective_bytes_per_device=float(cost.collective_bytes),
        collective_breakdown=dict(cost.coll_bytes),
        collective_counts=dict(cost.coll_counts),
        xla_raw_flops=float(ca.get("flops", 0.0)),
        xla_raw_bytes=float(ca.get("bytes accessed", 0.0)),
    )


def memory_summary(compiled) -> dict[str, float]:
    ma = compiled.memory_analysis()
    fields = ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes")
    out = {f: float(getattr(ma, f, 0.0)) for f in fields}
    out["total_hbm_bytes"] = (out["argument_size_in_bytes"]
                              + out["temp_size_in_bytes"]
                              + out["output_size_in_bytes"]
                              - out["alias_size_in_bytes"])
    return out
