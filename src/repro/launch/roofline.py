"""Roofline-term derivation from compiled dry-run artifacts (deliverable g).

Per (arch x shape x mesh):

    compute    = HLO_FLOPs / (chips * peak bf16 FLOP/s)
    memory     = HLO_bytes / (chips * HBM bytes/s)
    collective = collective_bytes / (chips * ICI bytes/s per link)

``cost_analysis()`` provides FLOPs / bytes (whole-program totals across
devices on recent JAX; detected and normalized).  Collective bytes are NOT in
cost_analysis — we parse the compiled SPMD HLO and sum the result-shape bytes
of every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute.  HLO shapes are per-device; multiplying by chip count
gives the global volume the formulas above divide back down.

Hardware peaks live in one table keyed by ``device_kind`` (as JAX reports
it); a kind missing from the table is an error, never a default.
"""

from __future__ import annotations

import dataclasses
import re


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    flops_bf16: float       # FLOP/s per chip
    hbm_bw: float           # HBM bytes/s per chip
    hbm_bytes: float        # HBM capacity per chip
    ici_bw: float           # interconnect bytes/s per link


# Source: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 16 GB
# HBM at 819 GB/s, 1,600 Gbit/s inter-chip interconnect over 4 links.
PEAKS: dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(flops_bf16=197e12, hbm_bw=819e9, hbm_bytes=16e9,
                               ici_bw=1600e9 / 8 / 4),
}


def peaks(device_kind: str) -> DevicePeaks:
    """The published peaks of ``device_kind``; unknown kinds raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_COLLECTIVE_RE = re.compile(
    r"=\s*((?:\([^)]*\)|\S+))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|f8e4m3fn|f8e5m2|s64|u64|s32|u32|s16|u16|s8|u8|pred)\[([0-9,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes_from_hlo(hlo_text: str) -> dict[str, int]:
    """Per-device bytes by collective kind, from compiled SPMD HLO text."""
    out: dict[str, int] = {}
    for m in _COLLECTIVE_RE.finditer(hlo_text):
        result_type, kind = m.group(1), m.group(2)
        out[kind] = out.get(kind, 0) + _shape_bytes(result_type)
    return out


@dataclasses.dataclass
class RooflineTerms:
    arch: str
    cell: str
    mesh: str
    chips: int
    hlo_flops: float            # global FLOPs
    hlo_bytes: float            # global HBM bytes accessed
    collective_bytes: float     # global collective bytes
    model_flops: float          # analytic (6ND etc.)
    device_kind: str            # the chip whose peaks the terms use
    compute_s: float = 0.0
    memory_s: float = 0.0
    collective_s: float = 0.0

    def __post_init__(self):
        p = peaks(self.device_kind)
        self.compute_s = self.hlo_flops / (self.chips * p.flops_bf16)
        self.memory_s = self.hlo_bytes / (self.chips * p.hbm_bw)
        self.collective_s = self.collective_bytes / (self.chips * p.ici_bw)

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flop_fraction(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs — catches remat/redundancy waste."""
        return self.model_flops / max(self.hlo_flops, 1.0)

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the compute roofline the dominant term allows:
        (model-flops time at peak) / (time the dominant term costs)."""
        ideal = self.model_flops / (self.chips * peaks(self.device_kind).flops_bf16)
        return ideal / max(self.bound_s, 1e-30)

    def to_json(self) -> dict:
        return {
            "arch": self.arch, "cell": self.cell, "mesh": self.mesh,
            "chips": self.chips, "device_kind": self.device_kind,
            "hlo_flops": self.hlo_flops, "hlo_bytes": self.hlo_bytes,
            "collective_bytes": self.collective_bytes,
            "model_flops": self.model_flops,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "useful_flop_fraction": self.useful_flop_fraction,
            "roofline_fraction": self.roofline_fraction,
        }


def derive_terms(
    arch_id: str,
    cell_name: str,
    mesh_name: str,
    chips: int,
    cost: dict,
    hlo_text: str,
    model_flops: float,
    device_kind: str,
    flops_are_global: bool = True,
) -> RooflineTerms:
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    if not flops_are_global:
        flops *= chips
        byts *= chips
    coll = collective_bytes_from_hlo(hlo_text)
    coll_global = float(sum(coll.values())) * chips
    return RooflineTerms(
        arch=arch_id, cell=cell_name, mesh=mesh_name, chips=chips,
        hlo_flops=flops, hlo_bytes=byts, collective_bytes=coll_global,
        model_flops=model_flops, device_kind=device_kind,
    )
