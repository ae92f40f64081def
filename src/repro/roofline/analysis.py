"""Roofline-term derivation from compiled dry-run artifacts.

Three terms per (arch × shape × mesh), in seconds (EXPERIMENTS.md §Roofline):

    compute    = HLO_FLOPs / (chips × peak_FLOPs)
    memory     = HLO_bytes / (chips × HBM_bw)
    collective = collective_bytes / (chips × link_bw)

``cost_analysis`` supplies FLOPs and bytes; collective bytes are parsed
from the optimized HLO text (result-shape bytes of every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute op, summed;
ops inside loops/scans are counted once per trip via the enclosing
while-loop trip count when it is statically printed — otherwise once,
recorded as a lower bound).
"""
from __future__ import annotations

import dataclasses
import re


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops: float             # bf16 FLOP/s
    hbm_bw: float            # HBM bytes/s
    link_bw: float           # bytes/s per ICI link


# Published per-chip peaks keyed by ``jax.Device.device_kind``. Source:
# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM
# at 819 GB/s, 1,600 Gbit/s of interchip interconnect over 4 links
# (50 GB/s each).
PEAKS = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, link_bw=50e9),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """The peaks of ``device_kind``; a kind without published peaks in
    :data:`PEAKS` is an error, never another chip's numbers."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add "
            f"them to PEAKS with their source (known: {sorted(PEAKS)})"
        ) from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Total bytes of an HLO shape string like 'bf16[256,7168]' or a tuple
    '(f32[8,128], u32[8])'."""
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


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum result-shape bytes per collective kind from optimized HLO."""
    out = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*((?:\([^)]*\))|(?:\w+\[[0-9,]*\]"
                     r"(?:\{[^}]*\})?))\s+([\w\-]+)", line)
        if not m:
            continue
        op = m.group(2)
        for kind in _COLLECTIVES:
            if op == kind or op.startswith(kind + "-start"):
                out[kind] += _shape_bytes(m.group(1))
                out["count"] += 1
                break
    return out


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    device_kind: str         # key of PEAKS: the chip the terms assume
    hlo_flops: float
    hlo_bytes: float
    coll_bytes: float
    coll_counts: dict
    model_flops: float | None = None
    mem_per_device: float | None = None
    # operand+result bytes of custom-call instructions (Pallas kernels —
    # for the HBM-paged refine variant this is the kernel's bytes-moved
    # attribution, an upper bound on its chunk DMA traffic)
    custom_call_bytes: float = 0.0
    custom_call_count: int = 0

    @property
    def t_compute(self) -> float:
        return self.hlo_flops / chip_peaks(self.device_kind).flops

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / chip_peaks(self.device_kind).hbm_bw

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / chip_peaks(self.device_kind).link_bw

    @property
    def bottleneck(self) -> str:
        ts = {"compute": self.t_compute, "memory": self.t_memory,
              "collective": self.t_collective}
        return max(ts, key=ts.get)

    @property
    def useful_flops_frac(self) -> float | None:
        if not self.model_flops or not self.hlo_flops:
            return None
        return self.model_flops / (self.hlo_flops * self.chips)

    def to_dict(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips, "device_kind": self.device_kind,
            "hlo_flops_per_device": self.hlo_flops,
            "hlo_bytes_per_device": self.hlo_bytes,
            "coll_bytes_per_device": self.coll_bytes,
            "coll_counts": self.coll_counts,
            "custom_call_bytes_per_device": self.custom_call_bytes,
            "custom_call_count": self.custom_call_count,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "model_flops": self.model_flops,
            "useful_flops_frac": self.useful_flops_frac,
            "mem_per_device_bytes": self.mem_per_device,
        }


def analyze(arch: str, shape: str, mesh_name: str, chips: int,
            compiled, device_kind: str,
            model_flops: float | None = None) -> Roofline:
    chip_peaks(device_kind)          # an unknown kind fails here
    from .hlo_cost import analyze_hlo_text
    hlo = compiled.as_text()
    hc = analyze_hlo_text(hlo)       # loop-aware (scan bodies x trip count)
    flops = float(hc.flops)
    byts = float(hc.bytes)
    total_coll = float(hc.coll_bytes)
    coll = {k: float(v) for k, v in hc.coll_by_kind.items()}
    coll["unresolved_loops"] = hc.unresolved_loops
    # XLA's own (loop-undercounting) numbers kept for reference
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
        coll["xla_flops_ref"] = float(cost.get("flops", 0.0))
    except Exception:
        pass
    mem = None
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            mem = (getattr(ma, "argument_size_in_bytes", 0)
                   + getattr(ma, "output_size_in_bytes", 0)
                   + getattr(ma, "temp_size_in_bytes", 0)
                   - getattr(ma, "alias_size_in_bytes", 0))
    except Exception:
        pass
    return Roofline(arch=arch, shape=shape, mesh=mesh_name, chips=chips,
                    device_kind=device_kind, hlo_flops=flops, hlo_bytes=byts,
                    coll_bytes=total_coll, coll_counts=coll,
                    model_flops=model_flops, mem_per_device=mem,
                    custom_call_bytes=float(hc.custom_call_bytes),
                    custom_call_count=int(hc.custom_call_count))
