"""Roofline-term derivation from a compiled dry-run artifact.

    compute term    = HLO_FLOPs / (chips · peak_FLOP/s)
    memory term     = HLO_bytes / (chips · HBM_bw)
    collective term = per-device collective wire bytes / link_bw

cost_analysis() supplies FLOPs / bytes for the whole SPMD program
(per-device program × all devices on CPU-backend dry-runs is per-module;
we normalize to per-chip). Collective bytes are NOT in cost_analysis —
we parse the post-SPMD HLO text and sum wire bytes per op with the usual
ring conventions:

    all-gather          output bytes            (each chip receives ~out)
    reduce-scatter      operand bytes           (each chip sends ~in)
    all-reduce          2 × operand bytes       (RS + AG ring)
    all-to-all          operand bytes
    collective-permute  operand bytes

Post-SPMD HLO shapes are per-device, so the sums are already per-chip wire
traffic; the collective term divides by the chip's interconnect
bandwidth only. The peaks come from the PEAKS table, keyed by the device
kind JAX reports.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, NamedTuple


class Peaks(NamedTuple):
    flops: float      # dense bf16 FLOP/s per chip
    hbm_bw: float     # HBM bytes/s per chip
    ici_bw: float     # chip-to-chip interconnect bytes/s per chip
    source: str


#: published per-chip peaks, keyed by `jax.devices()[0].device_kind`
PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(
        flops=197e12, hbm_bw=819e9, ici_bw=1600e9 / 8,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip "
               "interconnect"),
}


def peaks_for(device_kind: str) -> Peaks:
    """The peak table row of a device kind; an unknown kind is an error,
    never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_COLL_RE = re.compile(
    r"=\s*(?:\([^)]*\)|\S+)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")

_WIRE_FACTOR = {"all-gather": ("out", 1.0), "all-reduce": ("in", 2.0),
                "reduce-scatter": ("in", 1.0), "all-to-all": ("in", 1.0),
                "collective-permute": ("in", 1.0)}


def _shape_bytes(tok_type: str, dims: str) -> int:
    if tok_type not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[tok_type]


def parse_collectives(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Per collective kind: op count, operand bytes, output bytes, wire
    bytes (per-device)."""
    out: Dict[str, Dict[str, float]] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        kind = m.group(1)
        if "-done(" in line:
            continue  # bytes counted on the -start (or sync) op
        eq = line.index("=")
        paren = line.index("(", m.end(1) - 1)
        out_shapes = _SHAPE_RE.findall(line[:paren][eq:])
        in_shapes = _SHAPE_RE.findall(line[paren:])
        out_b = sum(_shape_bytes(t, d) for t, d in out_shapes)
        in_b = sum(_shape_bytes(t, d) for t, d in in_shapes)
        if in_b == 0:
            # post-optimization HLO often elides operand types
            # (`collective-permute(%copy.27)`); in ≈ out for permute /
            # all-to-all / all-reduce, and a lower bound for reduce-scatter
            in_b = out_b
        src, f = _WIRE_FACTOR[kind]
        wire = f * (out_b if src == "out" else in_b)
        d = out.setdefault(kind, {"count": 0, "operand_bytes": 0,
                                  "output_bytes": 0, "wire_bytes": 0})
        d["count"] += 1
        d["operand_bytes"] += in_b
        d["output_bytes"] += out_b
        d["wire_bytes"] += wire
    return out


@dataclasses.dataclass
class Roofline:
    """All byte/FLOP fields are PER-DEVICE: jax's compiled.cost_analysis()
    reports the post-SPMD per-device module (verified empirically: a
    sharded 1024³ matmul on 8 devices reports total/8 FLOPs). The brief's
    `HLO_FLOPs/(chips·peak)` with whole-program FLOPs equals
    `per_device_FLOPs/peak`, which is what these terms compute."""

    flops: float               # per-device HLO FLOPs
    hbm_bytes: float           # per-device bytes accessed
    wire_bytes: float          # per-device collective wire bytes
    chips: int
    model_flops: float         # 6·N·D analytic, whole model
    collectives: Dict[str, Dict[str, float]]
    # wire-codec model: the HLO above is lowered with exchange="exact";
    # a codec shrinks only the wire term (HBM cost of encode/decode is
    # noise next to the plane pass). wire.payload_nbytes(codec)/exact
    # gives the ratio to plug in here (e.g. fp16 ≈ 0.5, q8ef ≈ 0.3).
    wire_codec_ratio: float = 1.0
    # overlap model: the double-buffered schedules hide the exchange
    # behind the bucket plane passes, so the step is max(local, wire)
    # instead of local + wire. See step_s.
    overlap: bool = True
    device_kind: str = "TPU v5 lite"

    @property
    def peaks(self) -> Peaks:
        return peaks_for(self.device_kind)

    @property
    def compute_s(self) -> float:
        return self.flops / self.peaks.flops

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes / self.peaks.hbm_bw

    @property
    def collective_s(self) -> float:
        return self.wire_bytes * self.wire_codec_ratio / self.peaks.ici_bw

    @property
    def step_s(self) -> float:
        """Modeled per-step wall time. With overlap (the double-buffered
        schedules) the exchange hides behind compute: max of the terms.
        Without it the collective serializes after the local phase:
        max(compute, memory) + collective."""
        local = max(self.compute_s, self.memory_s)
        if self.overlap:
            return max(local, self.collective_s)
        return local + self.collective_s

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_compute_ratio(self) -> float:
        per_dev_model = self.model_flops / self.chips
        return per_dev_model / self.flops if self.flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """useful-FLOPs time / achievable step time — the MFU-style score
        the perf loop drives up. Step time is `step_s`: max(local, wire)
        under the overlapped schedules (the default), local + wire
        otherwise — both variants are reported in EXPERIMENTS."""
        t = self.step_s
        return ((self.model_flops / (self.chips * self.peaks.flops)) / t
                if t else 0.0)

    def to_dict(self) -> dict:
        return {
            "flops": self.flops, "hbm_bytes": self.hbm_bytes,
            "wire_bytes_per_chip": self.wire_bytes, "chips": self.chips,
            "model_flops": self.model_flops,
            "device_kind": self.device_kind,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "wire_codec_ratio": self.wire_codec_ratio,
            "overlap": self.overlap,
            "step_s": self.step_s,
            "bottleneck": self.bottleneck,
            "useful_compute_ratio": self.useful_compute_ratio,
            "roofline_fraction": self.roofline_fraction,
            "collectives": self.collectives,
        }


def analyze(compiled, chips: int, model_flops: float,
            wire_codec_ratio: float = 1.0, overlap: bool = True,
            device_kind: str = "TPU v5 lite") -> Roofline:
    cost = compiled.cost_analysis()
    if isinstance(cost, list):  # older jax returns [dict]
        cost = cost[0]
    flops = float(cost.get("flops", 0.0))
    hbm = float(cost.get("bytes accessed", 0.0))
    colls = parse_collectives(compiled.as_text())
    wire = sum(d["wire_bytes"] for d in colls.values())
    return Roofline(flops=flops, hbm_bytes=hbm, wire_bytes=wire, chips=chips,
                    model_flops=model_flops, collectives=colls,
                    wire_codec_ratio=wire_codec_ratio, overlap=overlap,
                    device_kind=device_kind)
