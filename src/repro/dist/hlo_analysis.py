"""Roofline-term extraction from compiled XLA programs.

Three per-chip cost terms bound a step:

* compute     — FLOPs / peak FLOPs
* memory      — HBM bytes accessed / HBM bandwidth
* collective  — wire bytes moved by collectives / interconnect bandwidth

FLOPs and HBM bytes come from ``compiled.cost_analysis()``; collective wire
bytes are parsed from the optimized HLO text, using the standard ring-
algorithm conventions (per-chip bytes on the wire, group size g):

    all-gather          result_bytes * (g-1)/g
    reduce-scatter      result_bytes * (g-1)     (result is the shard)
    all-reduce          result_bytes * 2(g-1)/g  (RS + AG phases)
    all-to-all          result_bytes * (g-1)/g
    collective-permute  result_bytes

Async pairs are counted once on the ``-start`` op (whose result is a tuple;
the transferred operand is its last element); ``-done`` ops and operand
mentions of collective instruction names never match.

The per-chip peaks that turn those terms into seconds come from ``PEAKS``,
keyed by the ``device_kind`` JAX reports; a kind missing from the table is
an error, never a default.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field


@dataclass(frozen=True)
class ChipPeaks:
    flops: float       # bf16 FLOP/s
    hbm_bw: float      # HBM bytes/s
    ici_bw: float      # chip-to-chip interconnect bytes/s


# Published per-chip peaks. "TPU v5 lite" is TPU v5e (Google Cloud
# documentation, "TPU v5e"): 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s
# chip-to-chip interconnect.
PEAKS: dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, ici_bw=1600e9 / 8),
}


def peaks_for(device_kind: str) -> ChipPeaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "collective-permute",
    "all-to-all",
)

# `%name = <type> <op>(` — the op position after `=` only, so operand
# references (e.g. a tuple() consuming %all-gather.6) never match.
_OP_RE = re.compile(
    r"=\s+(?P<ty>\([^)]*\)|\S+)\s+"
    r"(?P<op>" + "|".join(_COLLECTIVES) + r")(?P<start>-start)?\("
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_IOTA_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[\d+\]")
_LIST_GROUPS_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")


def _shape_bytes(ty: str) -> int:
    """Bytes of an HLO result type; for tuples, the last element (the
    completed transfer of an async -start pair)."""
    matches = _SHAPE_RE.findall(ty)
    if not matches:
        return 0
    dtype, dims = matches[-1]
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _group_size(line: str) -> int:
    m = _IOTA_GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _LIST_GROUPS_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 1


def _wire_bytes(op: str, result_bytes: int, g: int) -> int:
    if op == "all-reduce":
        return result_bytes * 2 * (g - 1) // g
    if op == "reduce-scatter":
        return result_bytes * (g - 1)
    if op == "collective-permute":
        return result_bytes
    # all-gather / all-to-all
    return result_bytes * (g - 1) // g


@dataclass
class CollectiveStats:
    count: int = 0
    by_op: dict = field(default_factory=dict)
    schedule: list = field(default_factory=list)  # [(op, wire_bytes), ...]

    @property
    def total_bytes(self) -> int:
        return sum(self.by_op.values())


def collective_stats(hlo_text: str) -> CollectiveStats:
    """Parse per-chip collective wire bytes out of optimized HLO text."""
    st = CollectiveStats()
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if m is None:
            continue
        op = m.group("op")
        b = _wire_bytes(op, _shape_bytes(m.group("ty")), _group_size(line))
        st.count += 1
        st.by_op[op] = st.by_op.get(op, 0) + b
        st.schedule.append((op, b))
    return st


def cost_analysis_terms(compiled) -> tuple[float, float]:
    """(flops, hbm_bytes) per chip from an XLA compiled executable."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if ca is None:
        return 0.0, 0.0
    return float(ca.get("flops", 0.0)), float(ca.get("bytes accessed", 0.0))


@dataclass
class Roofline:
    """Per-chip roofline: which term bounds the step and by how much."""

    flops: float
    hbm_bytes: float
    coll_bytes: float
    peaks: ChipPeaks
    model_flops: float = 0.0  # useful (model-math) FLOPs, for MFU

    @property
    def compute_s(self) -> float:
        return self.flops / self.peaks.flops

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes / self.peaks.hbm_bw

    @property
    def collective_s(self) -> float:
        return self.coll_bytes / self.peaks.ici_bw

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_frac(self) -> float:
        return self.model_flops / self.flops if self.flops else 0.0

    @property
    def roofline_frac(self) -> float:
        """MFU upper bound: useful-compute time / roofline-bound time."""
        if not self.bound_s:
            return 0.0
        return (self.model_flops / self.peaks.flops) / self.bound_s

    def to_json(self) -> dict:
        return {
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "coll_bytes": self.coll_bytes,
            "model_flops": self.model_flops,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bound_s": self.bound_s,
            "dominant": self.dominant,
            "useful_flops_frac": self.useful_flops_frac,
            "roofline_frac": self.roofline_frac,
        }
