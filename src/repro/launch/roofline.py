"""Roofline-term extraction from compiled dry-run artifacts.

Terms per (arch x shape x mesh), all in seconds:

  compute    = per-device HLO flops / (197 TFLOP/s bf16)
  memory     = per-device HLO bytes / (819 GB/s HBM)
  collective = per-device collective bytes / (50 GB/s ICI link)

XLA's ``compiled.cost_analysis()`` is *per partitioned device* (verified
empirically), so no further division by chip count. Collective bytes are
not in cost_analysis: we parse the post-SPMD HLO text and sum the result
shapes of all-reduce / all-gather / reduce-scatter / all-to-all /
collective-permute ops (per-device shard shapes — i.e. bytes that hit
this chip's links; the single-link divisor is conservative).
"""
from __future__ import annotations

import dataclasses
import re

from ..core.engine import TPU_V5E_BF16_FLOPS, TPU_V5E_HBM_BW, TPU_V5E_ICI_BW

DTYPE_BYTES = {
    "pred": 1,
    "s8": 1,
    "u8": 1,
    "s16": 2,
    "u16": 2,
    "bf16": 2,
    "f16": 2,
    "s32": 4,
    "u32": 4,
    "f32": 4,
    "s64": 8,
    "u64": 8,
    "f64": 8,
    "c64": 8,
    "c128": 16,
}

COLLECTIVE_OPS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"(pred|bf16|f16|f32|f64|s8|u8|s16|u16|s32|u32|s64|u64|c64|c128)\[([0-9,]*)\]")


def _shape_bytes(type_str: str) -> float:
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(type_str):
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def _computation_blocks(hlo_text: str):
    """Split HLO text into {computation_name: [lines]}."""
    blocks: dict[str, list[str]] = {}
    cur = None
    for line in hlo_text.splitlines():
        # computation headers: "%name (args...) -> type {"  (args may nest parens)
        m = re.match(r"\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$", line)
        if m:
            cur = m.group(1)
            blocks[cur] = []
        elif cur is not None:
            blocks[cur].append(line)
    return blocks


def _loop_multipliers(hlo_text: str) -> dict[str, float]:
    """computation -> product of enclosing while-loop trip counts.

    XLA annotates ``backend_config={"known_trip_count":{"n":...}}`` on
    while ops; multipliers propagate from the entry computation into loop
    bodies and everything they call (fusions, remat bodies, nested loops)."""
    blocks = _computation_blocks(hlo_text)
    call_re = re.compile(r"(?:body|condition|to_apply|calls)=%?([\w.\-]+)")
    trip_re = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
    edges: dict[str, list[tuple[str, float]]] = {c: [] for c in blocks}
    for caller, lines in blocks.items():
        for line in lines:
            trips = trip_re.search(line)
            is_while = " while(" in line or "= while(" in line
            weight = float(trips.group(1)) if (is_while and trips) else 1.0
            for callee in call_re.findall(line):
                if callee in blocks:
                    edges[caller].append((callee, weight))
    referenced = {c for outs in edges.values() for c, _ in outs}
    roots = [c for c in blocks if c not in referenced]
    mult: dict[str, float] = {}

    def visit(c, m, depth=0):
        if depth > 32 or mult.get(c, 0.0) >= m:
            return
        mult[c] = m
        for callee, w in edges.get(c, []):
            visit(callee, m * w, depth + 1)

    for r in roots:
        visit(r, 1.0)
    return mult


def parse_collective_bytes(hlo_text: str) -> dict[str, float]:
    """Sum result-shape bytes per collective kind from (post-SPMD) HLO,
    weighting each op by the product of its enclosing while-loop trip
    counts — so per-microbatch / per-layer-scan collectives count once
    per iteration, not once per program text."""
    out = {k: 0.0 for k in COLLECTIVE_OPS}
    counts = {k: 0 for k in COLLECTIVE_OPS}
    mult = _loop_multipliers(hlo_text)
    blocks = _computation_blocks(hlo_text)
    for comp, lines in blocks.items():
        m_comp = mult.get(comp, 1.0)
        for line in lines:
            s = line.strip()
            m = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.+?)\s+([a-z\-]+)(?:-start|-done)?\(", s)
            if not m:
                continue
            type_str, op = m.groups()
            if op in COLLECTIVE_OPS:
                if "-done(" in s:  # async pairs: count the -start only
                    continue
                out[op] += _shape_bytes(type_str) * m_comp
                counts[op] += 1
    out["counts"] = counts  # type: ignore
    return out


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    n_chips: int
    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float
    collective_counts: dict
    t_compute: float
    t_memory: float
    t_collective: float
    model_flops: float  # 6ND (train) / 2·N_active·tokens (decode), global
    hlo_flops_global: float
    memory_per_device: dict
    loop_correction: float = 1.0
    hlo_flops_raw: float = 0.0
    bytes_upper_bound: float = 0.0

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory, "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def t_total(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_ratio(self) -> float:
        return self.model_flops / self.hlo_flops_global if self.hlo_flops_global else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the dominant-term floor that is useful model compute:
        (model_flops / chips / peak) / t_total."""
        ideal = self.model_flops / self.n_chips / TPU_V5E_BF16_FLOPS
        return ideal / self.t_total if self.t_total else 0.0

    def row(self) -> dict:
        return {
            "arch": self.arch,
            "shape": self.shape,
            "mesh": self.mesh,
            "chips": self.n_chips,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "model_flops": self.model_flops,
            "hlo_flops_global": self.hlo_flops_global,
            "useful_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
            "collective_counts": self.collective_counts,
            "memory_per_device": self.memory_per_device,
            "loop_correction": self.loop_correction,
            "hlo_flops_raw_per_device": self.hlo_flops_raw,
            "t_memory_upper_s": self.bytes_upper_bound / TPU_V5E_HBM_BW,
        }


def analyze(arch, shape, mesh_name, n_chips, compiled, model_flops, analytic_total=None, analytic_bytes_dev=None) -> Roofline:
    """``analytic_total`` (global executed flops from launch.analytic) powers
    the compute term; XLA under-counts while-loop bodies inconsistently on
    this backend, so the measured HLO flops only *calibrate* a loop
    correction factor that re-scales the byte / collective terms (the same
    loops hold those bytes)."""

    ca = compiled.cost_analysis()
    hlo_flops = float(ca.get("flops", 0.0))
    if analytic_total is None:
        analytic_total = hlo_flops * n_chips
    correction = max(1.0, (analytic_total / n_chips) / hlo_flops) if hlo_flops else 1.0
    flops = analytic_total / n_chips
    bytes_hlo = float(ca.get("bytes accessed", 0.0)) * correction
    # the loop-corrected HLO byte count is a (loose, CPU-backend-inflated)
    # upper bound; the analytic streaming model is the floor we report.
    bytes_ = analytic_bytes_dev if analytic_bytes_dev is not None else bytes_hlo
    coll = parse_collective_bytes(compiled.as_text())  # loop-weighted
    counts = coll.pop("counts")
    coll_bytes = sum(coll.values())
    ma = compiled.memory_analysis()
    mem = {
        "arguments": int(ma.argument_size_in_bytes),
        "outputs": int(ma.output_size_in_bytes),
        "temps": int(ma.temp_size_in_bytes),
        "code": int(ma.generated_code_size_in_bytes),
        "total": int(
            ma.argument_size_in_bytes + ma.temp_size_in_bytes + ma.output_size_in_bytes
        ),
    }
    return Roofline(
        arch=arch,
        shape=shape,
        mesh=mesh_name,
        n_chips=n_chips,
        flops_per_device=flops,
        bytes_per_device=bytes_,
        collective_bytes_per_device=coll_bytes,
        collective_counts={**counts, "bytes_by_kind": coll},
        t_compute=flops / TPU_V5E_BF16_FLOPS,
        t_memory=bytes_ / TPU_V5E_HBM_BW,
        t_collective=coll_bytes / TPU_V5E_ICI_BW,
        model_flops=model_flops,
        hlo_flops_global=analytic_total,
        memory_per_device=mem,
        loop_correction=correction,
        hlo_flops_raw=hlo_flops,
        bytes_upper_bound=bytes_hlo,
    )
