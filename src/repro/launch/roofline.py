"""Roofline-term extraction from a compiled dry-run artifact.

Three terms per (arch × shape × mesh), in seconds (§Roofline):

  compute    = HLO_FLOPs   / (chips × peak_FLOP/s)
  memory     = HLO_bytes   / (chips × HBM_bw)
  collective = coll_bytes  / (chips × link_bw)

``compiled.cost_analysis()`` reports flops/bytes of the PER-DEVICE partitioned
program, so totals are ``value × chips`` and the per-chip division cancels:
compute = cost['flops'] / peak, memory = cost['bytes accessed'] / bw.

Collective bytes are NOT in cost_analysis — we parse the post-SPMD HLO and
sum operand bytes of all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute ops (per-device shapes, so again no chips division).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

# TPU v5e hardware constants (assignment spec)
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# result shapes:  %x = f32[256,128]{1,0} all-gather(%param), ...
#                 %y = (f32[8], f32[8]) all-reduce(...)   (tuple form)
_LINE_RE = re.compile(
    r"=\s*(?P<shapes>\([^)]*\)|[a-z][a-z0-9]*\[[0-9,]*\](?:\{[^}]*\})?)\s+"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?P<suffix>-start|-done)?\(")
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
# replica_groups={{0,1},{2,3}}  or  replica_groups=[32,8]<=[256]...
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dtype, 4)
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len([t for t in m.group(1).split(",") if t.strip()])
    return 1


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-device ICI traffic per collective kind, from post-SPMD HLO.

    Ring cost model on the RESULT shape R with group size g
    (operand shapes are not printed in compiled HLO):
      all-gather        R·(g−1)/g      (result = gathered full tensor)
      all-reduce        2·R·(g−1)/g    (reduce-scatter + all-gather phases)
      reduce-scatter    R·(g−1)        (operand = R·g; send all but own shard)
      all-to-all        R·(g−1)/g
      collective-permute R
    """
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _LINE_RE.search(line)
        if not m or m.group("suffix") == "-done":
            continue
        kind = m.group("op")
        nbytes = _shape_bytes(m.group("shapes"))
        g = _group_size(line)
        if g <= 1 and kind != "collective-permute":
            continue
        if kind == "all-gather":
            traffic = nbytes * (g - 1) / g
        elif kind == "all-reduce":
            traffic = 2.0 * nbytes * (g - 1) / g
        elif kind == "reduce-scatter":
            traffic = nbytes * (g - 1)
        elif kind == "all-to-all":
            traffic = nbytes * (g - 1) / g
        else:
            traffic = nbytes
        out[kind] += int(traffic)
    return out


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_device: float
    bytes_per_device: float
    coll_bytes_per_device: float
    coll_breakdown: Dict[str, int]
    peak_mem_bytes: float            # per-device from memory_analysis
    model_flops: float               # 6·N·D analytic
    compute_s: float
    memory_s: float
    collective_s: float

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_ratio(self) -> float:
        total = self.flops_per_device * self.chips
        return self.model_flops / total if total else 0.0


def analyze(compiled, *, arch: str, shape: str, mesh_name: str, chips: int,
            model_flops: float, hlo_text: Optional[str] = None) -> Roofline:
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    text = hlo_text if hlo_text is not None else compiled.as_text()
    coll = collective_bytes(text)
    coll_total = float(sum(coll.values()))
    try:
        mem = compiled.memory_analysis()
        peak = float(getattr(mem, "peak_memory_in_bytes", 0) or
                     (mem.argument_size_in_bytes
                      + mem.output_size_in_bytes
                      + mem.temp_size_in_bytes))
    except Exception:
        peak = 0.0
    return Roofline(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        flops_per_device=flops, bytes_per_device=byts,
        coll_bytes_per_device=coll_total, coll_breakdown=coll,
        peak_mem_bytes=peak, model_flops=model_flops,
        compute_s=flops / PEAK_FLOPS,
        memory_s=byts / HBM_BW,
        collective_s=coll_total / ICI_BW,
    )


def model_flops_estimate(cfg, shape) -> float:
    """MODEL_FLOPS = 6·N·D (dense) / 6·N_active·D (MoE); decode D = batch·1."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch


def format_table(rows: List[Roofline]) -> str:
    hdr = (f"{'arch':<26}{'shape':<13}{'mesh':<9}{'compute_s':>11}"
           f"{'memory_s':>11}{'coll_s':>11}{'dominant':>11}{'useful':>8}")
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        lines.append(
            f"{r.arch:<26}{r.shape:<13}{r.mesh:<9}{r.compute_s:>11.4g}"
            f"{r.memory_s:>11.4g}{r.collective_s:>11.4g}{r.dominant:>11}"
            f"{r.useful_ratio:>8.3f}")
    return "\n".join(lines)
