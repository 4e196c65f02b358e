"""Roofline analysis from compiled (SPMD-partitioned) HLO.

XLA's built-in cost analysis counts a while-loop body ONCE and reports
per-device numbers post-partitioning (verified empirically on this jax
build) — useless for scanned-layer models.  This module parses the
optimized HLO text instead:

  * computations are parsed into blocks; `while` ops multiply their body's
    cost by the trip count recovered from the loop condition's constant;
  * FLOPs come from `dot(`/`convolution(` lines (2 x result x contraction);
  * HBM bytes are approximated as the result bytes of every materializing
    op (fusions, dots, copies, slices, collectives) — fused interiors
    excluded, mirroring what actually hits HBM;
  * collective bytes take the largest shape on each collective line
    (local, i.e. per-device), x2 for all-reduce (reduce + broadcast
    phases of a ring).

All numbers are per-chip.  Hardware constants per the brief: 197 TFLOP/s
bf16, 819 GB/s HBM, ~50 GB/s/link ICI (TPU v5e-class).
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Dict, List, Optional, Tuple

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLLECTIVES = ("all-gather(", "all-reduce(", "reduce-scatter(",
                "all-to-all(", "collective-permute(")
_MATERIALIZING = re.compile(
    r"= \w+\[[\d,]*\][^ ]* (fusion|dot|convolution|copy|dynamic-slice|"
    r"dynamic-update-slice|gather|scatter|slice|concatenate|broadcast|"
    r"transpose|reduce|select-and-scatter|sort|iota|rng|pad|reshape|"
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|"
    r"custom-call)\(")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _shapes_on(line: str) -> List[int]:
    return [_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(line)]


def _result_shape(line: str) -> Optional[Tuple[str, List[int]]]:
    m = re.search(r"= (\w+)\[([\d,]*)\]", line)
    if not m:
        return None
    dims = [int(x) for x in m.group(2).split(",")] if m.group(2) else []
    return m.group(1), dims


_OPERANDS_RE = re.compile(r"\((%[\w\.\-]+(?:, %[\w\.\-]+)*)\)")
_DEF_RE = re.compile(r"^(?:ROOT )?%([\w\.\-]+) = (\w+)\[([\d,]*)\]")


def _dot_flops(line: str, shapes: Dict[str, Tuple[str, List[int]]]) -> float:
    res = _result_shape(line)
    if res is None:
        return 0.0
    _, rdims = res
    result_elems = math.prod(rdims) if rdims else 1
    # contraction size from the (name-resolved) lhs shape
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", line)
    contraction = 1
    op_part = line.split(" dot(", 1)[1] if " dot(" in line else line
    names = re.findall(r"%([\w\.\-]+)", op_part)
    if m and names and names[0] in shapes:
        lhs_dims = shapes[names[0]][1]
        for idx in (int(i) for i in m.group(1).split(",") if i):
            if idx < len(lhs_dims):
                contraction *= lhs_dims[idx]
    return 2.0 * result_elems * contraction


@dataclasses.dataclass
class CompCost:
    flops: float = 0.0
    bytes: float = 0.0
    coll_bytes: float = 0.0
    coll_detail: Dict[str, float] = dataclasses.field(default_factory=dict)


class HloAnalyzer:
    def __init__(self, hlo_text: str):
        self.comps: Dict[str, List[str]] = {}
        self.entry: Optional[str] = None
        self.shapes: Dict[str, Tuple[str, List[int]]] = {}
        self._parse(hlo_text)
        self._memo: Dict[str, CompCost] = {}

    def _parse(self, text: str):
        cur = None
        for line in text.splitlines():
            m = re.match(r"(ENTRY )?%([\w\.\-]+)[ ]*\(.*\) -> .* \{", line)
            if m:
                cur = m.group(2)
                self.comps[cur] = []
                if m.group(1):
                    self.entry = cur
                continue
            if line.startswith("}"):
                cur = None
                continue
            if cur is not None:
                stripped = line.strip()
                self.comps[cur].append(stripped)
                d = _DEF_RE.match(stripped)
                if d:
                    dims = ([int(x) for x in d.group(3).split(",")]
                            if d.group(3) else [])
                    self.shapes[d.group(1)] = (d.group(2), dims)

    def _operand_bytes(self, line: str, op: str) -> List[int]:
        """Byte sizes of an op's named operands (resolved via the def map)."""
        part = line.split(" " + op, 1)
        if len(part) < 2:
            return []
        out = []
        m = re.match(r"\(([^)]*)\)", part[1])
        if not m:
            return []
        for name in re.findall(r"%([\w\.\-]+)", m.group(1)):
            if name in self.shapes:
                dt, dims = self.shapes[name]
                out.append(math.prod(dims or [1]) * _DTYPE_BYTES.get(dt, 4))
        return out

    def _trip_count(self, cond: str) -> int:
        best = 1
        for line in self.comps.get(cond, []):
            for m in re.finditer(r"constant\((\d+)\)", line):
                best = max(best, int(m.group(1)))
        return best

    def cost(self, comp: Optional[str] = None) -> CompCost:
        comp = comp or self.entry
        if comp in self._memo:
            return self._memo[comp]
        total = CompCost()
        for line in self.comps.get(comp, []):
            if " while(" in line:
                mb = re.search(r"body=%([\w\.\-]+)", line)
                mc = re.search(r"condition=%([\w\.\-]+)", line)
                if mb and mc:
                    trips = self._trip_count(mc.group(1))
                    sub = self.cost(mb.group(1))
                    total.flops += trips * sub.flops
                    total.bytes += trips * sub.bytes
                    total.coll_bytes += trips * sub.coll_bytes
                    for k, v in sub.coll_detail.items():
                        total.coll_detail[k] = total.coll_detail.get(k, 0) + trips * v
                continue
            if " dot(" in line or " convolution(" in line:
                total.flops += _dot_flops(line, self.shapes)
            m = re.search(r"(?:calls|to_apply)=%([\w\.\-]+)", line)
            if m and " fusion(" in line:
                total.flops += self.cost(m.group(1)).flops
            elif m and (" call(" in line or " conditional(" in line):
                sub = self.cost(m.group(1))
                total.flops += sub.flops
                total.bytes += sub.bytes
                total.coll_bytes += sub.coll_bytes
            for cname in _COLLECTIVES:
                if " " + cname in line:
                    shapes = _shapes_on(line) + self._operand_bytes(line, cname)
                    if shapes:
                        b = max(shapes)
                        factor = 2.0 if cname == "all-reduce(" else 1.0
                        total.coll_bytes += factor * b
                        key = cname.rstrip("(")
                        total.coll_detail[key] = total.coll_detail.get(key, 0) + factor * b
                    break
            if _MATERIALIZING.search(line):
                res = _result_shape(line)
                if res:
                    dt, dims = res
                    total.bytes += math.prod(dims or [1]) * _DTYPE_BYTES.get(dt, 4)
        self._memo[comp] = total
        return total


@dataclasses.dataclass
class Roofline:
    flops: float
    bytes: float
    coll_bytes: float
    coll_detail: Dict[str, float]
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops_per_chip: float = 0.0
    useful_ratio: float = 0.0

    def to_dict(self):
        return dataclasses.asdict(self)


def analyze(hlo_text: str, model_flops_global: float = 0.0,
            n_chips: int = 1) -> Roofline:
    c = HloAnalyzer(hlo_text).cost()
    # bytes counted result-side only; reads roughly double the traffic
    hbm_bytes = 2.0 * c.bytes
    compute_s = c.flops / PEAK_FLOPS
    memory_s = hbm_bytes / HBM_BW
    coll_s = c.coll_bytes / ICI_BW
    terms = {"compute": compute_s, "memory": memory_s, "collective": coll_s}
    bottleneck = max(terms, key=terms.get)
    mf = model_flops_global / max(1, n_chips)
    return Roofline(
        flops=c.flops, bytes=hbm_bytes, coll_bytes=c.coll_bytes,
        coll_detail=c.coll_detail, compute_s=compute_s, memory_s=memory_s,
        collective_s=coll_s, bottleneck=bottleneck,
        model_flops_per_chip=mf,
        useful_ratio=(mf / c.flops) if c.flops else 0.0,
    )


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6·N·D (dense) / 6·N_active·D (MoE); decode: D = batch
    tokens; train: x3 is already in the 6 (fwd+bwd)."""
    n = param_count_active(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch  # one token per sequence


def param_count_active(cfg) -> float:
    """Active parameters per token (MoE counts top-k experts + router)."""
    d, v = cfg.d_model, cfg.vocab_padded
    emb = v * d * (1 if cfg.tie_embeddings else 2)
    per_layer = {}
    attn = d * cfg.n_heads * cfg.head_dim * 2 + d * cfg.n_kv_heads * cfg.head_dim * 2
    glu = 3 if cfg.mlp in ("swiglu", "geglu") else 2
    mlp_p = glu * d * cfg.d_ff
    total = emb
    for i, ch in enumerate(cfg.layer_pattern):
        n_of_this = cfg.n_layers // len(cfg.layer_pattern) + (
            1 if i < cfg.n_layers % len(cfg.layer_pattern) else 0)
        if ch in ("g", "l"):
            layer = attn + (cfg.n_experts_active * mlp_p + d * cfg.n_experts
                            if cfg.n_experts else mlp_p)
        elif ch == "m":
            di = cfg.d_inner
            layer = (d * 2 * di + di * d + cfg.d_conv * di
                     + di * (cfg.dt_rank_eff + 2 * cfg.ssm_state)
                     + cfg.dt_rank_eff * di + di * cfg.ssm_state)
        else:  # rg-lru
            w = cfg.lru_width_eff
            layer = d * w * 2 + w * d + w * w * 2 + cfg.d_conv * w + mlp_p
        total += n_of_this * layer
    if cfg.enc_layers:
        total += cfg.enc_layers * (attn + mlp_p)
    return float(total)
