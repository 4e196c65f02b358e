"""Pallas plan checker: pad plans, logical output shapes, accumulators.

Three layers of the kernel contract (DESIGN.md §7), checked without
executing a single kernel:

1. **Pad-plan invariants** — for a hostile sweep of real geometries
   (primes, 1568 = 28·28·2, tiny readouts), every ``pad_spec`` /
   ``pad_hc_spec`` plan must produce an aligned block that divides the
   padded dim exactly (BlockSpec shapes divide the padded extents — the
   Mosaic precondition), pad minimally, and keep hypercolumns whole per
   block (per-HC softmax stays block-local).
2. **Logical output shapes** — ``jax.eval_shape`` over every registered
   public kernel wrapper on a deliberately misaligned geometry: the
   wrapper must slice its padded outputs back to the logical shapes, so
   padding can never leak into a caller.
3. **Accumulator dtypes** — an AST scan of ``kernels/*.py`` asserting
   every ``pltpu.VMEM`` scratch buffer carries its kernel's declared
   accumulator dtype (f32 everywhere; i32 for the exact int8 kernels)
   and every kernel matmul pins ``preferred_element_type`` to f32.
"""
from __future__ import annotations

import ast
from pathlib import Path
from typing import Any, Callable, Dict, List, Tuple

# Declared accumulator contract per kernel module: the dtypes VMEM
# scratch buffers are allowed to carry.  quant.py accumulates exact int8
# products in i32 (DESIGN.md §8); everything else accumulates f32.
KERNEL_ACCUMULATOR_DTYPES: Dict[str, set] = {
    "bcpnn_fwd.py": {"jnp.float32"},
    "bcpnn_update.py": {"jnp.float32"},
    "hc_softmax.py": {"jnp.float32"},
    "patchy.py": {"jnp.float32"},
    "quant.py": {"jnp.int32"},
}

# Geometry sweep for the pad-plan invariants: the repo's real shapes
# (Model 1's 1568-unit pre side, 10/2-class readouts) plus primes and
# degenerate sizes that historically exposed fit-down-to-divisor bugs.
_DIMS = (1, 2, 3, 5, 7, 8, 10, 13, 16, 21, 100, 127, 128, 129, 130, 200,
         1009, 1568)
_BLOCKS = (8, 16, 128, 512)
_HC_GEOMS = ((1, 2), (1, 10), (3, 10), (7, 3), (28, 2), (32, 128),
             (13, 5), (784, 2))


def check_pad_plans() -> List[str]:
    """Layer 1: pad_spec/pad_hc_spec invariants over the hostile sweep."""
    from ..kernels.tiling import (
        LANE, SUBLANE, lane_multiple, pad_hc_spec, pad_spec, round_up,
    )
    import math

    problems: List[str] = []
    for dim in _DIMS:
        for block in _BLOCKS:
            for multiple in (SUBLANE, lane_multiple(dim)):
                ps = pad_spec(dim, block, multiple)
                where = (f"pad_spec(dim={dim}, block={block}, "
                         f"multiple={multiple})")
                if ps.block % multiple != 0:
                    problems.append(f"{where}: block {ps.block} is not "
                                    f"aligned to {multiple}")
                if ps.padded % ps.block != 0:
                    problems.append(f"{where}: block {ps.block} does not "
                                    f"divide padded {ps.padded}")
                if ps.padded < dim:
                    problems.append(f"{where}: padded {ps.padded} < dim")
                if ps.padded > round_up(dim, multiple):
                    problems.append(
                        f"{where}: padded {ps.padded} over-pads (a "
                        f"{multiple}-aligned block reaches "
                        f"{round_up(dim, multiple)})")
    for n_hc, n_mc in _HC_GEOMS:
        for block_units in (128, 512, 2048):
            hs = pad_hc_spec(n_hc, n_mc, block_units)
            where = f"pad_hc_spec({n_hc}, {n_mc}, {block_units})"
            if hs.mc_padded < n_mc:
                problems.append(f"{where}: mc_padded {hs.mc_padded} < n_mc")
            if hs.hc.padded % hs.hc.block != 0:
                problems.append(f"{where}: HC block {hs.hc.block} does not "
                                f"divide padded HC count {hs.hc.padded}")
            # whole 128-lane tiles per block: the HC-count block must be a
            # multiple of LANE/gcd(mc_padded, LANE)
            hq = LANE // math.gcd(hs.mc_padded, LANE)
            if hs.hc.block % hq != 0:
                problems.append(
                    f"{where}: HC block {hs.hc.block} breaks whole-lane "
                    f"tiling (needs a multiple of {hq})")
    return problems


def _hostile_shapes() -> Tuple[Dict[str, int], Any, Any, Any, Any]:
    """One deliberately misaligned geometry shared by every wrapper
    check: B=5, pre 7×3 (Ni=21), post 3×10 (Nj=30), nact=2 (K=6) —
    nothing divides 8 or 128."""
    import jax.numpy as jnp
    from jax import ShapeDtypeStruct as S

    f32, i8, i32 = jnp.float32, jnp.int8, jnp.int32
    d = dict(b=5, hi=7, mi=3, hj=3, mj=10, nact=2)
    d["ni"] = d["hi"] * d["mi"]
    d["nj"] = d["hj"] * d["mj"]
    d["k"] = d["nact"] * d["mi"]
    return d, S, f32, i8, i32


def check_output_shapes() -> List[str]:
    """Layer 2: every registered kernel wrapper returns LOGICAL shapes."""
    import jax
    import jax.numpy as jnp
    from ..kernels.bcpnn_fwd import bcpnn_fwd_pallas
    from ..kernels.bcpnn_update import bcpnn_update_pallas
    from ..kernels.hc_softmax import hc_softmax_pallas
    from ..kernels.patchy import (
        compact_forward, compact_update, patchy_forward, patchy_update,
    )
    from ..kernels.quant import (
        quant_compact_forward, quant_fwd_pallas, quant_patchy_forward,
    )

    d, S, f32, i8, i32 = _hostile_shapes()
    b, ni, nj, hi, mi, hj, mj, k = (d["b"], d["ni"], d["nj"], d["hi"],
                                    d["mi"], d["hj"], d["mj"], d["k"])
    x = S((b, ni), f32)
    w = S((ni, nj), f32)
    bias = S((nj,), f32)
    table = S((hj, d["nact"]), i32)
    scale = S((hj,), f32)
    alpha = S((), f32)

    # name -> (thunk, expected output shapes)
    cases: Dict[str, Tuple[Callable, Tuple[Tuple[int, ...], ...]]] = {
        "hc_softmax": (lambda: jax.eval_shape(
            lambda s: hc_softmax_pallas(s, hj, mj), S((b, nj), f32)),
            ((b, nj),)),
        "bcpnn_fwd": (lambda: jax.eval_shape(
            lambda *a: bcpnn_fwd_pallas(*a, n_hc=hj, n_mc=mj), x, w, bias),
            ((b, nj),)),
        "bcpnn_update": (lambda: jax.eval_shape(
            lambda pij, lpi, lpj, xx, yy, mask, al: bcpnn_update_pallas(
                pij, lpi, lpj, xx, yy, mask, al),
            S((ni, nj), f32), S((ni,), f32), S((nj,), f32), x,
            S((b, nj), f32), S((hi, hj), f32), alpha),
            ((ni, nj), (ni, nj))),
        "patchy_forward": (lambda: jax.eval_shape(
            lambda xx, ww, bb, tt: patchy_forward(xx, ww, bb, tt, mi, hj, mj),
            x, w, bias, table), ((b, nj),)),
        "patchy_update": (lambda: jax.eval_shape(
            lambda pij, lpi, lpj, xx, yy, tt, al: patchy_update(
                pij, lpi, lpj, xx, yy, tt, al, mi, hj, mj),
            S((ni, nj), f32), S((ni,), f32), S((nj,), f32), x,
            S((b, nj), f32), table, alpha), ((ni, nj), (ni, nj))),
        "compact_forward": (lambda: jax.eval_shape(
            lambda xx, wc, bb, tt: compact_forward(xx, wc, bb, tt, mi),
            x, S((hj, k, mj), f32), bias, table), ((b, nj),)),
        "compact_update": (lambda: jax.eval_shape(
            lambda pc, lpi, lpj, xx, yy, tt, al: compact_update(
                pc, lpi, lpj, xx, yy, tt, al, mi),
            S((hj, k, mj), f32), S((ni,), f32), S((nj,), f32), x,
            S((b, nj), f32), table, alpha),
            ((hj, k, mj), (hj, k, mj))),
        "quant_fwd": (lambda: jax.eval_shape(
            lambda xx, wq, bb, ss: quant_fwd_pallas(xx, wq, bb, ss, hj, mj),
            x, S((ni, nj), i8), bias, scale), ((b, nj),)),
        "quant_patchy_forward": (lambda: jax.eval_shape(
            lambda xx, wq, bb, ss, tt: quant_patchy_forward(
                xx, wq, bb, ss, tt, mi, hj, mj),
            x, S((ni, nj), i8), bias, scale, table), ((b, nj),)),
        "quant_compact_forward": (lambda: jax.eval_shape(
            lambda xx, wq, bb, ss, tt: quant_compact_forward(
                xx, wq, bb, ss, tt, mi),
            x, S((hj, k, mj), i8), bias, scale, table), ((b, nj),)),
    }

    from ..kernels import ops
    kernel_modules = {f"{ops.__package__}.{f[:-3]}"
                      for f in KERNEL_ACCUMULATOR_DTYPES}
    called = {name.removesuffix("_pallas") for name, obj in vars(ops).items()
              if getattr(obj, "__module__", None) in kernel_modules}
    problems: List[str] = []
    missing = called - set(cases)
    if missing:
        problems.append(f"kernels that kernels/ops.py calls are not "
                        f"shape-checked here: {sorted(missing)} — add cases")
    for name, (thunk, expected) in cases.items():
        try:
            out = thunk()
        except Exception as e:  # noqa: BLE001 — any trace failure is the finding
            problems.append(f"{name}: abstract eval failed on the hostile "
                            f"geometry: {type(e).__name__}: {e}")
            continue
        shapes = tuple(o.shape for o in jax.tree_util.tree_leaves(out))
        if shapes != expected:
            problems.append(f"{name}: output shapes {shapes} != logical "
                            f"{expected} — padded extents leaked past the "
                            f"wrapper's unpad slice")
    return problems


def check_accumulators(kernels_dir: Path = None) -> List[str]:
    """Layer 3: VMEM scratch dtypes + preferred_element_type, by AST."""
    if kernels_dir is None:
        kernels_dir = Path(__file__).resolve().parent.parent / "kernels"
    problems: List[str] = []
    for fname, allowed in sorted(KERNEL_ACCUMULATOR_DTYPES.items()):
        fpath = kernels_dir / fname
        if not fpath.exists():
            problems.append(f"{fname}: declared in "
                            f"KERNEL_ACCUMULATOR_DTYPES but missing on disk")
            continue
        tree = ast.parse(fpath.read_text(encoding="utf-8"))
        n_vmem = n_dot = 0
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            name = _dotted(node.func)
            if name.endswith("VMEM") and len(node.args) >= 2:
                n_vmem += 1
                dt = _dotted(node.args[1])
                if dt.split(".")[-1] not in {a.split(".")[-1]
                                             for a in allowed}:
                    problems.append(
                        f"{fname}:{node.lineno}: VMEM scratch dtype {dt!r} "
                        f"violates the declared accumulator contract "
                        f"{sorted(allowed)}")
            if name.endswith("dot") or name.endswith("dot_general"):
                n_dot += 1
                pet = next((kw.value for kw in node.keywords
                            if kw.arg == "preferred_element_type"), None)
                if pet is None or _dotted(pet).split(".")[-1] != "float32":
                    problems.append(
                        f"{fname}:{node.lineno}: kernel matmul without "
                        f"preferred_element_type=jnp.float32 — accumulation "
                        f"precision is part of the kernel contract")
        if fname != "hc_softmax.py" and n_dot == 0:
            problems.append(f"{fname}: expected at least one kernel matmul "
                            f"to audit, found none (scan out of date?)")
    return problems


def _dotted(node: ast.AST) -> str:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def check_pallas_plans() -> List[str]:
    """All three layers; empty list = the kernel plan contract holds."""
    return (check_pad_plans() + check_output_shapes()
            + check_accumulators())
