"""Pallas TPU kernel: fused BCPNN plasticity stage.

One kernel performs, per (Ni, Nj) tile of the projection:

    co      = XᵀY / n                    (MXU, contraction over batch)
    p_ij'   = (1-α)·p_ij + α·co          (trace EMA)
    w       = (log p_ij' − log p_i − log p_j) [· mask]   (Bayesian weights)

On the FPGA these are three pipeline stages connected by FIFOs fed from
four partitioned HBM channels (paper Opt #3); here each (ti, tj) tile of
p_ij streams HBM→VMEM once and both outputs stream back once — the joint
trace and the weight matrix never make an extra HBM round-trip.

Grid = (Ni/ti, Nj/tj, B/tk) over the PADDED shapes, contraction
innermost.  Pad semantics (DESIGN.md §7): pad batch rows of x/y are zero,
so they add nothing to XᵀY.  The divisor ``n`` is a runtime operand, the
number of GENUINE rows: the plain call passes the batch size, and the
masked tail-batch learn (``core.bcpnn_layer.learn_masked``) passes its
real row count after zeroing the pad rows, so one compiled kernel serves
every step of a fit whose data does not divide the batch.  Pad
rows/columns of pij and pad rows of the mask are zero, producing inert
outputs that are sliced off.

The mask is an optional operand: a dense projection's mask is all ones
by construction, so its caller passes ``mask=None`` and the kernel
neither reads nor multiplies one (a static flag of the kernel).  A
patchy projection passes its (Hi, Hj) hypercolumn-level mask; Mi and Mj
follow from the shapes.  The wrapper widens it along rows only, to
(Ni, Hj) (1 MB at Model 3's widths), and each (ti, tj) tile widens its
(ti, Hj) rows to unit columns in VMEM: a 0/1 product with an iota-built
(Hj, tj) one-hot on the MXU, exact because every unit column belongs to
at most one hypercolumn.  No O(Ni·Nj) mask exists in HBM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .padding import pad_axis
from .tiling import SUBLANE, lane_multiple, pad_spec


def _unit_mask(rows: jax.Array, j, tj: int, mj: int) -> jax.Array:
    """The (ti, tj) unit mask of column tile ``j`` from the tile's (ti, Hj)
    rows of the row-widened HC mask: unit column ``c`` belongs to post-HC
    ``(j*tj + c) // mj``; a pad column (past Hj*mj) to none, so it reads 0.
    """
    hj = rows.shape[1]
    col = j * tj + jax.lax.broadcasted_iota(jnp.int32, (hj, tj), 1)
    lo = jax.lax.broadcasted_iota(jnp.int32, (hj, tj), 0) * mj
    hot = jnp.where((col >= lo) & (col < lo + mj), 1.0, 0.0)
    # each output element is one 0/1 product: exact in one bf16 pass with
    # an f32 sum, whatever ``jax_default_matmul_precision`` asks elsewhere
    return jnp.dot(rows.astype(jnp.bfloat16), hot.astype(jnp.bfloat16),
                   precision=jax.lax.Precision.DEFAULT,
                   preferred_element_type=jnp.float32)


def _kernel(*refs, k_steps: int, eps: float, mj: Optional[int]):
    masked = mj is not None
    if masked:
        (x_ref, y_ref, pij_ref, lpi_ref, lpj_ref, alpha_ref, n_ref, mask_ref,
         pij_out_ref, w_out_ref, acc_ref) = refs
    else:
        (x_ref, y_ref, pij_ref, lpi_ref, lpj_ref, alpha_ref, n_ref,
         pij_out_ref, w_out_ref, acc_ref) = refs
    j, k = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # x block: (tk, ti) — pre-transposed so the MXU contracts the batch dim.
    acc_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32).T,
        y_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == k_steps - 1)
    def _epilogue():
        alpha = alpha_ref[0, 0]
        co = acc_ref[...] / n_ref[0, 0]
        new_pij = (1.0 - alpha) * pij_ref[...] + alpha * co
        pij_out_ref[...] = new_pij
        logp = jnp.log(jnp.clip(new_pij, eps * eps, 1.0))
        w = logp - (lpi_ref[...].T + lpj_ref[...])
        if masked:
            w = w * _unit_mask(mask_ref[...], j, w.shape[1], mj)
        w_out_ref[...] = w


@functools.partial(
    jax.jit,
    static_argnames=("eps", "block_i", "block_j", "block_k", "interpret"),
)
def bcpnn_update_pallas(
    pij: jax.Array,     # (Ni, Nj)
    log_pi: jax.Array,  # (Ni,) log of updated+clipped pre marginals
    log_pj: jax.Array,  # (Nj,)
    x: jax.Array,       # (B, Ni), pad rows zero
    y: jax.Array,       # (B, Nj), pad rows zero
    mask: Optional[jax.Array],  # (Hi, Hj) HC mask, or None: all ones
    alpha: jax.Array,   # scalar
    n: Optional[jax.Array] = None,  # scalar genuine-row count; None: B
    eps: float = 1e-4,
    block_i: int = 512,
    block_j: int = 512,
    block_k: int = 128,
    interpret: bool = False,
):
    """Returns (new_pij, new_w) — see module docstring."""
    b, ni = x.shape
    nj = y.shape[1]
    # Ni is the lane dim of x blocks AND the sublane dim of pij/w blocks;
    # Nj is a lane dim throughout; the batch is sublane-only.
    is_ = pad_spec(ni, block_i, lane_multiple(ni))
    js = pad_spec(nj, block_j, lane_multiple(nj))
    ks = pad_spec(b, block_k, SUBLANE)
    xp = pad_axis(pad_axis(x, 1, is_.pad), 0, ks.pad)
    yp = pad_axis(pad_axis(y, 1, js.pad), 0, ks.pad)
    pijp = pad_axis(pad_axis(pij, 0, is_.pad), 1, js.pad)
    lpip = pad_axis(log_pi.reshape(1, ni), 1, is_.pad)
    lpjp = pad_axis(log_pj.reshape(1, nj), 1, js.pad)
    n = b if n is None else n
    tile = pl.BlockSpec((is_.block, js.block), lambda i, j, k: (i, j))
    scalar = pl.BlockSpec((1, 1), lambda i, j, k: (0, 0))
    in_specs = [
        pl.BlockSpec((ks.block, is_.block), lambda i, j, k: (k, i)),   # x
        pl.BlockSpec((ks.block, js.block), lambda i, j, k: (k, j)),    # y
        tile,                                                          # pij
        pl.BlockSpec((1, is_.block), lambda i, j, k: (0, i)),          # log_pi
        pl.BlockSpec((1, js.block), lambda i, j, k: (0, j)),           # log_pj
        scalar,                                                        # alpha
        scalar,                                                        # n
    ]
    operands = [xp, yp, pijp, lpip, lpjp,
                jnp.reshape(alpha, (1, 1)).astype(jnp.float32),
                jnp.reshape(n, (1, 1)).astype(jnp.float32)]
    mj = None
    if mask is not None:
        hi, hj = mask.shape
        if ni % hi or nj % hj:
            raise ValueError(
                f"bcpnn_update_pallas: a ({hi}, {hj}) HC mask does not tile "
                f"a ({ni}, {nj}) projection")
        mj = nj // hj
        rows = jnp.repeat(mask.astype(jnp.float32), ni // hi, axis=0)
        # Hj is the whole lane extent of the block, so any Hj is legal
        in_specs.append(pl.BlockSpec((is_.block, hj), lambda i, j, k: (i, 0)))
        operands.append(pad_axis(rows, 0, is_.pad))
    kern = functools.partial(_kernel, k_steps=ks.grid, eps=eps, mj=mj)
    new_pij, w = pl.pallas_call(
        kern,
        grid=(is_.grid, js.grid, ks.grid),
        in_specs=in_specs,
        out_specs=[tile, tile],
        out_shape=[
            jax.ShapeDtypeStruct((is_.padded, js.padded), jnp.float32),
            jax.ShapeDtypeStruct((is_.padded, js.padded), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((is_.block, js.block), jnp.float32)],
        # p_ij' overwrites p_ij tile by tile (each output tile reads only
        # its own input tile), so a caller whose p_ij dies here -- the
        # epoch scan's carry -- updates it in place instead of copying
        # the whole matrix first.
        input_output_aliases={2: 0},
        interpret=interpret,
    )(*operands)
    return new_pij[:ni, :nj], w[:ni, :nj]
