"""Tier-2 low-precision inference: per-hypercolumn int8 quantization and
the int8 forward kernels.

The paper's fixed-point analysis (§3) splits the precision budget: the
trace EMAs must stay fp32 (per-step increments are below narrow-float
resolution), but the *inference-only* weights — folded log-odds that are
read, never accumulated into — tolerate aggressive quantization.  This
module is that split's int8 tier (DESIGN.md §8):

* **Per-post-HC symmetric scales.**  A post-hypercolumn is the natural
  quantization group: its Mj minicolumns compete in one softmax, so a
  shared scale preserves their support *ordering* exactly up to rounding,
  and the scale folds into the softmax epilogue as one scalar per HC.
  ``scale[j] = absmax_j / 127`` with ``w ≈ w_q * scale[j]`` — symmetric,
  zero-point-free (BCPNN log-odds are naturally zero-centered: silent and
  independent synapses sit at exactly 0, which must quantize to exactly
  0 for the patchy forward to stay exact).
* **Fixed Q0.7 activations.**  BCPNN rates are probabilities in [0, 1]
  (per-HC softmax outputs / complement-coded inputs), so activations
  quantize with the *static* scale 1/127 — no per-batch ranging on the
  hot path.
* **Integer accumulation, fp32 epilogue.**  The support matmul
  accumulates int8×int8 products exactly; dequantization is one fused
  multiply (``acc * scale[j]/127²``) folded into the fp32 bias-add +
  per-HC softmax stage, which stays fp32 like every other kernel here.

Accumulator note: the kernels keep an int32 accumulator but compute each
block's partial product on the float unit (operands cast int8→f32, dot
with f32 preferred type, partial cast back to int32).  For block_k ≤ 1024
this is *bit-exact* int8×int8→int32 arithmetic — products are ≤ 127² <
2¹⁴ and a ≤1024-term sum stays < 2²⁴, inside f32's exact-integer range —
while running at f32 MXU/GEMM speed everywhere (XLA:CPU lowers native s8
dots to scalar loops ~7× slower, and the bandwidth win of int8 operands
is the point of this tier, not integer ALUs).  ``_check_exact_block``
enforces the bound.

Everything here is forward/inference-only: quantization happens at fold
boundaries from the fp32 weights (core.bcpnn_layer.pack_projection), and
no learning state ever leaves fp32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.compact import unit_indices
from .hc_softmax import softmax_tile
from .padding import pad_axis, pad_hc_axis, unpad_hc_axis
from .tiling import LANE, NEG, pad_hc_spec, pad_mc, pad_spec

INT8_MAX = 127          # symmetric: code -128 is never emitted
ACT_SCALE = 1.0 / 127   # fixed Q0.7 step for rates in [0, 1]
INT8_SUBLANE = 32       # int8 Mosaic sublane tile (f32's is 8)

# Exact-integer ceiling of the f32-emulated int8 dot: a block_k-term sum
# of ≤127² products must stay below 2^24.
_EXACT_BLOCK_K = (1 << 24) // (INT8_MAX * INT8_MAX)


def _check_exact_block(block_k: int) -> None:
    if block_k > _EXACT_BLOCK_K:
        raise ValueError(
            f"int8 kernels require block_k <= {_EXACT_BLOCK_K} for the "
            f"f32-emulated integer dot to be bit-exact (got {block_k})")


# ------------------------------------------------- fold-time quantize ----

def quantize_acts(x: jax.Array) -> jax.Array:
    """Rates (values in [0, 1]) -> int8 codes on the fixed Q0.7 grid.
    Per-request cost, O(B·N) — the weight quantization is the fold-time
    half; this is the streaming half."""
    return jnp.round(jnp.clip(x, 0.0, 1.0) * INT8_MAX).astype(jnp.int8)


def _scales_from_absmax(absmax: jax.Array) -> jax.Array:
    # An all-zero group (freshly-initialized or fully-silent HC) gets a
    # harmless nonzero scale: its codes are all 0 either way.
    return jnp.maximum(absmax, jnp.float32(1e-12)) / INT8_MAX


def quantize_dense(w: jax.Array, n_hc: int, n_mc: int):
    """Dense (Ni, Nj=n_hc·n_mc) fp32 weights -> (w_q int8, scale (Hj,))
    with per-post-HC symmetric scales: ``w ≈ w_q * scale[j]``."""
    ni, nj = w.shape
    w3 = w.reshape(ni, n_hc, n_mc)
    scale = _scales_from_absmax(jnp.max(jnp.abs(w3), axis=(0, 2)))
    codes = jnp.round(w3 / scale[None, :, None])
    w_q = jnp.clip(codes, -INT8_MAX, INT8_MAX).astype(jnp.int8)
    return w_q.reshape(ni, nj), scale


def quantize_compact(w_c: jax.Array):
    """Compact-resident (Hj, K, Mj) fp32 weights -> (w_q int8,
    scale (Hj,)); same per-post-HC scheme on the compact layout."""
    scale = _scales_from_absmax(jnp.max(jnp.abs(w_c), axis=(1, 2)))
    codes = jnp.round(w_c / scale[:, None, None])
    w_q = jnp.clip(codes, -INT8_MAX, INT8_MAX).astype(jnp.int8)
    return w_q, scale


def dequantize_dense(w_q: jax.Array, scale: jax.Array, n_hc: int,
                     n_mc: int) -> jax.Array:
    ni, nj = w_q.shape
    w3 = w_q.astype(jnp.float32).reshape(ni, n_hc, n_mc)
    return (w3 * scale[None, :, None]).reshape(ni, nj)


def dequantize_compact(w_q: jax.Array, scale: jax.Array) -> jax.Array:
    return w_q.astype(jnp.float32) * scale[:, None, None]


# ----------------------------------------------------- jnp references ----

def quant_support_dense_jnp(x, w_q, scale, b, n_hc, n_mc):
    """Fixed-point support on the dense layout, pure jnp: the production
    path of ``backend="jnp"`` int8 projections and the oracle of the
    padded-dense int8 kernel.  Same arithmetic (quantized activations,
    integer-valued accumulation, scale-folded fp32 dequant)."""
    xq = quantize_acts(x).astype(jnp.float32)
    acc = xq @ w_q.astype(jnp.float32)
    su = jnp.repeat(scale * ACT_SCALE, n_mc)
    return b.astype(jnp.float32)[None, :] + acc * su[None, :]


def quant_support_compact_jnp(x, w_q, scale, b, table, mi):
    """Fixed-point support on the compact (Hj, K, Mj) layout, pure jnp."""
    hj, k, mj = w_q.shape
    ui = unit_indices(table, mi, sentinel=x.shape[1])
    xq = jnp.take(quantize_acts(x).astype(jnp.float32), ui, axis=1,
                  mode="fill", fill_value=0.0)            # (B, Hj, K)
    acc = jnp.einsum("bjk,jkm->bjm", xq, w_q.astype(jnp.float32))
    s3 = acc * (scale * ACT_SCALE)[None, :, None]
    return s3.reshape(x.shape[0], hj * mj) + b.astype(jnp.float32)[None, :]


# --------------------------------------------- padded-dense int8 kernel ----

def _quant_kernel(x_ref, w_ref, b_ref, s_ref, o_ref, acc_ref, *,
                  k_steps: int, n_mc: int, gain: float):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Bit-exact int8×int8→int32 on the float unit (see module docstring).
    acc_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32),
        w_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ).astype(jnp.int32)

    @pl.when(k == k_steps - 1)
    def _epilogue():
        # Scale-folded dequant straight into the fp32 logit stage: one
        # fused multiply-add per unit, then the standard per-HC softmax.
        s = (acc_ref[...].astype(jnp.float32) * s_ref[...] + b_ref[...]) * gain
        o_ref[...] = softmax_tile(s, n_mc).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("n_hc", "n_mc", "gain", "block_b", "block_j",
                     "block_k", "interpret"),
)
def quant_fwd_pallas(
    x: jax.Array,      # (B, Ni) fp32 rates
    w_q: jax.Array,    # (Ni, Nj) int8 codes
    bias: jax.Array,   # (Nj,) fp32
    scale: jax.Array,  # (Hj,) fp32 per-post-HC dequant scales
    n_hc: int,
    n_mc: int,
    gain: float = 1.0,
    block_b: int = 128,
    block_j: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """int8 variant of ``bcpnn_fwd_pallas``: fused support matmul over
    int8 operands + per-HC softmax, dequantized in the epilogue.  Output
    is fp32 rates like the fp32 kernel."""
    _check_exact_block(block_k)
    b, ni = x.shape
    nj = w_q.shape[1]
    assert nj == n_hc * n_mc
    assert w_q.dtype == jnp.int8
    bs = pad_spec(b, block_b, INT8_SUBLANE)
    ks = pad_spec(ni, block_k, LANE if ni >= LANE else INT8_SUBLANE)
    js = pad_hc_spec(n_hc, n_mc, block_j)
    xq = quantize_acts(x)
    xp = pad_axis(pad_axis(xq, 1, ks.pad), 0, bs.pad)
    wp = pad_hc_axis(pad_axis(w_q, 0, ks.pad), 1, js)
    bp = pad_hc_axis(bias.reshape(1, nj), 1, js, value=NEG)
    # Per-unit dequant row: scale[j]·(1/127) broadcast over each HC's
    # padded lanes (pad HCs get a harmless 1 — their NEG bias keeps them
    # inert through the softmax regardless).
    srow = jnp.broadcast_to((scale * ACT_SCALE)[:, None],
                            (n_hc, js.mc_padded)).reshape(1, -1)
    sp = pad_axis(srow.reshape(1, n_hc, js.mc_padded), 1,
                  js.hc.pad, value=1.0).reshape(1, js.padded_units)
    grid = (bs.grid, js.grid, ks.grid)
    out = pl.pallas_call(
        functools.partial(_quant_kernel, k_steps=ks.grid,
                          n_mc=js.mc_padded, gain=gain),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bs.block, ks.block), lambda i, j, k: (i, k)),
            pl.BlockSpec((ks.block, js.block_units), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, js.block_units), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, js.block_units), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bs.block, js.block_units),
                               lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bs.padded, js.padded_units), x.dtype),
        scratch_shapes=[pltpu.VMEM((bs.block, js.block_units), jnp.int32)],
        interpret=interpret,
    )(xp, wp, bp, sp)
    return unpad_hc_axis(out[:b], 1, js)


# -------------------------------------------- compact-patchy int8 kernel ----

def _quant_patchy_kernel(xg_ref, wg_ref, b_ref, s_ref, o_ref, acc_ref, *,
                         k_steps: int, gain: float):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        xg_ref[0].astype(jnp.float32),
        wg_ref[0].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ).astype(jnp.int32)

    @pl.when(k == k_steps - 1)
    def _epilogue():
        # One post-HC per tile: dequant by its scalar scale, bias, softmax
        # over the whole (padded) lane.
        s = (acc_ref[...].astype(jnp.float32) * s_ref[0] + b_ref[0]) * gain
        s = s - jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp(s)
        o_ref[0] = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(o_ref.dtype)


def _gather_pre_q(xq: jax.Array, ui: jax.Array, b_pad: int) -> jax.Array:
    """int8 codes (B, Ni) -> compact (Hj, B+b_pad, Kp), zero-code pads."""
    xg = jnp.take(xq, ui, axis=1, mode="fill",
                  fill_value=0)                          # (B, Hj, Kp)
    return pad_axis(xg, 0, b_pad).transpose(1, 0, 2)


@functools.partial(
    jax.jit,
    static_argnames=("mi", "gain", "block_b", "block_k", "interpret"),
)
def quant_compact_forward(
    x: jax.Array,      # (B, Ni) fp32 rates
    w_q: jax.Array,    # (Hj, K, Mj) int8 compact-resident codes
    bias: jax.Array,   # (Hj*Mj,) fp32
    scale: jax.Array,  # (Hj,) fp32
    table: jax.Array,  # (Hj, nact)
    mi: int,
    gain: float = 1.0,
    block_b: int = 128,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """int8 variant of ``compact_forward``: the activation gather runs on
    1-byte codes (4× less gather traffic than fp32), the resident weights
    stream as int8, and the per-HC scale dequantizes in the epilogue."""
    _check_exact_block(block_k)
    b, ni = x.shape
    hj, k_units, mj = w_q.shape
    assert w_q.dtype == jnp.int8
    bs = pad_spec(b, block_b, INT8_SUBLANE)
    ks = pad_spec(k_units, block_k,
                  LANE if k_units >= LANE else INT8_SUBLANE)
    mp = pad_mc(mj)
    ui = unit_indices(table, mi, ks.pad, sentinel=ni)
    xg = _gather_pre_q(quantize_acts(x), ui, bs.pad)       # (Hj, Bp, Kp) i8
    wg = pad_axis(pad_axis(w_q, 1, ks.pad), 2, mp - mj)    # (Hj, Kp, Mp) i8
    bg = pad_axis(bias.reshape(hj, 1, mj), 2, mp - mj, value=NEG)
    sg = jnp.broadcast_to((scale * ACT_SCALE)[:, None, None], (hj, 1, mp))
    out = pl.pallas_call(
        functools.partial(_quant_patchy_kernel, k_steps=ks.grid, gain=gain),
        grid=(hj, bs.grid, ks.grid),
        in_specs=[
            pl.BlockSpec((1, bs.block, ks.block), lambda h, i, k: (h, i, k)),
            pl.BlockSpec((1, ks.block, mp), lambda h, i, k: (h, k, 0)),
            pl.BlockSpec((1, 1, mp), lambda h, i, k: (h, 0, 0)),
            pl.BlockSpec((1, 1, mp), lambda h, i, k: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bs.block, mp), lambda h, i, k: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((hj, bs.padded, mp), x.dtype),
        scratch_shapes=[pltpu.VMEM((bs.block, mp), jnp.int32)],
        interpret=interpret,
    )(xg, wg, bg, sg)
    return out[:, :b, :mj].transpose(1, 0, 2).reshape(b, hj * mj)


@functools.partial(
    jax.jit,
    static_argnames=("mi", "hj", "mj", "gain", "block_b", "block_k",
                     "interpret"),
)
def quant_patchy_forward(
    x: jax.Array,      # (B, Ni)
    w_q: jax.Array,    # (Ni, Hj*Mj) int8 masked dense codes
    bias: jax.Array,   # (Hj*Mj,)
    scale: jax.Array,  # (Hj,)
    table: jax.Array,  # (Hj, nact)
    mi: int,
    hj: int,
    mj: int,
    gain: float = 1.0,
    block_b: int = 128,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """int8 patchy forward over DENSE-resident codes: gather the live
    pre-blocks of the int8 weight matrix per call (masked-out weights are
    exactly code 0, so the gather is exact) and run the compact int8
    kernel.  The dense-resident tier's analogue of ``patchy_forward``."""
    _check_exact_block(block_k)
    b, ni = x.shape
    k_units = table.shape[1] * mi
    bs = pad_spec(b, block_b, INT8_SUBLANE)
    ks = pad_spec(k_units, block_k,
                  LANE if k_units >= LANE else INT8_SUBLANE)
    mp = pad_mc(mj)
    ui = unit_indices(table, mi, ks.pad, sentinel=ni)
    xg = _gather_pre_q(quantize_acts(x), ui, bs.pad)
    w3 = w_q.reshape(ni, hj, mj)
    take = lambda idx, col: jnp.take(col, idx, axis=0, mode="fill",
                                     fill_value=0)
    wg = pad_axis(jax.vmap(take, in_axes=(0, 1))(ui, w3), 2, mp - mj)
    bg = pad_axis(bias.reshape(hj, 1, mj), 2, mp - mj, value=NEG)
    sg = jnp.broadcast_to((scale * ACT_SCALE)[:, None, None], (hj, 1, mp))
    out = pl.pallas_call(
        functools.partial(_quant_patchy_kernel, k_steps=ks.grid, gain=gain),
        grid=(hj, bs.grid, ks.grid),
        in_specs=[
            pl.BlockSpec((1, bs.block, ks.block), lambda h, i, k: (h, i, k)),
            pl.BlockSpec((1, ks.block, mp), lambda h, i, k: (h, k, 0)),
            pl.BlockSpec((1, 1, mp), lambda h, i, k: (h, 0, 0)),
            pl.BlockSpec((1, 1, mp), lambda h, i, k: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bs.block, mp), lambda h, i, k: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((hj, bs.padded, mp), x.dtype),
        scratch_shapes=[pltpu.VMEM((bs.block, mp), jnp.int32)],
        interpret=interpret,
    )(xg, wg, bg, sg)
    return out[:, :b, :mj].transpose(1, 0, 2).reshape(b, hj * mj)
