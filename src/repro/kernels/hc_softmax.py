"""Pallas TPU kernel: per-hypercolumn softmax (divisive normalization).

The (padded) minicolumn dimension is kept whole inside each block so the
normalization is block-local; the batch and hypercolumn dimensions tile
the grid.  Operands are padded to aligned blocks (tiling.pad_hc_spec):
pad minicolumn lanes carry ``NEG`` support, so they underflow to zero
probability and leave real softmax sums untouched; pad batch rows and
pad-HCs produce inert values that are sliced off before returning.

``softmax_tile`` is the shared in-kernel epilogue: the fused forward
kernels (bcpnn_fwd.py, quant.py) end in the same per-HC softmax.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .padding import pad_axis, pad_hc_axis, unpad_hc_axis
from .tiling import LANE, NEG, SUBLANE, pad_hc_spec, pad_spec


def _segment_allreduce(v: jax.Array, seg: int, op) -> jax.Array:
    """Reduce ``v`` (tb, tn) with ``op`` over each aligned run of ``seg``
    lanes (``seg`` a power of two below 128) and broadcast the result
    back to every lane of the run.

    A recursive-doubling butterfly: at distance ``d`` each lane combines
    with lane ``i ^ d``, so after log2(seg) steps every lane holds its
    run's reduction, identical across the run.  Lane rotations replace
    the (tb, tn/seg, seg) reshape, which Mosaic cannot lower when it
    splits a 128-lane tile.  Of the two rotations by ``d`` one brings
    lane ``i ^ d`` to lane ``i``; the rotated lane iota picks it, so the
    result does not depend on the rotation's direction convention."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    n = v.shape[1]
    d = 1
    while d < seg:
        fwd = pltpu.roll(v, d, 1)
        bwd = pltpu.roll(v, n - d, 1)
        fwd_is_partner = pltpu.roll(lanes, d, 1) == (lanes ^ d)
        v = op(v, jnp.where(fwd_is_partner, fwd, bwd))
        d *= 2
    return v


def softmax_tile(s: jax.Array, n_mc: int) -> jax.Array:
    """Per-HC softmax of an f32 (tb, tn) tile holding whole HCs of
    ``n_mc`` lanes each (``n_mc`` as planned by ``tiling.pad_mc``: a
    power of two below 128, a multiple of 128 above)."""
    tb, tn = s.shape
    if n_mc % LANE == 0:
        # HCs span whole lane tiles: the 3-D view splits no tile.
        s3 = s.reshape(tb, tn // n_mc, n_mc)
        s3 = s3 - jnp.max(s3, axis=-1, keepdims=True)
        e = jnp.exp(s3)
        return (e / jnp.sum(e, axis=-1, keepdims=True)).reshape(tb, tn)
    e = jnp.exp(s - _segment_allreduce(s, n_mc, jnp.maximum))
    return e / _segment_allreduce(e, n_mc, jnp.add)


def _kernel(s_ref, o_ref, *, n_mc: int, gain: float):
    s = s_ref[...].astype(jnp.float32) * gain          # (tb, th*M)
    o_ref[...] = softmax_tile(s, n_mc).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("n_hc", "n_mc", "gain", "block_b", "block_h", "interpret")
)
def hc_softmax_pallas(
    support: jax.Array,
    n_hc: int,
    n_mc: int,
    gain: float = 1.0,
    block_b: int = 128,
    block_h: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """support: (B, n_hc*n_mc) -> rates, softmax within each HC."""
    b, n = support.shape
    assert n == n_hc * n_mc, (n, n_hc, n_mc)
    bs = pad_spec(b, block_b, SUBLANE)
    hs = pad_hc_spec(n_hc, n_mc, block_h * n_mc)
    s = pad_hc_axis(support, 1, hs, value=NEG)
    s = pad_axis(s, 0, bs.pad)
    grid = (bs.grid, hs.grid)
    out = pl.pallas_call(
        functools.partial(_kernel, n_mc=hs.mc_padded, gain=gain),
        grid=grid,
        in_specs=[pl.BlockSpec((bs.block, hs.block_units), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bs.block, hs.block_units), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bs.padded, hs.padded_units),
                                       support.dtype),
        interpret=interpret,
    )(s)
    return unpad_hc_axis(out[:b], 1, hs)
