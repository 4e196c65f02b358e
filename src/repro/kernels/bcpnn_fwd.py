"""Pallas TPU kernel: fused BCPNN activation stage.

support = bias + x @ w, followed by per-hypercolumn softmax — in ONE
kernel, so the support matrix never exists in HBM.  This is the TPU
translation of the paper's stream-dataflow: the FPGA forwards support
packets from the matmul stage straight into the softmax stage through a
FIFO; here the MXU accumulator feeds the epilogue in VMEM.

Grid = (B/tb, Nj/tj, Ni/tk) over the PADDED shapes, contraction
innermost.  Pad semantics (DESIGN.md §7): batch rows and contraction
columns pad with zeros (inert in the matmul); the post-synaptic unit axis
pads HC-aware — extra minicolumn lanes get zero weight columns and
``NEG`` bias, so they vanish from every real softmax sum.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .hc_softmax import softmax_tile
from .padding import pad_axis, pad_hc_axis, unpad_hc_axis
from .tiling import NEG, SUBLANE, lane_multiple, pad_hc_spec, pad_spec


def _kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, k_steps: int, n_mc: int, gain: float):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32),
        w_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == k_steps - 1)
    def _epilogue():
        s = (acc_ref[...] + b_ref[...]) * gain       # (tb, tj)
        o_ref[...] = softmax_tile(s, n_mc).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("n_hc", "n_mc", "gain", "block_b", "block_j", "block_k", "interpret"),
)
def bcpnn_fwd_pallas(
    x: jax.Array,      # (B, Ni)
    w: jax.Array,      # (Ni, Nj)
    bias: jax.Array,   # (Nj,)
    n_hc: int,
    n_mc: int,
    gain: float = 1.0,
    block_b: int = 128,
    block_j: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    b, ni = x.shape
    nj = w.shape[1]
    assert nj == n_hc * n_mc
    bs = pad_spec(b, block_b, SUBLANE)
    ks = pad_spec(ni, block_k, lane_multiple(ni))
    js = pad_hc_spec(n_hc, n_mc, block_j)  # keep HCs whole in a tile
    xp = pad_axis(pad_axis(x, 1, ks.pad), 0, bs.pad)
    wp = pad_hc_axis(pad_axis(w, 0, ks.pad), 1, js)
    bp = pad_hc_axis(bias.reshape(1, nj), 1, js, value=NEG)
    grid = (bs.grid, js.grid, ks.grid)
    out = pl.pallas_call(
        functools.partial(_kernel, k_steps=ks.grid, n_mc=js.mc_padded,
                          gain=gain),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bs.block, ks.block), lambda i, j, k: (i, k)),
            pl.BlockSpec((ks.block, js.block_units), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, js.block_units), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bs.block, js.block_units),
                               lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bs.padded, js.padded_units), x.dtype),
        scratch_shapes=[pltpu.VMEM((bs.block, js.block_units), jnp.float32)],
        interpret=interpret,
    )(xp, wp, bp)
    return unpad_hc_axis(out[:b], 1, js)
