"""Public jit'd wrappers for the Pallas kernels.

Auto-selects interpret mode off-TPU (this container validates kernels on
CPU via the Pallas interpreter; on a real TPU the same calls compile to
Mosaic).  `fused_forward` and `fused_learn` are the production
implementations behind `ProjSpec(backend="pallas")`: the core's dispatch
point (core/bcpnn_layer.py, DESIGN.md §3) routes every activation /
plasticity call of a pallas-tagged projection here, mirroring the paper's
stream-dataflow configuration, while the pure-jnp reference path plays
the sequential baseline.

Dense vs patchy is chosen here per projection (DESIGN.md §7): projections
with an ``nact`` connectivity budget route ``fused_forward`` through the
compact patchy kernels (kernels/patchy.py), streaming only live
pre-blocks; ``fused_learn`` additionally requires ``spec.patchy_traces``
(patchy plasticity is a semantic choice — silent synapses hold their
traces — not just a schedule).  Block sizes come from each kernel's
padded plan (kernels/tiling.py) unless the caller passes explicit
``block_*`` kwargs.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.bcpnn_layer import (
    InferPack, Projection, ProjSpec, is_compact, is_patchy, update_kernel,
)
from ..core.compact import cached_table
from ..core.traces import Traces
from .bcpnn_fwd import bcpnn_fwd_pallas
from .bcpnn_update import bcpnn_update_pallas
from .hc_softmax import hc_softmax_pallas
from .patchy import compact_forward, compact_update, patchy_forward, patchy_update
from .quant import quant_compact_forward, quant_fwd_pallas, quant_patchy_forward


@functools.lru_cache(maxsize=1)
def _default_backend() -> str:
    # jax.default_backend() initializes the platform on every call; the
    # answer cannot change within a process, so resolve it once.
    return jax.default_backend()


def _interpret() -> bool:
    """Kernels compile to Mosaic on a TPU and run in the Pallas
    interpreter everywhere else; nothing overrides the platform."""
    return _default_backend() != "tpu"


def hc_softmax(support: jax.Array, n_hc: int, n_mc: int, gain: float = 1.0,
               **kw) -> jax.Array:
    return hc_softmax_pallas(support, n_hc, n_mc, gain,
                             interpret=_interpret(), **kw)


def bcpnn_fwd(x: jax.Array, w: jax.Array, bias: jax.Array, n_hc: int,
              n_mc: int, gain: float = 1.0, **kw) -> jax.Array:
    return bcpnn_fwd_pallas(x, w, bias, n_hc, n_mc, gain,
                            interpret=_interpret(), **kw)


def bcpnn_update(pij, log_pi, log_pj, x, y, mask, alpha, n=None, eps=1e-4,
                 **kw):
    return bcpnn_update_pallas(pij, log_pi, log_pj, x, y, mask, alpha, n,
                               eps=eps, interpret=_interpret(), **kw)


# ------------------------------------------------- fused core stages ----

def fused_forward(proj: Projection, spec: ProjSpec, x: jax.Array) -> jax.Array:
    """Kernel-fused equivalent of core.bcpnn_layer.forward.

    Patchy projections stream only the live pre-blocks (exact: masked-out
    weights are zero, so the skipped work contributes nothing).
    Compact-resident projections additionally skip the per-call weight
    gather: the resident (Hj, K, Mj) weights and the persistent index
    table feed the kernel directly."""
    if is_compact(spec) and proj.table is not None:
        return compact_forward(x, proj.w, proj.b, proj.table, spec.pre.M,
                               spec.gain, interpret=_interpret())
    if is_patchy(spec):
        table = cached_table(proj.mask, spec.nact)
        return patchy_forward(
            x, proj.w, proj.b, table, spec.pre.M,
            spec.post.H, spec.post.M, spec.gain,
            interpret=_interpret())
    return bcpnn_fwd(x, proj.w, proj.b, spec.post.H, spec.post.M, spec.gain)


def fused_packed_forward(pack: InferPack, spec: ProjSpec,
                         x: jax.Array) -> jax.Array:
    """Kernel-fused forward from an ``InferPack`` (DESIGN.md §8).

    fp32/bf16 packs route through the same kernels as ``fused_forward``
    (their matmuls cast operands to fp32 in-kernel, so bf16 weights are
    a pure bandwidth win); int8 packs route through the fixed-point
    kernels in kernels/quant.py with the pack's per-HC scales folded
    into the softmax epilogue.  The patchy index table comes from the
    pack — never re-derived from the mask on the serving path."""
    if pack.w.dtype == jnp.int8:
        if pack.w.ndim == 3:  # compact-resident layout
            return quant_compact_forward(
                x, pack.w, pack.b, pack.scale, pack.table, spec.pre.M,
                spec.gain, interpret=_interpret())
        if is_patchy(spec) and pack.table is not None:
            return quant_patchy_forward(
                x, pack.w, pack.b, pack.scale, pack.table, spec.pre.M,
                spec.post.H, spec.post.M, spec.gain,
                interpret=_interpret())
        return quant_fwd_pallas(x, pack.w, pack.b, pack.scale, spec.post.H,
                                spec.post.M, spec.gain,
                                interpret=_interpret())
    if pack.w.ndim == 3:
        return compact_forward(x, pack.w, pack.b, pack.table, spec.pre.M,
                               spec.gain, interpret=_interpret())
    if is_patchy(spec) and pack.table is not None:
        return patchy_forward(
            x, pack.w, pack.b, pack.table, spec.pre.M,
            spec.post.H, spec.post.M, spec.gain,
            interpret=_interpret())
    return bcpnn_fwd(x, pack.w, pack.b, spec.post.H, spec.post.M, spec.gain)


def fused_learn(proj: Projection, spec: ProjSpec, x: jax.Array,
                y: jax.Array, n: Optional[jax.Array] = None) -> Projection:
    """Kernel-fused equivalent of core.bcpnn_layer.learn.

    The cheap vector traces (p_i, p_j) update in plain jnp; the O(Ni·Nj)
    joint-trace EMA + weight recompute run in the fused Pallas kernel
    ``update_kernel(spec)`` names — the compact patchy kernel when the
    projection opted into patchy-trace plasticity (DESIGN.md §7), the
    dense kernel otherwise, which reads the (Hi, Hj) HC mask only for a
    patchy projection (a dense one's mask is all ones).

    ``n`` is the genuine-row count of a zero-padded batch
    (``learn_masked``: pad rows of ``x``/``y`` already zero); every stat
    divides by it.  ``None`` means the whole batch, ``x.shape[0]``.  Only
    the dense kernel takes a runtime count.
    """
    path = update_kernel(spec)
    if n is not None and path != "bcpnn_update":
        raise ValueError(
            f"fused_learn: the {path} kernel divides by the static batch "
            f"size; a runtime row count needs the dense bcpnn_update path")
    if n is None:
        # the jnp reference's own mean (``_learn_jnp``): both backends then
        # round the marginals alike, and rewires rank the same MI
        xm, ym = jnp.mean(x, axis=0), jnp.mean(y, axis=0)
    else:
        xm, ym = jnp.sum(x, axis=0) / n, jnp.sum(y, axis=0) / n
    tr = proj.traces
    a = jnp.maximum(1.0 / (tr.t.astype(jnp.float32) + 1.0), spec.alpha)
    pi = (1.0 - a) * tr.pi + a * xm
    pj = (1.0 - a) * tr.pj + a * ym
    log_pi = jnp.log(jnp.clip(pi, spec.eps, 1.0))
    log_pj = jnp.log(jnp.clip(pj, spec.eps, 1.0))
    if path == "compact_update" and proj.table is None:
        raise ValueError(
            "fused_learn: ProjSpec.compact projection carries a dense-layout "
            "state (no index-table leaf); convert it with "
            "core.compact.compactify_state (or scripts/migrate_ckpt.py) — "
            "the dense-compute reference of the compact semantics lives on "
            "the jnp backend only")
    if path == "compact_update":
        # Scatter-free hot path: the kernel reads and writes the resident
        # compact trace/weights — zero O(Ni·Nj) work per step.
        new_pij, w = compact_update(
            tr.pij, log_pi, log_pj, x, y, proj.table, a, spec.pre.M,
            eps=spec.eps, interpret=_interpret())
    elif path == "patchy_update":
        table = cached_table(proj.mask, spec.nact)
        new_pij, w = patchy_update(
            tr.pij, log_pi, log_pj, x, y, table, a,
            spec.pre.M, spec.post.H, spec.post.M, eps=spec.eps,
            interpret=_interpret())
    else:
        mask = proj.mask if is_patchy(spec) else None
        new_pij, w = bcpnn_update(tr.pij, log_pi, log_pj, x, y, mask, a, n,
                                  eps=spec.eps)
    b = log_pj
    return Projection(
        traces=Traces(pi=pi, pj=pj, pij=new_pij, t=tr.t + 1),
        w=w, b=b, mask=proj.mask, table=proj.table,
    )
