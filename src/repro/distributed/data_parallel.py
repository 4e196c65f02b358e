"""shard_map data-parallel streaming train steps with an EXACT trace
all-reduce (StreamBrain-style multi-device BCPNN, DESIGN.md §7).

Why this is exact — and exact *to the bit*, not just in exact arithmetic:
batch-mean co-activation traces are linear, so per-device partial traces
sum to the true global trace (the StreamBrain observation, PAPERS.md).
But a batch-SPLIT decomposition (each device contracting its own rows,
then psum) reassociates the f32 reduction — partial1 + partial2 is not
bit-identical to the single-device gemm's accumulation order.  We instead
decompose over POST COLUMNS: the global batch of activations is
all-gathered (O(B·(Ni+Nj)) traffic — tiny next to the O(Ni·Nj) trace
matrices), and each device contracts the FULL batch against its own
post-HC column block.  Every output element is then produced by exactly
one device with the same per-element contraction order as the
single-device gemm, so the trace all-reduce — a ``psum`` of
disjoint-support partials — is a sum of one real value and zeros per
element: exact to the bit.  The forward pass is sharded the same way
(column slices of the support matmul, per-HC softmax block-local), and
exploration noise is generated from the replicated key at full batch
shape and column-sliced, so the whole step reproduces the single-device
``unsupervised_layer_step`` / ``supervised_readout_step`` bit-for-bit.
``tests/test_distributed.py`` asserts exactly that on a ≥2-device CPU
mesh, for dense and compact-resident projections.

Compact-resident projections (``ProjSpec.compact``) shard along the
leading post-HC axis of their (Hj, K, Mj) leaves, which shrinks the
all-reduced partials by the same nact/Hi factor as the resident state —
the distributed win of the compact layout.

Scope: the steps run the jnp reference compute path regardless of
``ProjSpec.backend`` (the fused Pallas kernels tile their grids in ways
that reassociate accumulation, so a kernel-fused DP step is a TPU
follow-up — see ROADMAP); the masked steps mirror
``core.bcpnn_layer._learn_masked_jnp``, the jnp stats of the masked
learn, even where the single-device masked step takes the kernel.  The
readout projection (a single output HC) replicates its tiny learn
instead of sharding it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..core.bcpnn_layer import (
    Projection,
    ProjSpec,
    apply_dense_stats,
    is_compact,
    masked_inputs,
)
from ..core.compact import apply_compact_stats, compact_co_stats, compact_support
from ..core.hypercolumns import LayerGeom, hc_softmax
from ..core.network import DeepState, NetworkSpec


def _check_geometry(spec: NetworkSpec, layer: int, n_shards: int) -> None:
    """The column decomposition needs whole HCs per shard on every
    projection the step touches (readout excluded — it replicates)."""
    for l in range(layer + 1):
        h = spec.projs[l].post.H
        if h % n_shards != 0:
            raise ValueError(
                f"data-parallel step: stack projection {l} has {h} post-HCs,"
                f" not divisible by the {n_shards}-way data axis — the "
                f"column-sharded decomposition needs whole HCs per shard")


def _axis_offset(axis: str, size: int):
    return jax.lax.axis_index(axis) * size


def _support_cols(proj: Projection, pspec: ProjSpec, xf: jax.Array,
                  axis: str, n_shards: int) -> jax.Array:
    """This device's post-column slice of the log-domain support, computed
    with the FULL-batch contraction (bit-identical to the same columns of
    the single-device support)."""
    if is_compact(pspec) and proj.table is not None:
        hj_l = pspec.post.H // n_shards
        off = _axis_offset(axis, hj_l)
        tbl = jax.lax.dynamic_slice_in_dim(proj.table, off, hj_l, 0)
        w_l = jax.lax.dynamic_slice_in_dim(proj.w, off, hj_l, 0)
        b_l = jax.lax.dynamic_slice_in_dim(proj.b, off * pspec.post.M,
                                           hj_l * pspec.post.M, 0)
        # the canonical contraction on sliced leaves — sharing the helper
        # keeps the single-device/DP identical-arithmetic guarantee
        # structural
        return compact_support(xf, w_l, b_l, tbl, pspec.pre.M)
    nj_l = pspec.post.N // n_shards
    off = _axis_offset(axis, nj_l)
    w_l = jax.lax.dynamic_slice_in_dim(proj.w, off, nj_l, 1)
    b_l = jax.lax.dynamic_slice_in_dim(proj.b, off, nj_l, 0)
    return b_l[None, :] + xf @ w_l


def _softmax_cols(s_l: jax.Array, pspec: ProjSpec, n_shards: int) -> jax.Array:
    """Per-HC softmax on a whole-HC column slice: block-local, so it is
    per-element identical to the same columns of the full softmax."""
    geom_l = LayerGeom(pspec.post.H // n_shards, pspec.post.M)
    return hc_softmax(s_l, geom_l, pspec.gain)


def _gather_cols(y_l: jax.Array, axis: str) -> jax.Array:
    return jax.lax.all_gather(y_l, axis, axis=1, tiled=True)


def _forward_cols(proj: Projection, pspec: ProjSpec, xf: jax.Array,
                  axis: str, n_shards: int) -> jax.Array:
    """Full post rates via column-sharded forward + gather."""
    return _gather_cols(_softmax_cols(
        _support_cols(proj, pspec, xf, axis, n_shards), pspec, n_shards),
        axis)


def _co_allreduce_dense(xf: jax.Array, y_l: jax.Array, nj: int, axis: str,
                        n_shards: int) -> jax.Array:
    """Disjoint-support trace all-reduce, dense layout: this device's
    full-batch column gemm scattered into zeros, psum'd.  Each element of
    the result is one real partial plus zeros — bit-exact."""
    xf, y_l = jax.lax.optimization_barrier((xf, y_l))
    part = xf.T @ y_l                                  # (Ni, Nj/n_shards)
    off = _axis_offset(axis, nj // n_shards)
    padded = jax.lax.dynamic_update_slice(
        jnp.zeros((xf.shape[1], nj), part.dtype), part, (0, off))
    return jax.lax.psum(padded, axis)


def _co_allreduce_compact(xf: jax.Array, y_l: jax.Array, proj: Projection,
                          pspec: ProjSpec, axis: str, n_shards: int,
                          n_valid=None) -> jax.Array:
    """Disjoint-support trace all-reduce, compact layout: partials are
    (Hj/n_shards, K, Mj) — nact/Hi smaller than the dense all-reduce.
    The partial is the canonical ``compact_co_stats`` contraction on this
    device's table rows and post columns (already batch-mean, or
    real-row-mean when ``n_valid`` is given), so the reduced result is
    bit-identical to the single-device stat."""
    hj, k_units, mj = proj.traces.pij.shape
    hj_l = hj // n_shards
    off = _axis_offset(axis, hj_l)
    tbl = jax.lax.dynamic_slice_in_dim(proj.table, off, hj_l, 0)
    part = compact_co_stats(xf, y_l, tbl, pspec.pre.M, mj, n_valid=n_valid)
    padded = jax.lax.dynamic_update_slice(
        jnp.zeros((hj, k_units, mj), part.dtype), part, (off, 0, 0))
    return jax.lax.psum(padded, axis)


def _learn_sharded(proj: Projection, pspec: ProjSpec, xf: jax.Array,
                   yf: jax.Array, y_l: jax.Array, axis: str,
                   n_shards: int, valid=None) -> Projection:
    """One plasticity step from all-reduced stats — the replicated EMA +
    fold applies the identical ops as the single-device jnp learn.

    ``valid`` (optional, (B,) 0/1, replicated) is the zero-padded
    tail-batch mask: it mirrors ``core.bcpnn_layer._learn_masked_jnp`` —
    pad rows are zeroed before any stat, and every divisor is the real
    row count.  The column slice of the masked full activations equals
    the masked column slice elementwise, so the disjoint-support
    all-reduce stays bit-exact against the single-device masked learn."""
    if valid is None:
        b = xf.shape[0]
        xf, yf = jax.lax.optimization_barrier((xf, yf))
        xm = jnp.mean(xf, axis=0)
        ym = jnp.mean(yf, axis=0)
        if is_compact(pspec) and proj.table is not None:
            # already batch-mean: compact_co_stats divides inside the partial
            co_c = _co_allreduce_compact(xf, y_l, proj, pspec, axis, n_shards)
            return apply_compact_stats(proj, pspec, xm, ym, co_c)
        co = _co_allreduce_dense(xf, y_l, pspec.post.N, axis, n_shards) / b
        return apply_dense_stats(proj, pspec, xm, ym, co)
    xv, yv, n = masked_inputs(xf, yf, valid)
    v = valid.astype(y_l.dtype)
    yv_l = y_l * v[:, None]
    xv, yv, yv_l = jax.lax.optimization_barrier((xv, yv, yv_l))
    xm = jnp.sum(xv, axis=0) / n
    ym = jnp.sum(yv, axis=0) / n
    if is_compact(pspec) and proj.table is not None:
        co_c = _co_allreduce_compact(xv, yv_l, proj, pspec, axis, n_shards,
                                     n_valid=n)
        return apply_compact_stats(proj, pspec, xm, ym, co_c)
    co = _co_allreduce_dense(xv, yv_l, pspec.post.N, axis, n_shards) / n
    return apply_dense_stats(proj, pspec, xm, ym, co)


def _learn_replicated(proj: Projection, pspec: ProjSpec, xf: jax.Array,
                      yf: jax.Array, valid=None) -> Projection:
    """Tiny projections (the single-HC readout) learn replicated: every
    device runs the identical full gemm — trivially bit-exact."""
    from ..core.bcpnn_layer import _learn_jnp, _learn_masked_jnp
    if valid is not None:
        return _learn_masked_jnp(proj, pspec, xf, yf, valid)
    return _learn_jnp(proj, pspec, xf, yf)


def _train_projection_body(state: DeepState, spec: NetworkSpec, layer: int,
                           h: jax.Array, axis: str, n_shards: int,
                           valid=None) -> DeepState:
    """The column-sharded equivalent of
    ``core.network.train_projection_step`` on the layer's DIRECT input
    rates ``h`` (full batch, replicated) — shared by the per-batch step
    factory (which derives ``h`` from row-sharded input via the frozen
    column forwards) and the scan-over-batches epoch factories, so both
    compile the identical barrier-pinned arithmetic."""
    pspec = spec.projs[layer]
    proj = state.projs[layer]
    key, sub = jax.random.split(state.key)
    s_l = _support_cols(proj, pspec, h, axis, n_shards)
    t = proj.traces.t.astype(jnp.float32)
    amp = pspec.support_noise * jnp.maximum(
        0.0, 1.0 - t / max(1, pspec.noise_steps))
    # Mirror _noisy_rates' pins: one materialized noise buffer, pinned
    # scaled product — the column slice then adds the same bits.
    noise = jax.lax.optimization_barrier(jax.random.normal(
        sub, (h.shape[0], pspec.post.N), s_l.dtype))
    nj_l = pspec.post.N // n_shards
    noise_l = jax.lax.dynamic_slice_in_dim(
        noise, _axis_offset(axis, nj_l), nj_l, 1)
    y_l = _softmax_cols(
        s_l + jax.lax.optimization_barrier(amp * noise_l), pspec,
        n_shards)
    yf = _gather_cols(y_l, axis)
    proj = _learn_sharded(proj, pspec, h, yf, y_l, axis, n_shards,
                          valid=valid)
    if pspec.struct_every > 0:
        from ..core.bcpnn_layer import rewire
        proj = jax.lax.cond(
            proj.traces.t % pspec.struct_every == 0,
            lambda p: rewire(p, pspec), lambda p: p, proj)
    projs = state.projs[:layer] + (proj,) + state.projs[layer + 1:]
    return DeepState(projs=projs, readout=state.readout,
                     step=state.step + 1, key=key)


def _supervised_body(state: DeepState, spec: NetworkSpec, xf: jax.Array,
                     labels: jax.Array, axis: str, n_shards: int,
                     valid=None) -> DeepState:
    """Column-sharded frozen stack forward + replicated readout learn on
    full-batch inputs — shared by the supervised step and epoch."""
    h = xf
    for l in range(spec.depth):
        h = _forward_cols(state.projs[l], spec.projs[l], h, axis, n_shards)
    y = jax.nn.one_hot(labels, spec.n_classes, dtype=h.dtype)
    ro = _learn_replicated(state.readout, spec.readout, h, y, valid=valid)
    return DeepState(projs=state.projs, readout=ro,
                     step=state.step + 1, key=state.key)


def make_data_parallel_unsupervised_step(spec: NetworkSpec, mesh: Mesh,
                                         layer: int = 0, axis: str = "data"):
    """Build the jitted shard_map equivalent of
    ``core.network.unsupervised_layer_step`` for a data mesh.

    Inputs: ``state`` replicated, ``x`` (B, Ni) sharded over rows on
    ``axis`` (B divisible by the axis size).  Output state is replicated
    and matches the single-device step bit-for-bit.
    """
    n_shards = mesh.shape[axis]
    _check_geometry(spec, layer, n_shards)

    def step(state: DeepState, x_l: jax.Array) -> DeepState:
        xf = jax.lax.all_gather(x_l, axis, tiled=True)
        h = xf
        for l in range(layer):
            h = _forward_cols(state.projs[l], spec.projs[l], h, axis,
                              n_shards)
        return _train_projection_body(state, spec, layer, h, axis, n_shards)

    return jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(P(), P(axis)), out_specs=P(),
        check_vma=False))


def make_data_parallel_supervised_step(spec: NetworkSpec, mesh: Mesh,
                                       axis: str = "data"):
    """Build the jitted shard_map equivalent of
    ``core.network.supervised_readout_step``: column-sharded frozen stack
    forward, replicated readout learn (one output HC — nothing to shard).
    ``labels`` (B,) int32, sharded over ``axis`` like ``x``."""
    n_shards = mesh.shape[axis]
    _check_geometry(spec, spec.depth - 1, n_shards)

    def step(state: DeepState, x_l: jax.Array,
             labels_l: jax.Array) -> DeepState:
        xf = jax.lax.all_gather(x_l, axis, tiled=True)
        labels = jax.lax.all_gather(labels_l, axis, tiled=True)
        return _supervised_body(state, spec, xf, labels, axis, n_shards)

    return jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(P(), P(axis), P(axis)), out_specs=P(),
        check_vma=False))


# ------------------------------------------- scan-over-batches epochs ----

def make_data_parallel_projection_epoch(spec: NetworkSpec, mesh: Mesh,
                                        layer: int = 0, axis: str = "data",
                                        masked: bool = False):
    """Build the jitted shard_map equivalent of
    ``core.trainer._train_projection_epoch``: one ``lax.scan`` over
    batch-major PRECOMPUTED layer-input rates ``hs`` (nb, B, N_layer),
    sharded over batch rows on ``axis`` — a whole greedy-phase epoch is
    one device program, like the single-device trainer.

    With ``masked=True`` the epoch takes an extra ``valid`` (nb, B) 0/1
    operand (replicated — every device needs the full-batch mask because
    stats contract the full gathered batch) and runs the real-row-count
    masked learn on every batch; the trainer passes it only when the
    data actually has a padded tail.  Per-step arithmetic is the same
    barrier-pinned body as ``make_data_parallel_unsupervised_step``, so
    the epoch is bit-for-bit equal to the single-device epoch program.
    """
    n_shards = mesh.shape[axis]
    _check_geometry(spec, layer, n_shards)

    if masked:
        def epoch(state: DeepState, hs_l: jax.Array,
                  valid: jax.Array) -> DeepState:
            def body(st, hv):
                h_l, v = hv
                hf = jax.lax.all_gather(h_l, axis, tiled=True)
                return _train_projection_body(
                    st, spec, layer, hf, axis, n_shards, valid=v), None
            state, _ = jax.lax.scan(body, state, (hs_l, valid))
            return state

        in_specs = (P(), P(None, axis), P())
    else:
        def epoch(state: DeepState, hs_l: jax.Array) -> DeepState:
            def body(st, h_l):
                hf = jax.lax.all_gather(h_l, axis, tiled=True)
                return _train_projection_body(
                    st, spec, layer, hf, axis, n_shards), None
            state, _ = jax.lax.scan(body, state, hs_l)
            return state

        in_specs = (P(), P(None, axis))

    return jax.jit(jax.shard_map(
        epoch, mesh=mesh, in_specs=in_specs, out_specs=P(),
        check_vma=False))


def make_data_parallel_supervised_epoch(spec: NetworkSpec, mesh: Mesh,
                                        axis: str = "data",
                                        masked: bool = False):
    """Build the jitted shard_map equivalent of
    ``core.trainer._supervised_epoch``: one scan over batch-major
    ``(xs, ys)`` (row-sharded on ``axis``), plus a replicated ``valid``
    operand when ``masked``."""
    n_shards = mesh.shape[axis]
    _check_geometry(spec, spec.depth - 1, n_shards)

    def _step(st, x_l, labels_l, v):
        xf = jax.lax.all_gather(x_l, axis, tiled=True)
        labels = jax.lax.all_gather(labels_l, axis, tiled=True)
        return _supervised_body(st, spec, xf, labels, axis, n_shards,
                                valid=v)

    if masked:
        def epoch(state: DeepState, xs_l: jax.Array, ys_l: jax.Array,
                  valid: jax.Array) -> DeepState:
            def body(st, xyv):
                x_l, labels_l, v = xyv
                return _step(st, x_l, labels_l, v), None
            state, _ = jax.lax.scan(body, state, (xs_l, ys_l, valid))
            return state

        in_specs = (P(), P(None, axis), P(None, axis), P())
    else:
        def epoch(state: DeepState, xs_l: jax.Array,
                  ys_l: jax.Array) -> DeepState:
            def body(st, xy):
                x_l, labels_l = xy
                return _step(st, x_l, labels_l, None), None
            state, _ = jax.lax.scan(body, state, (xs_l, ys_l))
            return state

        in_specs = (P(), P(None, axis), P(None, axis))

    return jax.jit(jax.shard_map(
        epoch, mesh=mesh, in_specs=in_specs, out_specs=P(),
        check_vma=False))
