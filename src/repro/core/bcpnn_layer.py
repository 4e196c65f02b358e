"""BCPNN projection: a plastic, patchily-connected weight matrix between
two hypercolumnar populations, plus its probability traces.

This is the unit of work the paper's accelerator streams: activation
(support matmul + HC softmax) and plasticity (trace EMA + log-weight
recompute).  Each projection carries a ``backend`` tag in its spec:

  * ``"jnp"``    — the pure-jnp reference path implemented in this module
                   (XLA fuses it within one jit; the "sequential" baseline
                   of the paper's §4.1 comparison);
  * ``"pallas"`` — the fused stream-dataflow kernels in ``kernels/``
                   (Mosaic on TPU, interpret mode elsewhere), the
                   production hot path.

``forward`` / ``support`` / ``learn`` below are the single dispatch
point: every caller (the deep network engine, the trainer, the serving
engine) routes through them, so flipping ``ProjSpec.backend`` swaps the
whole execution stack per projection.  See DESIGN.md §3.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from .hypercolumns import LayerGeom, hc_softmax
from .traces import Traces, init_traces, mutual_information, weights_from_traces

BACKENDS = ("jnp", "pallas")

# Serving dtypes of the dtype-polymorphic inference path (DESIGN.md §8).
# Learning state is fp32 regardless; ``infer_dtype`` governs only the
# derived inference weights a fold produces (``pack_projection``).
INFER_DTYPES = ("fp32", "bf16", "int8")


@dataclasses.dataclass(frozen=True)
class ProjSpec:
    """Static configuration of a projection.

    The trailing fields are per-projection training knobs used by the
    deep engine (core/network.py): exploration noise on the post support
    during unsupervised learning (annealed over ``noise_steps`` trace
    updates) and the structural-plasticity rewire period.
    """

    pre: LayerGeom
    post: LayerGeom
    alpha: float = 1e-3        # trace smoothing = dt / tau_p
    eps: float = 1e-4          # probability floor
    gain: float = 1.0          # softmax gain on support
    nact: Optional[int] = None  # active pre-HCs per post-HC (None = dense)
    backend: str = "jnp"       # "jnp" reference | "pallas" fused kernels
    support_noise: float = 0.0  # exploration noise amplitude (unsup. only)
    noise_steps: int = 0       # anneal horizon in trace updates
    struct_every: int = 0      # rewire period in trace updates (0 = off)
    patchy_traces: bool = False  # patchy plasticity: silent synapses carry
    #                              no dense joint trace (DESIGN.md §7)
    compact: bool = False      # compact-RESIDENT state: pij/w stored as
    #                            (Hj, K, Mj) + index-table leaf; the learn
    #                            path never materializes (Ni, Nj)
    infer_dtype: str = "fp32"  # serving dtype of the derived inference
    #                            weights: fp32 | bf16 (cast-on-fold) |
    #                            int8 (per-HC quantized); DESIGN.md §8

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r}; "
                             f"expected one of {BACKENDS}")
        if self.infer_dtype not in INFER_DTYPES:
            raise ValueError(f"unknown infer_dtype {self.infer_dtype!r}; "
                             f"expected one of {INFER_DTYPES}")
        if self.compact and not (self.patchy_traces and is_patchy(self)):
            raise ValueError(
                "ProjSpec.compact requires patchy_traces=True and a binding "
                f"nact budget (got nact={self.nact}, pre.H={self.pre.H}, "
                f"patchy_traces={self.patchy_traces}); only nact-budgeted "
                "patchy-trace projections have a compact (Hj, K, Mj) form")

    def with_backend(self, backend: str) -> "ProjSpec":
        return dataclasses.replace(self, backend=backend)

    def with_infer_dtype(self, infer_dtype: str) -> "ProjSpec":
        return dataclasses.replace(self, infer_dtype=infer_dtype)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Projection:
    """Learnable state of a projection (a pytree).

    Two layouts share this container (DESIGN.md §7): the dense layout
    (``w``/``traces.pij`` are (Ni, Nj), ``table`` is None) and the
    compact-resident layout of ``ProjSpec.compact`` projections
    (``w``/``traces.pij`` are (Hj, K, Mj) with K = nact·Mi, and ``table``
    holds the (Hj, nact) active-pre-HC indices — persistent state, rebuilt
    only by ``rewire``).
    """

    traces: Traces
    w: jax.Array     # (Ni, Nj) masked | (Hj, K, Mj) compact log-odds weights
    b: jax.Array     # (Nj,)    log-prior biases
    mask: jax.Array  # (Hi, Hj) float {0,1} structural connectivity
    table: Optional[jax.Array] = None  # (Hj, nact) int32, compact only


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class InferPack:
    """Derived, forward-only view of one projection in its serving dtype
    (DESIGN.md §8) — what a serve model slot actually reads per request.

    Built by ``pack_projection`` from the fp32 state at fold boundaries
    (after feedback folds and ``struct_every`` rewires, never
    per-request): ``w`` is the inference weight matrix cast (bf16) or
    per-post-HC quantized (int8, with ``scale``), in the dense (Ni, Nj)
    or compact (Hj, K, Mj) layout of its projection; ``table`` carries
    the patchy index table as *data*, so the jitted serving forward never
    re-derives it from the mask.  fp32 packs alias the projection's own
    arrays — packing is free when nothing is quantized.
    """

    w: jax.Array                       # weights in the serving dtype
    b: jax.Array                       # (Nj,) log-prior bias
    scale: Optional[jax.Array] = None  # (Hj,) per-post-HC scales, int8 only
    table: Optional[jax.Array] = None  # (Hj, nact), patchy only


def is_patchy(spec: ProjSpec) -> bool:
    """True when the projection has a binding connectivity budget."""
    return spec.nact is not None and spec.nact < spec.pre.H


def is_compact(spec: ProjSpec) -> bool:
    """True when the projection keeps its state compact-resident."""
    return spec.compact


def update_kernel(spec: ProjSpec) -> str:
    """The fused kernel ``fused_learn`` runs for ``spec``: the compact
    kernel for a compact-resident projection, the patchy kernel for
    patchy-trace plasticity, the dense ``bcpnn_update`` otherwise."""
    if is_compact(spec):
        return "compact_update"
    if is_patchy(spec) and spec.patchy_traces:
        return "patchy_update"
    return "bcpnn_update"


def learn_path(spec: ProjSpec, masked: bool = False) -> str:
    """The update path one step of ``learn`` (``masked``: of
    ``learn_masked``) runs for ``spec``: a fused kernel's name, or
    ``"jnp"``.  The one decision both dispatches and the trainer's
    ``trainer.epoch`` span read.  Only the dense kernel takes a runtime
    row count, so the masked step of a compact or patchy-trace projection
    keeps the jnp stats."""
    if spec.backend != "pallas":
        return "jnp"
    kernel = update_kernel(spec)
    if masked and kernel != "bcpnn_update":
        return "jnp"
    return kernel


def _compact_ops():
    # Lazy for the same reason as _pallas_ops: core.compact imports this
    # module for the Projection pytree type.
    from . import compact
    return compact


def validate_patchy_mask(mask, spec: ProjSpec, where: str = "projection") -> None:
    """Host-side guard (concrete arrays only — do NOT call under jit):
    the compact patchy kernels assume the exactly-nact mask invariant
    (``topk_mask``); a column with MORE live pre-HCs would be silently
    truncated by the index table.  Masks written by this codebase always
    satisfy it, but checkpoints predating the exactly-nact fix (or
    hand-built states) may not — fail loudly at the deployment boundary
    instead of serving wrong probabilities."""
    if not is_patchy(spec):
        return
    import numpy as np
    per_col = np.asarray(jax.device_get(mask)).sum(axis=0)
    if (per_col > spec.nact).any():
        bad = int(per_col.max())
        raise ValueError(
            f"{where}: patchy mask has a column with {bad} active pre-HCs, "
            f"exceeding nact={spec.nact}; the compact kernels would drop "
            f"connections. Rebuild the mask with topk_mask (e.g. rewire) "
            f"before serving.")


def validate_patchy_state(proj: Projection, spec: ProjSpec,
                          where: str = "projection") -> None:
    """Host-side deployment guard over the whole projection state
    (concrete arrays only — do NOT call under jit): the mask invariant of
    ``validate_patchy_mask`` plus, for compact-resident projections, that
    the persistent index table exists, has the compact shapes, and agrees
    with the mask — a table that drifted from its mask (hand-edited state,
    a bad migration) would serve through the WRONG synapses silently."""
    validate_patchy_mask(proj.mask, spec, where=where)
    if not is_compact(spec):
        return
    import numpy as np
    hj, mj = spec.post.H, spec.post.M
    k = spec.nact * spec.pre.M
    if proj.table is None:
        raise ValueError(
            f"{where}: compact-resident projection has no index table "
            f"leaf; was this state built dense? Migrate it with "
            f"scripts/migrate_ckpt.py.")
    for name, leaf, want in (("pij", proj.traces.pij, (hj, k, mj)),
                             ("w", proj.w, (hj, k, mj)),
                             ("table", proj.table, (hj, spec.nact))):
        if tuple(leaf.shape) != want:
            raise ValueError(
                f"{where}: compact leaf {name} has shape "
                f"{tuple(leaf.shape)}, expected {want}")
    if not _compact_ops().table_matches_mask(proj.mask, proj.table,
                                             spec.nact):
        mask = np.asarray(jax.device_get(proj.mask))
        table = np.asarray(jax.device_get(proj.table))
        for j in range(hj):
            live = np.flatnonzero(mask[:, j])
            if not np.array_equal(np.sort(table[j]), live):
                raise ValueError(
                    f"{where}: compact index table disagrees with the mask "
                    f"at post-HC {j} (table {np.sort(table[j]).tolist()} vs "
                    f"mask {live.tolist()}); rebuild the table from the "
                    f"mask (core.compact.build_table) before serving.")
        raise ValueError(
            f"{where}: compact index table disagrees with the mask; "
            f"rebuild it from the mask (core.compact.build_table) before "
            f"serving.")


def apply_hc_mask(w: jax.Array, mask: jax.Array, spec: ProjSpec) -> jax.Array:
    """Mask a (Ni, Nj) unit matrix with the (Hi, Hj) HC-level mask.

    Broadcast through the (Hi, Mi, Hj, Mj) view instead of materializing a
    repeated (Ni, Nj) unit mask: XLA fuses the broadcast into the multiply,
    so no O(Ni·Nj) mask array ever exists — the old ``jnp.repeat`` chain
    rebuilt one on every learn call.
    """
    hi, mi, hj, mj = spec.pre.H, spec.pre.M, spec.post.H, spec.post.M
    w4 = w.reshape(hi, mi, hj, mj) * mask[:, None, :, None]
    return w4.reshape(spec.pre.N, spec.post.N)


def topk_mask(scores: jax.Array, k: int) -> jax.Array:
    """Exactly-k column mask: scores (Hi, Hj) -> float {0,1} mask with
    exactly ``k`` ones per post-HC column.

    A threshold test (``scores >= kth_value``) admits *every* pre-HC tied
    at the cutoff, silently exceeding the ``nact`` connectivity budget —
    common early in training, when many HC pairs share identical ~0 MI.
    ``jax.lax.top_k`` returns k distinct indices (ties broken by index
    order), so the scattered one-hots sum to exactly k per column.
    """
    _, idx = jax.lax.top_k(scores.T, k)  # (Hj, k) distinct row indices
    hot = jax.nn.one_hot(idx, scores.shape[0], dtype=jnp.float32)
    return jnp.sum(hot, axis=1).T  # (Hi, Hj)


def init_projection(spec: ProjSpec, key: jax.Array) -> Projection:
    """Uniform-prior traces + random initial receptive fields.

    With nact set, each post-HC starts with a random subset of nact pre-HCs
    active (the paper's "sparse, patchy connectivity"); structural
    plasticity later rewires this mask toward high-MI inputs (Fig. 5).
    """
    k_tr, key = jax.random.split(key)
    tr = init_traces(spec.pre.N, spec.post.N, spec.pre.M, spec.post.M, key=k_tr)
    if spec.nact is None or spec.nact >= spec.pre.H:
        mask = jnp.ones((spec.pre.H, spec.post.H), jnp.float32)
    else:
        scores = jax.random.uniform(key, (spec.pre.H, spec.post.H))
        mask = topk_mask(scores, spec.nact)
    w, b = weights_from_traces(tr, spec.eps)
    w = apply_hc_mask(w, mask, spec)
    proj = Projection(traces=tr, w=w, b=b, mask=mask)
    if is_compact(spec):
        # Same dense init (same key -> same active values), then gathered:
        # compact and dense references start in lockstep on active entries.
        proj = _compact_ops().compactify_projection(proj, spec)
    return proj


# ------------------------------------------------------------- dispatch --

def _pallas_ops():
    # Imported lazily: kernels.ops imports this module for the pytree
    # types, so the dependency must point one way at import time.
    from ..kernels import ops
    return ops


def _quant_ops():
    # Lazy like _pallas_ops: kernels.quant imports core.compact.
    from ..kernels import quant
    return quant


def forward(proj: Projection, spec: ProjSpec, x: jax.Array) -> jax.Array:
    """Activation stage: rates -> post-synaptic rates.   x: (B, Ni)."""
    if spec.backend == "pallas":
        return _pallas_ops().fused_forward(proj, spec, x)
    return _forward_jnp(proj, spec, x)


def support(proj: Projection, spec: ProjSpec, x: jax.Array) -> jax.Array:
    """Log-domain support only (used by readout/inference and the noisy
    unsupervised path).  A bare matmul has no fusion epilogue to win, so
    both backends share the jnp implementation; it is kept behind the
    dispatch point so a future support-only kernel slots in here.
    Compact-resident projections contract against the resident (Hj, K,
    Mj) weights instead of a dense matmul."""
    # Accept low-precision weight operands (the bf16 cast-on-fold tier
    # feeds this reference too): contract and accumulate in fp32.
    w = proj.w if proj.w.dtype == jnp.float32 else proj.w.astype(jnp.float32)
    b = proj.b if proj.b.dtype == jnp.float32 else proj.b.astype(jnp.float32)
    if is_compact(spec) and proj.table is not None:
        return _compact_ops().compact_support(x, w, b, proj.table,
                                              spec.pre.M)
    return b[None, :] + x @ w


def normalize(support_vals: jax.Array, spec: ProjSpec) -> jax.Array:
    """Divisive normalization of a post-population support matrix."""
    if spec.backend == "pallas":
        return _pallas_ops().hc_softmax(
            support_vals, spec.post.H, spec.post.M, spec.gain)
    return hc_softmax(support_vals, spec.post, spec.gain)


def learn(proj: Projection, spec: ProjSpec, x: jax.Array, y: jax.Array) -> Projection:
    """Plasticity stage: one streaming batch update of traces + weights."""
    if spec.backend == "pallas":
        return _pallas_ops().fused_learn(proj, spec, x, y)
    if is_compact(spec) and proj.table is not None:
        return _compact_ops().learn_compact_jnp(proj, spec, x, y)
    return _learn_jnp(proj, spec, x, y)


# ------------------------------------------- packed (serving) dispatch ----

def pack_projection(proj: Projection, spec: ProjSpec) -> InferPack:
    """Derive the forward-only ``InferPack`` of one projection from its
    fp32 state, in ``spec.infer_dtype`` — the fold-boundary half of the
    precision contract (DESIGN.md §8).  Callers decide the cadence: the
    serving engine packs after every feedback fold / rewire; ``infer``
    packs inline (per jit trace) for honest low-precision evaluation.

    Patchy projections get their index table attached here: from the
    persistent leaf (compact-resident) or via the mask-identity memo
    (``cached_table`` — dense-resident states pack on concrete arrays at
    fold boundaries, so the table is rebuilt only when the mask actually
    changed, i.e. on rewire)."""
    table = proj.table
    if table is None and is_patchy(spec):
        table = _compact_ops().cached_table(proj.mask, spec.nact)
    if spec.infer_dtype == "bf16":
        return InferPack(w=proj.w.astype(jnp.bfloat16),
                         b=proj.b.astype(jnp.bfloat16), table=table)
    if spec.infer_dtype == "int8":
        q = _quant_ops()
        if proj.w.ndim == 3:
            w_q, scale = q.quantize_compact(proj.w)
        else:
            w_q, scale = q.quantize_dense(proj.w, spec.post.H, spec.post.M)
        return InferPack(w=w_q, b=proj.b, scale=scale, table=table)
    return InferPack(w=proj.w, b=proj.b, table=table)


def packed_forward(pack: InferPack, spec: ProjSpec, x: jax.Array) -> jax.Array:
    """Activation stage from an ``InferPack`` — same dispatch contract as
    ``forward`` but over the serving-dtype weights."""
    if spec.backend == "pallas":
        return _pallas_ops().fused_packed_forward(pack, spec, x)
    return hc_softmax(packed_support(pack, spec, x), spec.post, spec.gain)


def packed_support(pack: InferPack, spec: ProjSpec, x: jax.Array) -> jax.Array:
    """Log-domain support from an ``InferPack``: fp32/bf16 contract in
    fp32; int8 runs the fixed-point reference arithmetic (quantized
    activations, scale-folded dequant).  Always returns fp32."""
    if pack.w.dtype == jnp.int8:
        q = _quant_ops()
        if pack.w.ndim == 3:
            return q.quant_support_compact_jnp(x, pack.w, pack.scale, pack.b,
                                               pack.table, spec.pre.M)
        return q.quant_support_dense_jnp(x, pack.w, pack.scale, pack.b,
                                         spec.post.H, spec.post.M)
    w = pack.w if pack.w.dtype == jnp.float32 else pack.w.astype(jnp.float32)
    b = pack.b if pack.b.dtype == jnp.float32 else pack.b.astype(jnp.float32)
    if pack.w.ndim == 3:
        return _compact_ops().compact_support(x, w, b, pack.table, spec.pre.M)
    return b[None, :] + x @ w


# ------------------------------------------------------ jnp reference ----

def _forward_jnp(proj: Projection, spec: ProjSpec, x: jax.Array) -> jax.Array:
    s = support(proj, spec, x)
    return hc_softmax(s, spec.post, spec.gain)


def apply_dense_stats(proj: Projection, spec: ProjSpec, xm: jax.Array,
                      ym: jax.Array, co: jax.Array) -> Projection:
    """EMA + plasticity semantics + weight fold on dense-layout state from
    precomputed batch statistics — the single implementation behind
    ``_learn_jnp`` and the data-parallel step (which all-reduces the
    stats first, distributed/data_parallel.py), mirroring
    ``core.compact.apply_compact_stats`` for the compact layout.  Keeping
    one copy makes the single-device/DP shared-arithmetic guarantee
    structural."""
    from .traces import update_traces_from_stats

    tr = update_traces_from_stats(proj.traces, xm, ym, co, spec.alpha)
    if is_patchy(spec) and spec.patchy_traces:
        hi, mi, hj, mj = spec.pre.H, spec.pre.M, spec.post.H, spec.post.M
        keep = proj.mask[:, None, :, None] > 0
        if is_compact(spec):
            # Compact semantics (DESIGN.md §7): a silent synapse carries no
            # evidence — its joint probability IS the independence product
            # p_i·p_j (weight 0), recomputed from the current marginals, so
            # the dense state is a pure function of what the compact layout
            # stores (and rewire ranks silent HC pairs at exactly 0 MI).
            off = jnp.outer(tr.pi, tr.pj).reshape(hi, mi, hj, mj)
        else:
            # Patchy-held semantics: silent synapses HOLD their last
            # joint-trace value (the memory-capped hardware model of the
            # dense-resident patchy path).
            off = proj.traces.pij.reshape(hi, mi, hj, mj)
        pij = jnp.where(keep, tr.pij.reshape(hi, mi, hj, mj), off)
        tr = Traces(pi=tr.pi, pj=tr.pj,
                    pij=pij.reshape(spec.pre.N, spec.post.N), t=tr.t)
    w, b = weights_from_traces(tr, spec.eps)
    w = apply_hc_mask(w, proj.mask, spec)
    return Projection(traces=tr, w=w, b=b, mask=proj.mask, table=proj.table)


def _learn_jnp(proj: Projection, spec: ProjSpec, x: jax.Array, y: jax.Array) -> Projection:
    """Dense-layout reference of all three plasticity semantics: dense
    traces, patchy-held traces, and (for a ``compact`` spec on a
    dense-layout state) the compact semantics computed densely — the
    oracle the scatter-free compact paths are tested against."""
    x, y = jax.lax.optimization_barrier((x, y))  # see update_traces
    b = x.shape[0]
    return apply_dense_stats(proj, spec, jnp.mean(x, axis=0),
                             jnp.mean(y, axis=0), (x.T @ y) / b)


def masked_inputs(x: jax.Array, y: jax.Array, valid: jax.Array):
    """Pin and pad-zero one masked stat seam: returns ``(xv, yv, n)``
    where rows with ``valid == 0`` are zeroed and ``n`` is the REAL row
    count (clamped to 1 so an all-pad batch stays finite).

    The inert-pad contract mirrors ``kernels/padding.py``: a pad row
    contributes exact zeros to every sum, so dividing the sums by ``n``
    IS the mean over genuine rows.  The barrier pins the mask products so
    the single-device and data-parallel masked programs multiply the same
    materialized buffers (distributed/data_parallel.py mirrors this seam)."""
    x, y, valid = jax.lax.optimization_barrier((x, y, valid))
    v = valid.astype(x.dtype)
    n = jnp.maximum(jnp.sum(v), 1.0)
    xv = x * v[:, None]
    yv = y * v[:, None]
    return xv, yv, n


def learn_masked(proj: Projection, spec: ProjSpec, x: jax.Array,
                 y: jax.Array, valid: jax.Array) -> Projection:
    """Plasticity step over a zero-padded batch: batch stats divide by the
    number of GENUINE rows (``valid`` 0/1 per row), so pad slots are inert
    rather than diluting the traces.

    A fit whose data does not divide the batch runs this on EVERY step of
    its masked epoch program, not only on the tail.  On the pallas backend
    a dense or dense-resident patchy projection takes the fused update
    kernel with the real row count as a runtime operand, like ``learn``;
    with valid all ones it computes exactly what ``learn`` does (``x * 1``
    is exact and ``n`` is the batch size).  Compact and patchy-trace
    projections, whose kernels bake a static divisor, and the jnp backend
    compute the stats in jnp (``_learn_masked_jnp``).  ``learn_path(spec,
    masked=True)`` names the path."""
    if learn_path(spec, masked=True) == "bcpnn_update":
        xv, yv, n = masked_inputs(x, y, valid)
        return _pallas_ops().fused_learn(proj, spec, xv, yv, n=n)
    return _learn_masked_jnp(proj, spec, x, y, valid)


def _learn_masked_jnp(proj: Projection, spec: ProjSpec, x: jax.Array,
                      y: jax.Array, valid: jax.Array) -> Projection:
    """The jnp stats of ``learn_masked``, whatever the backend: the
    reference of the masked step, and what the data-parallel steps mirror
    (distributed/data_parallel.py), so padded fits stay bit-exact across
    meshes."""
    xv, yv, n = masked_inputs(x, y, valid)
    xv, yv = jax.lax.optimization_barrier((xv, yv))
    xm = jnp.sum(xv, axis=0) / n
    ym = jnp.sum(yv, axis=0) / n
    if is_compact(spec) and proj.table is not None:
        co_c = _compact_ops().compact_co_stats(
            xv, yv, proj.table, spec.pre.M, spec.post.M, n_valid=n)
        return _compact_ops().apply_compact_stats(proj, spec, xm, ym, co_c)
    co = (xv.T @ yv) / n
    return apply_dense_stats(proj, spec, xm, ym, co)


def maybe_rewire(proj: Projection, spec: ProjSpec) -> Projection:
    """Trace-counter-keyed structural plasticity: rewire when the
    projection's own trace clock hits a ``struct_every`` multiple, else
    pass through.  jit-safe (``lax.cond``), and the one rewire entry both
    the trainer's unsupervised step and the serving engine's
    online-learning fold go through — ``rewire`` rebuilds the mask AND
    (for compact-resident projections) the index-table leaf together, so
    a state that passed ``validate_patchy_state`` at deployment keeps its
    invariants across any number of in-deployment rewires."""
    if spec.struct_every <= 0:
        return proj
    return jax.lax.cond(
        proj.traces.t % spec.struct_every == 0,
        lambda p: rewire(p, spec),
        lambda p: p,
        proj,
    )


def rewire(proj: Projection, spec: ProjSpec) -> Projection:
    """Structural plasticity: keep the top-nact highest-MI pre-HCs per
    post-HC.  Fully on-device (beyond-paper: the paper did this on the host
    and paid a measured total-time penalty on small datasets).  Cold path:
    runs every ``struct_every`` steps, so it stays pure jnp on both
    backends.  Rewire is also where the patchy index tables turn over:
    this produces a NEW mask array, which invalidates the identity-keyed
    table memo of dense-resident projections (core.compact.cached_table),
    and ``rewire_compact`` rebuilds the persistent table leaf of
    compact-resident ones — nothing else may rebuild or mutate them.
    Compact-resident projections densify their joint trace here (the one
    O(Ni·Nj) touch of the compact layout, on the cold path only) so
    rewiring ranks over the same MI scores as the dense reference."""
    if spec.nact is None or spec.nact >= spec.pre.H:
        return proj
    if is_compact(spec) and proj.table is not None:
        return _compact_ops().rewire_compact(proj, spec)
    mi = mutual_information(
        proj.traces, spec.pre.H, spec.pre.M, spec.post.H, spec.post.M, spec.eps
    )  # (Hi, Hj)
    mask = topk_mask(mi, spec.nact)
    w, b = weights_from_traces(proj.traces, spec.eps)
    w = apply_hc_mask(w, mask, spec)
    return Projection(traces=proj.traces, w=w, b=b, mask=mask)
