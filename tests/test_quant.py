"""Low-precision serving (DESIGN.md §8): bf16 cast-on-fold and the int8
per-hypercolumn-quantized kernels, against the fp32 reference path.

Learning state is always fp32 — precision only enters through the packed
inference view (``InferPack``/``InferParams``), derived at fold
boundaries.  These tests pin: kernel-level parity on the three Table-1
model geometries and hostile shapes, pad-HC NaN safety, the
quantize→dequantize error bound, checkpoint round-trip of the
``infer_dtype`` tag, table memoization across folds, and the serving
engine's fold-boundary requantization (stale-scale regression).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bcpnn_layer import INFER_DTYPES, ProjSpec
from repro.core.compact import cached_table
from repro.core.hypercolumns import LayerGeom
from repro.core.network import (
    BCPNNConfig, infer, infer_packed, init_network, online_learn_step,
    pack_state, spec_from_dict, spec_to_dict,
)
from repro.kernels import (
    dequantize_compact, dequantize_dense, quant_compact_forward,
    quant_fwd_pallas, quantize_compact, quantize_dense, ref_bcpnn_fwd,
)
from repro.kernels.quant import (
    quant_support_compact_jnp, quant_support_dense_jnp,
)
from repro.kernels.ops import hc_softmax


def _net(backend="pallas", compact=True, infer_dtype="fp32", **kw):
    cfg = BCPNNConfig(input_hc=kw.pop("input_hc", 16), input_mc=2,
                      hidden_hc=kw.pop("hidden_hc", 4),
                      hidden_mc=kw.pop("hidden_mc", 8),
                      n_classes=kw.pop("n_classes", 4),
                      nact_hi=kw.pop("nact_hi", 6), backend=backend,
                      patchy_traces=compact, compact=compact,
                      infer_dtype=infer_dtype, **kw)
    spec = cfg.network_spec()
    state = init_network(spec, jax.random.PRNGKey(0))
    return state, spec


def _learned(state, spec, steps=5, b=16, seed=0):
    rng = np.random.default_rng(seed)
    ni = spec.projs[0].pre.N
    x = rng.random((b, ni)).astype(np.float32)
    y = rng.integers(0, spec.n_classes, b).astype(np.int32)
    for _ in range(steps):
        state = online_learn_step(state, spec, jnp.asarray(x),
                                  jnp.asarray(y))
    return state, x


# --------------------------------------------------- kernel-level parity --

# The paper's three Table-1 geometries (full Ni x Nj weight panes, small
# batch) + hostile shapes: prime batch, odd minicolumn counts, an
# all-pad-HC block (7 HCs x 10 MCs pads to 8 HCs x 16 lanes).
GEOMETRIES = [
    (8, 1568, 32, 128),    # Model 1 (MNIST): 784x2 -> 32x128
    (8, 1568, 32, 256),    # Model 2 (pneumonia): 784x2 -> 32x256
    (8, 8192, 32, 128),    # Model 3 (breast): 4096x2 -> 32x128
    (13, 33, 7, 10),       # hostile: prime batch, pad rows/lanes/HCs
    (1, 5, 1, 2),          # degenerate toy
]


@pytest.mark.parametrize("b,ni,hj,mj", GEOMETRIES)
def test_quant_fwd_matches_jnp_ref(b, ni, hj, mj):
    """Padded-dense int8 kernel == jnp fixed-point reference (same codes,
    same scales — only the schedule differs), finite through pad HCs."""
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.uniform(k[0], (b, ni))
    w = jax.random.normal(k[1], (ni, hj * mj)) * 0.5
    bias = jax.random.normal(k[2], (hj * mj,))
    w_q, scale = quantize_dense(w, hj, mj)
    ref = hc_softmax(quant_support_dense_jnp(x, w_q, scale, bias, hj, mj),
                     hj, mj, 1.0)
    got = quant_fwd_pallas(x, w_q, bias, scale, hj, mj, 1.0, interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("b,ni,hj,mj", GEOMETRIES)
def test_quant_fwd_close_to_fp32(b, ni, hj, mj):
    """Quantized forward tracks the fp32 kernel: probabilities within the
    per-HC quantization tolerance on every geometry."""
    k = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.uniform(k[0], (b, ni))
    w = jax.random.normal(k[1], (ni, hj * mj)) * 0.1
    bias = jax.random.normal(k[2], (hj * mj,))
    w_q, scale = quantize_dense(w, hj, mj)
    got = quant_fwd_pallas(x, w_q, bias, scale, hj, mj, 1.0, interpret=True)
    want = ref_bcpnn_fwd(x, w, bias, hj, mj)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-2)


def test_quant_compact_forward_matches_ref():
    """Compact-resident int8 kernel == jnp fixed-point reference on a
    hostile patchy geometry."""
    hi, mi, hj, mj, nact, b = 11, 3, 5, 10, 4, 13
    k = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.uniform(k[0], (b, hi * mi))
    w_c = jax.random.normal(k[1], (hj, nact * mi, mj)) * 0.5
    bias = jax.random.normal(k[2], (hj * mj,))
    # exactly-nact mask -> persistent index table
    mask = np.zeros((hi, hj), np.float32)
    rng = np.random.default_rng(0)
    for j in range(hj):
        mask[rng.choice(hi, nact, replace=False), j] = 1.0
    table = cached_table(jnp.asarray(mask), nact)
    w_q, scale = quantize_compact(w_c)
    ref = hc_softmax(
        quant_support_compact_jnp(x, w_q, scale, bias, table, mi),
        hj, mj, 1.0)
    got = quant_compact_forward(x, w_q, bias, scale, table, mi, 1.0,
                                interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)


def test_quantize_dequantize_round_trip_bound():
    """Per-post-HC symmetric int8: |w - dq(q(w))| <= scale_j / 2 element-
    wise, and the absmax element of every HC is exactly representable."""
    k = jax.random.PRNGKey(4)
    hj, mj, ni = 6, 10, 40
    w = jax.random.normal(k, (ni, hj * mj)) * 2.0
    w_q, scale = quantize_dense(w, hj, mj)
    assert w_q.dtype == jnp.int8 and scale.shape == (hj,)
    wd = np.asarray(dequantize_dense(w_q, scale, hj, mj))
    bound = np.repeat(np.asarray(scale), mj)[None, :] / 2 + 1e-6
    assert np.all(np.abs(wd - np.asarray(w)) <= bound)
    # compact layout: same contract on (Hj, K, Mj)
    w_c = jax.random.normal(k, (hj, 12, mj)) * 2.0
    cq, cs = quantize_compact(w_c)
    cd = np.asarray(dequantize_compact(cq, cs))
    assert np.all(np.abs(cd - np.asarray(w_c))
                  <= np.asarray(cs)[:, None, None] / 2 + 1e-6)


# ----------------------------------------------- network-level parity ----

@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("compact", [True, False])
@pytest.mark.parametrize("dtype", ["bf16", "int8"])
def test_low_precision_infer_tracks_fp32(backend, compact, dtype):
    """End-to-end ``infer`` under a low-precision spec: probabilities
    within quantization tolerance of fp32, NaN-free, on both backends and
    both patchy layouts (compact-resident and dense-resident)."""
    state, spec = _net(backend=backend, compact=compact)
    state, x = _learned(state, spec)
    p32, _ = infer(state, spec, jnp.asarray(x))
    p, _ = infer(state, spec.with_infer_dtype(dtype), jnp.asarray(x))
    assert np.isfinite(np.asarray(p)).all()
    np.testing.assert_allclose(np.asarray(p), np.asarray(p32), atol=5e-2)


def test_fp32_pack_aliases_state_and_matches_infer():
    """All-fp32 packs are free (alias the state's arrays) and the packed
    path is bit-identical to ``infer`` — the serving engine can route
    every dtype through ``infer_packed`` without perturbing fp32."""
    state, spec = _net()
    state, x = _learned(state, spec)
    params = pack_state(state, spec)
    assert params.projs[0].w is state.projs[0].w
    assert params.readout.b is state.readout.b
    p_ref, pred_ref = infer(state, spec, jnp.asarray(x))
    p, pred = infer_packed(params, spec, jnp.asarray(x))
    assert np.array_equal(np.asarray(p), np.asarray(p_ref))
    assert np.array_equal(np.asarray(pred), np.asarray(pred_ref))


def test_valid_mask_semantics_survive_quantization():
    """Padded-batch masking under int8: pad rows stay inert (probs 0,
    pred -1) exactly as in fp32."""
    state, spec = _net()
    state, x = _learned(state, spec)
    sp = spec.with_infer_dtype("int8")
    valid = jnp.asarray([1.0] * 8 + [0.0] * 8)
    probs, pred = infer(state, sp, jnp.asarray(x), valid=valid)
    assert np.all(np.asarray(probs)[8:] == 0.0)
    assert np.all(np.asarray(pred)[8:] == -1)
    assert np.isfinite(np.asarray(probs)).all()


def test_infer_dtype_validation():
    with pytest.raises(ValueError, match="infer_dtype"):
        ProjSpec(LayerGeom(4, 2), LayerGeom(2, 4), infer_dtype="fp16")
    for dt in INFER_DTYPES:
        ProjSpec(LayerGeom(4, 2), LayerGeom(2, 4), infer_dtype=dt)


def test_spec_infer_dtype_roundtrips_serialization():
    """The infer_dtype tag rides the checkpoint manifest; manifests from
    before the field existed (no key) default to fp32."""
    _, spec = _net(infer_dtype="int8")
    spec2 = spec_from_dict(spec_to_dict(spec))
    assert spec2 == spec
    assert all(p.infer_dtype == "int8" for p in spec2.projs)
    d = spec_to_dict(spec)
    for p in d["projs"] + [d["readout"]]:
        p.pop("infer_dtype")
    old = spec_from_dict(d)
    assert all(p.infer_dtype == "fp32" for p in old.projs)


# ------------------------------------------------- table memoization ----

def test_cached_table_survives_fold_rebuilds_on_rewire():
    """Satellite contract: a learn fold returns a NEW mask buffer with
    unchanged values — the (Hj, nact) index table must be reused, not
    rebuilt; only an actual rewire (content change) rebuilds it."""
    mask = np.zeros((8, 3), np.float32)
    rng = np.random.default_rng(1)
    for j in range(3):
        mask[rng.choice(8, 4, replace=False), j] = 1.0
    m1 = jnp.asarray(mask)
    t1 = cached_table(m1, 4)
    m2 = jnp.array(m1)               # new buffer, same values (a fold)
    assert m2 is not m1
    assert cached_table(m2, 4) is t1  # content-level hit
    mask2 = mask.copy()               # a rewire: move one connection
    j = 0
    on = np.flatnonzero(mask2[:, j] > 0)
    off = np.flatnonzero(mask2[:, j] == 0)
    mask2[on[0], j], mask2[off[0], j] = 0.0, 1.0
    t2 = cached_table(jnp.asarray(mask2), 4)
    assert t2 is not t1
    assert not np.array_equal(np.asarray(t2), np.asarray(t1))


def test_cached_table_never_returns_a_deleted_buffer():
    """Donation regression: Trainer's train steps donate the state, so a
    cached table's buffer can be consumed (deleted) after it was handed
    out as a compact-state leaf.  Both cache levels must rebuild instead
    of serving the dead array (seen as ``Array has been deleted`` from
    ``compactify_projection`` in the serve CLI smoke)."""
    mask = np.zeros((8, 3), np.float32)
    rng = np.random.default_rng(2)
    for j in range(3):
        mask[rng.choice(8, 4, replace=False), j] = 1.0
    m1 = jnp.asarray(mask)
    t1 = cached_table(m1, 4)
    expect = np.asarray(t1).copy()
    t1.delete()                       # what a donating jit does
    t_again = cached_table(m1, 4)     # identity-level hit path
    assert not t_again.is_deleted()
    np.testing.assert_array_equal(np.asarray(t_again), expect)
    t_again.delete()
    m2 = jnp.array(m1)                # content-level hit path
    t_content = cached_table(m2, 4)
    assert not t_content.is_deleted()
    np.testing.assert_array_equal(np.asarray(t_content), expect)


# --------------------------------------------------- serving engine ----

def test_serve_requantizes_at_fold_boundaries():
    """Stale-scale regression: after online-learning folds (including a
    struct_every rewire inside learn_fn), the slot's packed int8 weights
    must equal a FRESH quantization of the post-fold state — never the
    registration-time codes/scales."""
    from repro.serve.engine import BCPNNService

    cfg = BCPNNConfig(input_hc=16, input_mc=2, hidden_hc=4, hidden_mc=8,
                      n_classes=4, nact_hi=6, backend="pallas",
                      patchy_traces=True, compact=True, struct_every=2)
    spec = cfg.network_spec()
    state = init_network(spec, jax.random.PRNGKey(0))
    state, x = _learned(state, spec, steps=2)
    y = np.random.default_rng(2).integers(0, 4, len(x)).astype(np.int32)
    svc = BCPNNService(state, spec, online_learning=True, learn_stack=True,
                       feedback_batch=4, infer_dtype="int8",
                       max_batch=8).start()
    pack0 = svc.model_pack()
    assert pack0.readout.w.dtype == jnp.int8
    r = svc.classify(x[0])
    assert np.isfinite(r.probs).all()
    for i in range(12):               # crosses struct_every boundaries
        svc.feedback(x[i % len(x)], int(y[i % len(y)]))
    svc.stop()
    st1, pack1 = svc.model_state(), svc.model_pack()
    wq, sc = quantize_compact(st1.projs[0].w)
    assert np.array_equal(np.asarray(pack1.projs[0].w), np.asarray(wq))
    assert np.array_equal(np.asarray(pack1.projs[0].scale), np.asarray(sc))
    rq, rs = quantize_dense(st1.readout.w, spec.readout.post.H,
                            spec.readout.post.M)
    assert np.array_equal(np.asarray(pack1.readout.w), np.asarray(rq))
    assert np.array_equal(np.asarray(pack1.readout.scale), np.asarray(rs))
    # the folds really moved the readout (the regression is only
    # meaningful if a stale pack WOULD have differed)
    assert not np.array_equal(np.asarray(pack1.readout.w),
                              np.asarray(pack0.readout.w))
    # the rewire moved the mask -> the pack's table tracked it
    assert np.array_equal(
        np.asarray(pack1.projs[0].table),
        np.asarray(cached_table(st1.projs[0].mask, spec.projs[0].nact)))


def test_serve_infer_dtype_validation():
    from repro.serve.engine import BCPNNService

    state, spec = _net()
    with pytest.raises(ValueError, match="infer_dtype"):
        BCPNNService(state, spec, infer_dtype="fp16")


# --------------------------------------------- end-to-end accuracy gate --

MAX_QUANT_ACC_DELTA_PP = 0.5  # low-precision eval may lose at most this


def test_low_precision_eval_accuracy_within_budget():
    """A small compact-patchy network trained on the synthetic task, then
    the SAME fp32 state evaluated under each serving dtype (``infer``
    reroutes low-precision specs through the packed path, so this measures
    what the engine serves): bf16 and int8 each lose at most 0.5 pp of
    eval accuracy against fp32."""
    from repro.configs.bcpnn_models import deep_synth_spec
    from repro.core import Trainer, evaluate_padded
    from repro.data.synthetic import encode_images, make_synthetic

    ds = make_synthetic(768, 256, 8, 4, seed=3, max_shift=1)
    xt, xe = encode_images(ds.x_train), encode_images(ds.x_test)
    spec = deep_synth_spec(side=8, depth=1, n_classes=4, hidden_hc=8,
                           hidden_mc=16, nact=[32], patchy_traces=True,
                           compact=True, struct_every=25, backend="pallas")
    tr = Trainer(spec, seed=0)
    tr.fit(xt, ds.y_train, epochs=6, batch=64)
    acc32 = evaluate_padded(tr.state, spec, xe, ds.y_test, 64)
    for dt in ("bf16", "int8"):
        acc = evaluate_padded(tr.state, spec.with_infer_dtype(dt),
                              xe, ds.y_test, 64)
        delta_pp = (acc32 - acc) * 100
        assert delta_pp <= MAX_QUANT_ACC_DELTA_PP, (
            f"{dt} inference lost {delta_pp:.2f} pp against fp32 "
            f"({acc32 * 100:.2f}% -> {acc * 100:.2f}%)")
