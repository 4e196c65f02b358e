"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp
oracles (interpret mode on CPU; identical calls compile to Mosaic on TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (
    bcpnn_fwd, bcpnn_update, fused_forward, fused_learn, hc_softmax,
    ref_bcpnn_fwd, ref_bcpnn_update, ref_hc_softmax,
)
from repro.core.bcpnn_layer import ProjSpec, forward, init_projection, learn
from repro.core.hypercolumns import LayerGeom


@pytest.mark.parametrize("b,h,m", [(8, 4, 8), (128, 16, 128), (64, 32, 64),
                                   (256, 8, 256),
                                   # hostile: prime batch, odd minicolumn
                                   # counts, single-HC readout shapes
                                   (97, 7, 10), (13, 1, 10), (64, 784, 2)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_hc_softmax_sweep(b, h, m, dtype):
    s = (jax.random.normal(jax.random.PRNGKey(0), (b, h * m)) * 4).astype(dtype)
    got = hc_softmax(s, h, m)
    want = ref_hc_softmax(s, h, m)
    tol = 1e-6 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)


@pytest.mark.parametrize("b,ni,hj,mj", [
    (8, 32, 4, 16), (64, 256, 8, 64), (128, 1024, 16, 128), (32, 512, 4, 128),
    # hostile: Model-1's 1568-unit pre side, prime batch, n_mc not a
    # multiple of 8 — the geometries the divisor-fitting layer degraded on
    (97, 1568, 4, 10), (64, 251, 3, 12),
])
def test_bcpnn_fwd_sweep(b, ni, hj, mj):
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.uniform(k[0], (b, ni))
    w = jax.random.normal(k[1], (ni, hj * mj)) * 0.1
    bias = jax.random.normal(k[2], (hj * mj,))
    got = bcpnn_fwd(x, w, bias, hj, mj)
    want = ref_bcpnn_fwd(x, w, bias, hj, mj)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# (b, ni, nj), and the HC counts (hi, hj) of the masked cases' random
# hypercolumn mask: Mi = 1 at 256 and at the prime 251, Mj = 10 and 12
# (neither divides a lane tile), and MNIST's 1568 = 784 x 2 pre side.
UPDATE_SHAPES = [((8, 32, 64), (16, 4)), ((64, 256, 512), (256, 8)),
                 ((128, 1024, 512), (128, 4)), ((256, 512, 2048), (64, 16)),
                 # hostile: prime batch/pre, odd post
                 ((97, 251, 40), (251, 4)), ((31, 1568, 96), (784, 8))]
# Variants beyond the masked whole-batch call: ``nomask`` passes no mask
# operand (a dense projection: the reference multiplies by ones);
# ``tail`` zeroes the last third of the rows and passes the genuine-row
# count at run time (the masked tail-batch learn), with the HC mask
# still applied; ``tail-nomask`` does both.
UPDATE_VARIANTS = ("nomask", "tail", "tail-nomask")


def _unit_mask(mask, mi, mj):
    """(Hi, Hj) HC mask -> (Ni, Nj) unit mask, the reference's operand."""
    hi, hj = mask.shape
    m4 = jnp.broadcast_to(mask[:, None, :, None], (hi, mi, hj, mj))
    return m4.reshape(hi * mi, hj * mj)


def _update_operands(b, ni, nj, hi, hj, tail):
    k = jax.random.split(jax.random.PRNGKey(2), 6)
    pij = jax.random.uniform(k[0], (ni, nj)) * 0.01 + 1e-5
    lpi = jnp.log(jax.random.uniform(k[1], (ni,)) * 0.5 + 1e-4)
    lpj = jnp.log(jax.random.uniform(k[2], (nj,)) * 0.5 + 1e-4)
    x = jax.random.uniform(k[3], (b, ni))
    y = jax.random.uniform(k[4], (b, nj))
    mask = (jax.random.uniform(k[5], (hi, hj)) > 0.3).astype(jnp.float32)
    n = b - b // 3 if tail else b
    if tail:
        rows = (jnp.arange(b) < n)[:, None]
        x, y = x * rows, y * rows
    return pij, lpi, lpj, x, y, mask, n


@pytest.mark.parametrize("b,ni,nj,hi,hj,variant", [
    *(pytest.param(*shape, *hc, "mask", id="-".join(map(str, shape)))
      for shape, hc in UPDATE_SHAPES),
    *(pytest.param(*shape, *hc, v, id="-".join(map(str, shape)) + f"-{v}")
      for v in UPDATE_VARIANTS for shape, hc in UPDATE_SHAPES),
])
def test_bcpnn_update_sweep(b, ni, nj, hi, hj, variant):
    pij, lpi, lpj, x, y, mask, n = _update_operands(
        b, ni, nj, hi, hj, variant.startswith("tail"))
    alpha = jnp.asarray(0.02)
    unit = _unit_mask(mask, ni // hi, nj // hj)
    if variant.endswith("nomask"):
        got = bcpnn_update(pij, lpi, lpj, x, y, None, alpha,
                           n=jnp.asarray(n, jnp.float32))
        unit = jnp.ones((ni, nj), jnp.float32)
    elif variant == "tail":
        got = bcpnn_update(pij, lpi, lpj, x, y, mask, alpha,
                           n=jnp.asarray(n, jnp.float32))
    else:
        got = bcpnn_update(pij, lpi, lpj, x, y, mask, alpha)
    wp, ww = ref_bcpnn_update(pij, lpi, lpj, x[:n], y[:n], unit, alpha)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(wp), atol=1e-6)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(ww), atol=1e-4)
    if variant == "tail":  # the patchy mask still zeroes silent synapses
        assert np.all(np.asarray(got[1])[np.asarray(unit) == 0] == 0)


@pytest.mark.parametrize("b,ni,nj,hi,hj,blocks", [
    pytest.param(8, 32, 64, 16, 4, {}, id="8-32-64"),
    pytest.param(97, 251, 40, 251, 4, {}, id="mi1-mj10"),
    pytest.param(31, 1568, 96, 784, 8, {}, id="mnist-pre"),
    # Mj = 10 across four 128-lane column tiles, pad rows and columns
    pytest.param(40, 300, 480, 100, 48,
                 dict(block_i=128, block_j=128, block_k=16), id="mj10-tiles"),
    # one post-HC spans two column tiles
    pytest.param(16, 256, 512, 64, 2, dict(block_i=128, block_j=128),
                 id="mj256-tiles"),
])
@pytest.mark.parametrize("tail", [False, True], ids=["whole", "tail"])
def test_bcpnn_update_hc_mask_is_bit_exact(b, ni, nj, hi, hj, blocks, tail):
    """Widening the HC mask per tile is the old unit-mask product, bit for
    bit: ``p_ij'`` does not see the mask, and ``w`` is the unmasked
    weights times the expanded 0/1 unit mask."""
    pij, lpi, lpj, x, y, mask, n = _update_operands(b, ni, nj, hi, hj, tail)
    alpha, n = jnp.asarray(0.02), jnp.asarray(n, jnp.float32)
    got = bcpnn_update(pij, lpi, lpj, x, y, mask, alpha, n=n, **blocks)
    pij_d, w_d = bcpnn_update(pij, lpi, lpj, x, y, None, alpha, n=n, **blocks)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(pij_d))
    np.testing.assert_array_equal(
        np.asarray(got[1]),
        np.asarray(w_d * _unit_mask(mask, ni // hi, nj // hj)))


def test_fused_stages_match_core():
    """The fused Pallas path must be a drop-in for the core's jnp path."""
    spec = ProjSpec(LayerGeom(64, 2), LayerGeom(4, 32), alpha=1e-2)
    proj = init_projection(spec, jax.random.PRNGKey(0))
    x = jax.random.uniform(jax.random.PRNGKey(1), (64, spec.pre.N))
    h_ref = forward(proj, spec, x)
    h_fused = fused_forward(proj, spec, x)
    np.testing.assert_allclose(np.asarray(h_fused), np.asarray(h_ref), atol=1e-5)

    y = h_ref
    p_ref = learn(proj, spec, x, y)
    p_fused = fused_learn(proj, spec, x, y)
    np.testing.assert_allclose(np.asarray(p_fused.traces.pij),
                               np.asarray(p_ref.traces.pij), atol=1e-6)
    np.testing.assert_allclose(np.asarray(p_fused.w), np.asarray(p_ref.w),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(p_fused.b), np.asarray(p_ref.b),
                               atol=1e-6)


def test_kernel_odd_tile_boundaries():
    """Shapes that don't align to the default blocks (block clamping)."""
    got = hc_softmax(jnp.ones((4, 6 * 10)), 6, 10, block_b=128, block_h=8)
    np.testing.assert_allclose(np.asarray(got), 0.1, atol=1e-6)


@pytest.mark.parametrize("nact", [None, 4])
def test_learn_parity_across_bias_correction_crossover(nact):
    """fused_learn must match _learn_jnp on BOTH sides of the effective-
    smoothing crossover: while young the trace update is a running mean
    (a = 1/(t+1) > alpha), past t > 1/alpha it is the fixed-alpha EMA.
    With alpha=0.25 the crossover sits at t=4, so 10 chained steps cross
    it mid-run; every step is compared on traces, weights and bias."""
    from repro.core.bcpnn_layer import _learn_jnp

    spec = ProjSpec(LayerGeom(12, 2), LayerGeom(4, 8), alpha=0.25, nact=nact)
    proj_j = init_projection(spec, jax.random.PRNGKey(0))
    proj_f = jax.tree.map(jnp.array, proj_j)
    keys = jax.random.split(jax.random.PRNGKey(1), 10)
    crossed = False
    for k in keys:
        kx, ky = jax.random.split(k)
        x = jax.random.uniform(kx, (16, spec.pre.N))
        y = jax.random.uniform(ky, (16, spec.post.N))
        proj_j = _learn_jnp(proj_j, spec, x, y)
        proj_f = fused_learn(proj_f, spec, x, y)
        t = int(proj_j.traces.t)
        crossed = crossed or (1.0 / t < spec.alpha if t else False)
        np.testing.assert_allclose(np.asarray(proj_f.traces.pij),
                                   np.asarray(proj_j.traces.pij),
                                   atol=1e-6, err_msg=f"pij diverged at t={t}")
        np.testing.assert_allclose(np.asarray(proj_f.traces.pi),
                                   np.asarray(proj_j.traces.pi), atol=1e-6)
        np.testing.assert_allclose(np.asarray(proj_f.traces.pj),
                                   np.asarray(proj_j.traces.pj), atol=1e-6)
        np.testing.assert_allclose(np.asarray(proj_f.w), np.asarray(proj_j.w),
                                   atol=1e-4, err_msg=f"w diverged at t={t}")
        np.testing.assert_allclose(np.asarray(proj_f.b), np.asarray(proj_j.b),
                                   atol=1e-6)
    assert crossed, "sweep never left the bias-correction regime"
    if nact is not None:  # patchy invariant holds through both regimes
        for p in (proj_j, proj_f):
            assert np.all(np.asarray(p.mask).sum(0) == nact)


# ------------------------------------------------ pad-to-aligned tiling --

@pytest.mark.parametrize("dim", [1, 5, 10, 97, 100, 251, 784, 1568, 4096])
@pytest.mark.parametrize("block", [8, 100, 128, 512])
def test_pad_spec_invariants(dim, block):
    """Every planned axis: aligned block, block divides padded size, and
    padding never exceeds one block."""
    from repro.kernels.tiling import SUBLANE, pad_spec

    ps = pad_spec(dim, block, SUBLANE)
    assert ps.block % SUBLANE == 0
    assert ps.padded % ps.block == 0
    assert ps.padded >= dim and ps.padded - dim < ps.block
    assert ps.grid == ps.padded // ps.block


@pytest.mark.parametrize("n_hc,n_mc", [(1, 10), (7, 10), (32, 128), (784, 2),
                                       (32, 100), (5, 200)])
def test_pad_hc_spec_lane_aligned(n_hc, n_mc):
    """Hypercolumnar blocks span whole HCs and a whole number of 128-lane
    tiles (or the whole padded axis for sub-lane-sized toys)."""
    from repro.kernels.tiling import LANE, pad_hc_spec

    hs = pad_hc_spec(n_hc, n_mc, 512)
    assert hs.mc_padded >= n_mc
    assert hs.block_units % hs.mc_padded == 0          # whole HCs per block
    assert hs.padded_units % hs.block_units == 0
    if hs.padded_units >= LANE:
        assert hs.block_units % LANE == 0


def test_no_misalignment_warnings_at_model1_scale():
    """Model 1's geometry (Ni=1568, Nj=4096, b=256) must plan aligned
    blocks end-to-end: no warnings from any kernel wrapper."""
    import warnings

    b, ni, hj, mj = 256, 1568, 32, 128
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.uniform(k[0], (b, ni))
    w = jax.random.normal(k[1], (ni, hj * mj)) * 0.1
    bias = jax.random.normal(k[2], (hj * mj,))
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        out = bcpnn_fwd(x, w, bias, hj, mj)
        jax.block_until_ready(out)
    assert out.shape == (b, hj * mj)


# ------------------------------------------------- low-precision pads ----

@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float16])
def test_pad_fill_clamped_to_dtype_range(dtype):
    """The softmax pad sentinel must stay FINITE after casting into the
    operand dtype (bf16 cast-on-fold serving, ROADMAP §bf16): an -inf
    fill makes an all-pad HC compute -inf - (-inf) = NaN.  clamp_fill
    pins it at finfo(dtype).min."""
    from repro.kernels.padding import clamp_fill, pad_axis, pad_hc_axis
    from repro.kernels.tiling import NEG, pad_hc_spec

    fill = clamp_fill(NEG, dtype)
    # In range (no -inf on cast: bf16 holds -1e30 as-is, f16 clamps to
    # its finfo.min) but still negative enough that exp underflows to 0.
    assert np.isfinite(fill) and fill >= float(jnp.finfo(dtype).min)
    assert np.asarray(jnp.asarray(fill, dtype), np.float32) < -1e4
    assert np.isfinite(np.asarray(jnp.asarray(fill, dtype), np.float32))
    padded = pad_axis(jnp.zeros((2, 3), dtype), 1, 5, value=NEG)
    assert np.isfinite(np.asarray(padded, np.float32)).all()
    hs = pad_hc_spec(3, 10, 512)  # mc pads 10 -> 16 with NEG lanes
    hc_padded = pad_hc_axis(jnp.zeros((4, 30), dtype), 1, hs, value=NEG)
    assert np.isfinite(np.asarray(hc_padded, np.float32)).all()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float16])
def test_hc_softmax_low_precision_pad_semantics(dtype):
    """Padded softmax lanes stay inert — zero probability, no NaN even
    through all-pad HCs — for the narrow serving dtypes, on a hostile
    geometry (odd minicolumn count -> NEG-filled lanes, prime batch ->
    pad rows)."""
    b, h, m = 13, 7, 10
    s = (jax.random.normal(jax.random.PRNGKey(3), (b, h * m)) * 4).astype(dtype)
    got = hc_softmax(s, h, m)
    assert got.dtype == dtype
    got32 = np.asarray(got, np.float32)
    assert np.isfinite(got32).all(), "pad lanes leaked NaN/inf"
    np.testing.assert_allclose(got32.reshape(b, h, m).sum(-1), 1.0,
                               atol=2e-2)
    want = np.asarray(ref_hc_softmax(s, h, m), np.float32)
    np.testing.assert_allclose(got32, want, atol=2e-2)


# ----------------------------------------------------- explicit blocks --

def test_explicit_blocks_reach_the_kernel(monkeypatch):
    """A caller's explicit ``block_*`` kwargs pass through the public
    wrapper to the kernel unchanged; without them the wrapper passes none
    and the kernel's own padded plan decides."""
    from repro.kernels import ops

    seen = {}
    real = ops.bcpnn_fwd_pallas

    def spy(*a, **kw):
        seen.update(kw)
        return real(*a, **kw)

    monkeypatch.setattr(ops, "bcpnn_fwd_pallas", spy)
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.uniform(k[0], (16, 48))
    w = jax.random.normal(k[1], (48, 32)) * 0.1
    bias = jax.random.normal(k[2], (32,))
    want = np.asarray(ref_bcpnn_fwd(x, w, bias, 4, 8))
    got = ops.bcpnn_fwd(x, w, bias, 4, 8)
    assert not any(key.startswith("block_") for key in seen)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)
    seen.clear()
    got = ops.bcpnn_fwd(x, w, bias, 4, 8, block_b=8)  # explicit kwarg wins
    assert seen["block_b"] == 8 and "block_j" not in seen
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)


def test_interpret_follows_platform(monkeypatch):
    """Interpret mode is chosen by the platform alone: Mosaic on a TPU,
    the interpreter on every other backend."""
    from repro.kernels import ops

    # memoized backend probe: the process's real platform decides
    assert ops._default_backend() == jax.default_backend()
    assert ops._interpret() == (jax.default_backend() != "tpu")
    for platform, interpreted in (("tpu", False), ("cpu", True),
                                  ("gpu", True)):
        monkeypatch.setattr(ops, "_default_backend", lambda p=platform: p)
        assert ops._interpret() is interpreted
