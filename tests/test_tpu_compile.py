"""Mosaic compiles of the main-path kernels for a described TPU v5e.

No chip is needed: the TPU compiler builds each kernel for a v5e device
that is described, not attached, and refuses what the chip would refuse
(tile-splitting reshapes, unaligned slices, VMEM overruns) -- faults that
interpret mode cannot show.  The geometries are the Table-1 widths: the
10-class readout, Model 1's 32x128 hidden layer on its 1568-unit input,
Model 3's 8192-unit input (with a unit mask, and without one at a runtime
row count), and the struct variants' nact=128 compact layout, all at
batch 128.  Beside the kernels, Model 3 struct's dense-trace, HC-masked
epoch programs (nact 128, the rewire under ``lax.cond``) compile whole.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bcpnn_fwd import bcpnn_fwd_pallas
from repro.kernels.bcpnn_update import bcpnn_update_pallas
from repro.kernels.hc_softmax import hc_softmax_pallas
from repro.kernels.patchy import compact_forward, compact_update
from repro.kernels.quant import quant_compact_forward, quant_fwd_pallas

B = 128
F32, BF16, I8, I32 = jnp.float32, jnp.bfloat16, jnp.int8, jnp.int32

# (name, kernel, operand shapes+dtypes, static kwargs)
READOUT = dict(n_hc=1, n_mc=10)          # Table-1 readout: 1 HC x 10 classes
HIDDEN = dict(n_hc=32, n_mc=128)         # Model 1 hidden layer
CASES = [
    ("hc_softmax-1x10", hc_softmax_pallas, [((B, 10), F32)], READOUT),
    ("hc_softmax-32x128", hc_softmax_pallas, [((B, 4096), F32)], HIDDEN),
    ("bcpnn_fwd-1x10", bcpnn_fwd_pallas,
     [((B, 4096), F32), ((4096, 10), F32), ((10,), F32)], READOUT),
    ("bcpnn_fwd-32x128", bcpnn_fwd_pallas,
     [((B, 1568), F32), ((1568, 4096), F32), ((4096,), F32)], HIDDEN),
    ("bcpnn_fwd-32x128-bf16", bcpnn_fwd_pallas,
     [((B, 1568), F32), ((1568, 4096), BF16), ((4096,), BF16)], HIDDEN),
    ("quant_fwd-1x10", quant_fwd_pallas,
     [((B, 4096), F32), ((4096, 10), I8), ((10,), F32), ((1,), F32)],
     READOUT),
    ("quant_fwd-32x128", quant_fwd_pallas,
     [((B, 1568), F32), ((1568, 4096), I8), ((4096,), F32), ((32,), F32)],
     HIDDEN),
    ("bcpnn_update-model3", bcpnn_update_pallas,
     [((8192, 4096), F32), ((8192,), F32), ((4096,), F32), ((B, 8192), F32),
      ((B, 4096), F32), ((8192, 4096), F32), ((), F32)], {}),
    # a dense projection's masked-epoch step: no mask operand, and the
    # genuine-row count as a runtime scalar
    ("bcpnn_update-model3-rows-nomask",
     lambda pij, lpi, lpj, x, y, alpha, n, **kw: bcpnn_update_pallas(
         pij, lpi, lpj, x, y, None, alpha, n, **kw),
     [((8192, 4096), F32), ((8192,), F32), ((4096,), F32), ((B, 8192), F32),
      ((B, 4096), F32), ((), F32), ((), F32)], {}),
    ("compact_forward-nact128", compact_forward,
     [((B, 1568), F32), ((32, 256, 128), F32), ((4096,), F32),
      ((32, 128), I32)], dict(mi=2)),
    ("compact_update-nact128", compact_update,
     [((32, 256, 128), F32), ((1568,), F32), ((4096,), F32),
      ((B, 1568), F32), ((B, 4096), F32), ((32, 128), I32), ((), F32)],
     dict(mi=2)),
    ("quant_compact_forward-nact128", quant_compact_forward,
     [((B, 1568), F32), ((32, 256, 128), I8), ((4096,), F32), ((32,), F32),
      ((32, 128), I32)], dict(mi=2)),
]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A compile for a described device is written to the persistent cache
    # but cannot be read back without the chip; keep the cache out of it.
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.mark.parametrize("name,kernel,operands,static", CASES,
                         ids=[c[0] for c in CASES])
def test_kernel_compiles_for_v5e(one_chip, name, kernel, operands, static):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in operands]
    fn = jax.jit(lambda *a: kernel(*a, interpret=False, **static))
    text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, f"{name}: no Mosaic kernel emitted"


@pytest.mark.parametrize("program", ["unsupervised", "supervised"])
def test_model3_struct_epoch_program_compiles_for_v5e(one_chip, monkeypatch,
                                                      program):
    """Table-1 Model 3 with nactHi 128 and a rewire every 8 steps: the
    masked epoch programs of a fit with a padded tail, the masked
    ``bcpnn_update_pallas`` and the rewire's conditional in the
    unsupervised one, ``patchy_forward`` in the supervised one."""
    import dataclasses
    import re

    from repro.configs.bcpnn_models import MODEL3_BREAST_STRUCT
    from repro.core.network import as_spec, init_deep
    from repro.core.trainer import (_supervised_epoch_masked,
                                    _train_projection_epoch_masked)
    from repro.kernels import ops

    # The wrappers pick interpret mode from the platform, which is the CPU
    # here: build the Mosaic kernels of the described chip instead.
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    spec = as_spec(dataclasses.replace(MODEL3_BREAST_STRUCT,
                                       backend="pallas"))
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda k: init_deep(spec, k), jax.random.PRNGKey(0)))

    def arg(shape, dtype=F32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    xs, valid = arg((5, B, 8192)), arg((5, B))
    try:
        if program == "unsupervised":
            fn = jax.jit(lambda s, h, v: _train_projection_epoch_masked(
                s, spec, h, v, 0), donate_argnums=(0,))
            lowered = fn.lower(state, xs, valid)
        else:
            fn = jax.jit(lambda s, x, y, v: _supervised_epoch_masked(
                s, spec, x, y, v), donate_argnums=(0,))
            lowered = fn.lower(state, xs, arg((5, B), I32), valid)
        text = lowered.compile().as_text()
    finally:
        jax.clear_caches()
    kernels = set(re.findall(r"%(\w+?)(?:\.\d+)? = \S.*custom-call\(",
                             text))
    assert "bcpnn_update_pallas" in kernels
    assert ("patchy_forward" in kernels) == (program == "supervised")
    assert (" conditional(" in text) == (program == "unsupervised")
