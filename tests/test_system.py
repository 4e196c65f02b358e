"""End-to-end system tests: training driver, checkpoint/restart, serving,
sharding machinery, MoE dispatch invariants."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke
from repro.configs.base import ModelConfig
from repro.checkpoint import CheckpointManager
from repro.distributed.sharding import make_rules, sharding_context
from repro.models import lm
from repro.models.moe import init_moe, moe_ffn
from repro.optim import init_opt_state

# The CLIs turn on JAX's persistent compilation cache; tests keep it off.
ENV = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu",
           JAX_ENABLE_COMPILATION_CACHE="false")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(cmd, timeout=600):
    return subprocess.run(cmd, cwd=ROOT, env=ENV, capture_output=True,
                          text=True, timeout=timeout)


@pytest.mark.slow
def test_train_driver_end_to_end(tmp_path):
    r = _run([sys.executable, "-m", "repro.launch.train", "--arch",
              "qwen1.5-0.5b", "--smoke", "--steps", "8", "--batch", "2",
              "--seq", "64", "--ckpt-dir", str(tmp_path), "--ckpt-every", "4"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "final loss" in r.stdout
    # restart resumes from the checkpoint
    r2 = _run([sys.executable, "-m", "repro.launch.train", "--arch",
               "qwen1.5-0.5b", "--smoke", "--steps", "12", "--batch", "2",
               "--seq", "64", "--ckpt-dir", str(tmp_path), "--ckpt-every", "4"])
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert "resumed from step 8" in r2.stdout


@pytest.mark.slow
def test_serve_driver_end_to_end():
    r = _run([sys.executable, "-m", "repro.launch.serve", "--arch",
              "gemma2-2b", "--smoke", "--batch", "2", "--prompt-len", "16",
              "--gen", "6"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "decode" in r.stdout


@pytest.mark.slow
def test_serve_bcpnn_driver_end_to_end(tmp_path):
    """The BCPNN serving driver: train -> checkpoint -> restore -> serve ->
    online-learn -> multi-model + in-deployment rewire, with its own smoke
    assertions (latency report, no drops, measurable readout improvement,
    struct_every boundary crossed while serving)."""
    r = _run([sys.executable, "-m", "repro.launch.serve_bcpnn", "--smoke",
              "--ckpt-dir", str(tmp_path / "ckpt")])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "smoke OK" in r.stdout
    assert "p99" in r.stdout
    assert "multi-model + rewire phase OK" in r.stdout
    # a second run must RESTORE the checkpoint rather than retrain, and
    # must be able to serve it as a multi-model deployment (--ckpt mode)
    r2 = _run([sys.executable, "-m", "repro.launch.serve_bcpnn", "--smoke",
               "--ckpt-dir", str(tmp_path / "ckpt"), "--no-online",
               "--no-multi"])
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert "no checkpoint" not in r2.stdout
    assert "restored step" in r2.stdout
    r3 = _run([sys.executable, "-m", "repro.launch.serve_bcpnn",
               "--ckpt", str(tmp_path / "ckpt"),
               "--ckpt", str(tmp_path / "ckpt"),
               "--requests", "64", "--no-online"])
    assert r3.returncode == 0, r3.stdout + r3.stderr
    assert "model 'ckpt'" in r3.stdout and "model 'ckpt#2'" in r3.stdout
    assert "aggregate" in r3.stdout


def test_checkpoint_roundtrip_and_retention(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_last=2)
    tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.float32),
            "nested": {"b": jnp.ones((4,), jnp.bfloat16)}}
    for step in (1, 2, 3):
        mgr.save(step, jax.tree.map(lambda x: x * step, tree), blocking=True)
    assert mgr.all_steps() == [2, 3]  # retention
    out = mgr.restore(3, tree)
    np.testing.assert_allclose(np.asarray(out["a"]),
                               np.asarray(tree["a"]) * 3)
    assert out["nested"]["b"].dtype == jnp.bfloat16


def test_sharding_context_noop_without_mesh():
    from repro.distributed.sharding import shard
    x = jnp.ones((4, 4))
    assert shard(x, "batch", "embed") is x


def test_moe_capacity_drop_keeps_residual_scale():
    """Dropped tokens must produce zero update (residual carries them)."""
    cfg = ModelConfig(name="m", family="moe", n_layers=2, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=64, vocab=64, head_dim=16,
                      n_experts=4, n_experts_active=4, capacity_factor=0.26)
    p = init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))
    y = moe_ffn(p, cfg, x)
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y)).all()
    cfg2 = cfg.with_(capacity_factor=8.0)
    y2 = moe_ffn(p, cfg2, x)
    assert float(jnp.linalg.norm(y)) <= float(jnp.linalg.norm(y2)) + 1e-3


def test_moe_group_invariance_matches_single_group():
    """Dispatch groups are a parallelization detail: results must match the
    single-group reference when capacity is ample."""
    cfg = ModelConfig(name="m", family="moe", n_layers=2, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=64, vocab=64, head_dim=16,
                      n_experts=4, n_experts_active=2, capacity_factor=8.0,
                      moe_groups=1)
    p = init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32))
    y1 = moe_ffn(p, cfg, x)
    y4 = moe_ffn(p, cfg.with_(moe_groups=4), x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y4), atol=1e-5)


def test_elastic_restore_across_meshes(tmp_path):
    """A checkpoint saved under one sharding restores under another."""
    cfg = smoke(get_config("qwen1.5-0.5b"))
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, params, blocking=True)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    from repro.models.params import param_shardings
    with sharding_context(mesh, make_rules(mesh)), mesh:
        shardings = param_shardings(params)
        restored = mgr.restore(1, params, shardings)
    for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_straggler_detection():
    import time
    from repro.distributed.fault import StepTimer
    t = StepTimer(window=50, threshold=3.0)
    for i in range(12):
        t.start()
        time.sleep(0.001)
        t.stop(i)
    t.start()
    time.sleep(0.05)
    t.stop(99)
    assert any(e["step"] == 99 for e in t.events)


def test_hlo_roofline_analyzer_on_known_program():
    """The HLO analyzer must recover while-loop trip counts and dot FLOPs."""
    from repro.launch.roofline import HloAnalyzer

    def f(ws, x):
        def body(c, w):
            return c @ w, None
        out, _ = jax.lax.scan(body, x, ws)
        return out.sum()

    ws = jnp.ones((12, 64, 64))
    x = jnp.ones((32, 64))
    compiled = jax.jit(f).lower(ws, x).compile()
    cost = HloAnalyzer(compiled.as_text()).cost()
    expected = 2 * 12 * 32 * 64 * 64
    assert abs(cost.flops - expected) / expected < 0.05, (cost.flops, expected)
