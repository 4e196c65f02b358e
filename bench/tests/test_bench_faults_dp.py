"""A data-parallel training cell at CPU size on two virtual devices:
the run with the exchange between chips left out is not correct."""
import os
import subprocess
import sys
import textwrap

import pytest

from . import tiny

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.write(str(tmp_path_factory.mktemp("bench-tree")))


DP = textwrap.dedent("""
    import json, sys, time
    sys.path[:0] = [{root!r}, {src!r}]
    from bench.tests import tiny
    if {broken}:
        from repro.distributed import data_parallel as dp
        import jax.numpy as jnp
        def no_exchange(xf, y_l, nj, axis, n_shards):
            part = xf.T @ y_l
            off = dp._axis_offset(axis, nj // n_shards)
            full = jnp.zeros((xf.shape[1], nj), part.dtype)
            full = jax.lax.dynamic_update_slice(full, part, (0, off))
            # the partial of the other chips never arrives: device 0's
            # own columns only, the same on every chip
            return jax.lax.all_gather(full, axis)[0]
        import jax
        dp._co_allreduce_dense = no_exchange
    out = tiny.run({tree!r}, "t-train-dp2")
    print(json.dumps(out["checks"]))
    print(json.dumps(out["correct"]))
""")


@pytest.mark.parametrize("broken", [False, True])
def test_data_parallel_exchange_left_out(root, broken):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    code = DP.format(root=ROOT, src=os.path.join(ROOT, "src"), tree=root,
                     broken=broken)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    correct = p.stdout.strip().splitlines()[-1]
    assert correct == ("false" if broken else "true"), p.stdout
