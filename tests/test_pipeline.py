"""Pipeline parallelism: schedule correctness vs sequential reference."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.distributed.pipeline import bubble_fraction, gpipe_apply


def stage_fn(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])


def make(S, d, key=0):
    ks = jax.random.split(jax.random.key(key), 2)
    return {"w": jax.random.normal(ks[0], (S, d, d)) * 0.3,
            "b": jax.random.normal(ks[1], (S, d)) * 0.1}


def sequential(params, xs):
    def one(x):
        for s in range(params["w"].shape[0]):
            x = stage_fn(jax.tree.map(lambda p: p[s], params), x)
        return x
    return jax.vmap(one)(xs)


def test_single_stage_degenerate():
    mesh = jax.make_mesh((1,), ("stage",), (AxisType.Auto,))
    params = make(1, 8)
    xs = jax.random.normal(jax.random.key(1), (4, 2, 8))
    got = gpipe_apply(stage_fn, params, xs, mesh=mesh, axis="stage")
    want = sequential(params, xs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_bubble_fraction():
    assert bubble_fraction(4, 12) == pytest.approx(3 / 15)
    assert bubble_fraction(1, 8) == 0.0


_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
from repro.distributed.pipeline import gpipe_apply

def stage_fn(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])

S, d, M = 4, 8, 6
ks = jax.random.split(jax.random.key(0), 2)
params = {"w": jax.random.normal(ks[0], (S, d, d)) * 0.3,
          "b": jax.random.normal(ks[1], (S, d)) * 0.1}
xs = jax.random.normal(jax.random.key(1), (M, 2, d))
mesh = jax.make_mesh((4,), ("stage",), (AxisType.Auto,))
got = gpipe_apply(stage_fn, params, xs, mesh=mesh, axis="stage")

def one(x):
    for s in range(S):
        x = stage_fn(jax.tree.map(lambda p: p[s], params), x)
    return x
want = jax.vmap(one)(xs)
np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                           rtol=1e-5, atol=1e-5)
print("pipeline-4stage ok")
"""


@pytest.mark.slow
def test_four_stage_subprocess():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"       # virtual CPU devices, never a chip
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    out = subprocess.run([sys.executable, "-c", _SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "pipeline-4stage ok" in out.stdout
