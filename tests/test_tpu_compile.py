"""Compile the chip path for a described TPU v5e (no chip attached).

The TPU compiler refuses what interpret mode accepts: misaligned kernel
blocks, programs that do not fit HBM.  These tests compile the Pallas
RALT kernel, the hotness tracker's record step and stablelm-3b's decode
step at full width (depth cut to 2) for one v5e chip.  The topology is
described inside a fixture, never at import: only one process may load
the TPU library, and every test worker imports this file.
"""
import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops, ralt_score
from repro.models.config import Block
from repro.models.transformer import init_cache, init_params
from repro.serving import engine
from repro.tiering.hotness import TrackerConfig, init_state, record_accesses

HBM_BYTES = 16 * 2**30          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no TPU here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("n", [8192, 100_000, 2**20])
def test_ralt_update_compiles(one_chip, n):
    vec = functools.partial(jax.ShapeDtypeStruct, (n,), sharding=one_chip)
    scalar = functools.partial(jax.ShapeDtypeStruct, (), sharding=one_chip)
    fn = functools.partial(ralt_score.ralt_update, alpha=0.999,
                           interpret=False)
    compiled = jax.jit(fn).lower(
        vec(jnp.int32), vec(jnp.float32), vec(jnp.int8),
        scalar(jnp.int32), scalar(jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_tracker_record_step_holds_kernel(one_chip, monkeypatch):
    # jax.default_backend() is the CPU here; steer the kernel to Mosaic.
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    page_bytes = 5 * 2**20
    cfg = TrackerConfig(n_units=512, unit_bytes=page_bytes,
                        fast_bytes=128 * page_bytes)
    state = _on(one_chip, jax.eval_shape(lambda: init_state(cfg)))
    mask = jax.ShapeDtypeStruct((512,), jnp.bool_, sharding=one_chip)
    compiled = jax.jit(lambda s, m: record_accesses(s, m, cfg)).lower(
        state, mask).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_step_full_width_fits_one_chip(one_chip):
    cfg = dataclasses.replace(get_config("stablelm-3b"),
                              stages=((2, (Block("attn"),)),))
    params = _on(one_chip, jax.eval_shape(
        lambda k: init_params(k, cfg), jax.random.key(0)))
    cache = _on(one_chip, jax.eval_shape(lambda: init_cache(cfg, 8, 1024)))
    toks = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = engine._decode.lower(params, cfg, cache, toks, pos).compile()
    mem = compiled.memory_analysis()
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(cache))
    assert mem.alias_size_in_bytes >= cache_bytes       # donated in place
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES
