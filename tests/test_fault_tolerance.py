"""Fault tolerance of the *training* substrate: atomic checkpoints
(step-atomic rename + parent-dir fsync, crash-debris GC, rolling
manager), restart-from-checkpoint equivalence of the train loop,
elastic resharding, data-pipeline determinism, gradient compression,
and straggler monitoring.

Crash recovery of the storage engine itself (WAL + manifest replay,
deterministic crash-point injection) is a separate subsystem with its
own suites: tests/test_crash_recovery.py and tests/test_crash_property.py.
"""
import os

import jax
import jax.numpy as jnp
from jax.sharding import AxisType
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, latest_step, restore, save
from repro.configs import smoke_config
from repro.data.lm_pipeline import DataConfig, LMPipeline
from repro.launch.train import StragglerMonitor, train


# ----------------------------------------------------------------------
# checkpoint
# ----------------------------------------------------------------------
def tree():
    return {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.bfloat16)}}


def test_save_restore_roundtrip(tmp_path):
    t = tree()
    save(str(tmp_path), 3, t, extra={"step": 3})
    assert latest_step(str(tmp_path)) == 3
    got, extra = restore(str(tmp_path), 3, t)
    assert extra["step"] == 3
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a, np.float32), np.asarray(b, np.float32)), t, got)


def test_crash_debris_is_ignored_and_cleaned(tmp_path):
    t = tree()
    save(str(tmp_path), 1, t)
    os.makedirs(tmp_path / "step_00000002.tmp")   # simulated crash
    # explicit barrier: make the debris entry durable before scanning,
    # mirroring the post-crash replay this test models (and keeping the
    # directory listing stable on lazily-syncing filesystems)
    dfd = os.open(str(tmp_path), os.O_RDONLY)
    try:
        os.fsync(dfd)
    finally:
        os.close(dfd)
    assert latest_step(str(tmp_path)) == 1
    assert not (tmp_path / "step_00000002.tmp").exists()


def test_manager_keeps_last_k(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    t = tree()
    for s in (1, 2, 3, 4):
        mgr.save(s, t, extra={"step": s})
    mgr.wait()
    steps = sorted(d for d in os.listdir(tmp_path))
    assert steps == ["step_00000003", "step_00000004"]


def test_async_manager(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3, async_write=True)
    t = tree()
    mgr.save(7, t, extra={"step": 7})
    mgr.wait()
    assert mgr.latest() == 7


def test_elastic_reshard(tmp_path):
    """Save under one sharding, restore under another mesh layout."""
    if len(jax.devices()) < 1:
        pytest.skip("needs devices")
    from jax.sharding import NamedSharding, PartitionSpec as P
    t = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
    save(str(tmp_path), 0, t, extra={})
    mesh = jax.make_mesh((1,), ("data",), (AxisType.Auto,))
    sh = {"w": NamedSharding(mesh, P("data", None))}
    got, _ = restore(str(tmp_path), 0, t, shardings=sh)
    np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(t["w"]))
    assert got["w"].sharding == sh["w"]


# ----------------------------------------------------------------------
# data pipeline determinism / elasticity
# ----------------------------------------------------------------------
def test_pipeline_deterministic_and_reshard_stable():
    cfg = DataConfig(vocab=128, seq_len=32, global_batch=8, seed=1)
    p1, p2 = LMPipeline(cfg), LMPipeline(cfg)
    a = p1.batch_at(5)
    b = p2.batch_at(5)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    # resharding: 2 shards concatenated == 1 shard
    whole = p1.batch_at(9)["tokens"]
    parts = np.concatenate([p1.batch_at(9, shard=s, num_shards=2)["tokens"]
                            for s in range(2)])
    np.testing.assert_array_equal(whole, parts)


def test_pipeline_labels_shift():
    cfg = DataConfig(vocab=64, seq_len=16, global_batch=2, seed=0)
    b = LMPipeline(cfg).batch_at(0)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


# ----------------------------------------------------------------------
# crash/restart equivalence (the headline FT property)
# ----------------------------------------------------------------------
def test_restart_matches_uninterrupted(tmp_path):
    cfg = smoke_config("llama3-8b")
    kw = dict(global_batch=4, seq_len=32, ckpt_every=5, log_every=100)
    # uninterrupted run
    _, _, h_ref = train(cfg, steps=12, ckpt_dir=str(tmp_path / "ref"),
                        async_ckpt=False, **kw)
    # crash at step 7, restart from latest checkpoint
    with pytest.raises(RuntimeError, match="injected failure"):
        train(cfg, steps=12, ckpt_dir=str(tmp_path / "crash"),
              inject_failure_at=7, async_ckpt=False, **kw)
    _, _, h2 = train(cfg, steps=12, ckpt_dir=str(tmp_path / "crash"),
                     resume=True, async_ckpt=False, **kw)
    # the resumed tail must match the uninterrupted run bit-for-bit
    # (deterministic data + deterministic step): compare final losses
    np.testing.assert_allclose(h2["loss"][-1], h_ref["loss"][-1],
                               rtol=1e-5, atol=1e-6)


def test_straggler_monitor_flags_slow_steps():
    m = StragglerMonitor(deadline_factor=2.0, warmup=1)
    flags = [m.observe(i, dt) for i, dt in
             enumerate([1.0, 1.0, 1.0, 5.0, 1.0])]
    assert flags[3] is True and sum(flags) == 1


# ----------------------------------------------------------------------
# gradient compression
# ----------------------------------------------------------------------
def test_compressed_allreduce_bounded_error_and_convergence():
    from repro.distributed.compression import (compressed_allreduce,
                                               init_error_state)
    mesh = jax.make_mesh((1,), ("data",), (AxisType.Auto,))
    g = {"w": jnp.asarray(np.random.default_rng(0)
                          .standard_normal((64, 64)), jnp.float32)}
    e = init_error_state(g)
    out, e2 = compressed_allreduce(g, e, mesh, dp_axes=("data",))
    # single-shard mean == dequantized value; error bounded by scale
    scale = float(jnp.abs(g["w"]).max()) / 127.0
    assert float(jnp.abs(out["w"] - g["w"]).max()) <= scale * 0.5 + 1e-6
    # error feedback: e2 carries the residual
    np.testing.assert_allclose(np.asarray(out["w"] + e2["w"]),
                               np.asarray(g["w"]), atol=1e-5)
    # toy convergence: minimize ||x||^2 with compressed grads
    x = jnp.full((16,), 5.0)
    err = {"x": jnp.zeros((16,))}
    for _ in range(60):
        grads = {"x": 2 * x}
        cg, err = compressed_allreduce(grads, err, mesh, ("data",))
        x = x - 0.05 * cg["x"]
    assert float(jnp.abs(x).max()) < 0.2
