"""The plain references, checked against the program on the CPU at small
sizes in float32, so that the yardstick is itself checked."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import models
from bench.reference import lowp, mamba2, ralt, transformer
from bench.tests.conftest import TINY


def spec(name, **kw):
    cfg = json.loads((TINY / "bench" / "configs" / f"{name}.json")
                     .read_text())
    return dataclasses.replace(models.spec(cfg), dtype="float32", **kw)


def program_params(s, seed):
    from repro.models.transformer import init_params
    mcfg = models.model_config(s)
    want = jax.eval_shape(lambda: init_params(jax.random.key(0), mcfg))
    rows = want["embed"].shape[0]
    ref = transformer if s.family == "transformer" else mamba2
    params = ref.make_params(s, seed, rows)
    assert jax.tree.structure(params) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype
    return mcfg, params


def test_weights_depend_on_the_seed_only():
    s = spec("stablelm-tiny")
    a = transformer.make_params(s, 2**33 + 1, 256)
    b = transformer.make_params(s, 2**33 + 1, 256)
    c = transformer.make_params(s, 2, 256)
    assert all(np.array_equal(x, y) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert not np.array_equal(a["embed"], c["embed"])


def test_transformer_reference_matches_forward():
    from repro.models.transformer import forward
    s = spec("stablelm-tiny")
    mcfg, params = program_params(s, 3)
    toks = jax.random.randint(jax.random.key(1), (2, 24), 0, s.vocab)
    want = np.asarray(forward(params, mcfg, toks)[..., :s.vocab])
    got = np.asarray(transformer.logits(params, s, toks))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_transformer_reference_matches_cached_decode():
    from repro.models.transformer import decode_step, init_cache
    s = spec("stablelm-tiny")
    mcfg, params = program_params(s, 4)
    B, T = 2, 12
    toks = jax.random.randint(jax.random.key(2), (B, T), 0, s.vocab)
    ref = np.asarray(transformer.logits(params, s, toks))
    cache = init_cache(mcfg, B, 16)
    for t in range(T):
        lg, cache = decode_step(params, mcfg, cache, toks[:, t], jnp.int32(t))
        np.testing.assert_allclose(np.asarray(lg[:, :s.vocab]), ref[:, t],
                                   rtol=2e-4, atol=2e-4)


def test_mamba2_reference_matches_decode_steps():
    from repro.models.transformer import decode_step, init_cache
    s = spec("mamba2-tiny")
    mcfg, params = program_params(s, 5)
    B, T = 2, 10
    toks = jax.random.randint(jax.random.key(3), (B, T), 0, s.vocab)
    ref = np.asarray(mamba2.logits(params, s, toks))
    cache = init_cache(mcfg, B, 16)
    for t in range(T):
        lg, cache = decode_step(params, mcfg, cache, toks[:, t], jnp.int32(t))
        np.testing.assert_allclose(np.asarray(lg[:, :s.vocab]), ref[:, t],
                                   rtol=2e-4, atol=2e-4)


def test_mamba2_reference_matches_chunked_forward():
    from repro.models.transformer import forward
    s = spec("mamba2-tiny")
    mcfg, params = program_params(s, 6)
    toks = jax.random.randint(jax.random.key(4), (2, 16), 0, s.vocab)
    want = np.asarray(forward(params, mcfg, toks)[..., :s.vocab])
    got = np.asarray(mamba2.logits(params, s, toks))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("n", [100, 2048])
def test_ralt_reference_matches_kernel_and_oracle(n):
    from repro.kernels import ops, ref
    rng = np.random.default_rng(n)
    ticks = rng.integers(0, 50, n).astype(np.int32)
    scores = (rng.random(n) * 5).astype(np.float32)
    hits = rng.integers(0, 2, n).astype(bool)
    nt, ns = ralt.ralt_update(ticks, scores, hits, 57, 0.999)
    kt, ks, _ = ops.ralt_update(jnp.asarray(ticks), jnp.asarray(scores),
                                jnp.asarray(hits), 57, 1.0, alpha=0.999)
    ot, os_ = ref.ralt_update_ref(jnp.asarray(ticks), jnp.asarray(scores),
                                  jnp.asarray(hits), 57, 0.999)
    assert np.array_equal(nt, np.asarray(kt))
    assert np.array_equal(nt, np.asarray(ot))
    assert ralt.score_error(np.asarray(ks), ns) < 1e-5
    assert ralt.score_error(np.asarray(os_), ns) < 1e-5
    # the control's precision is visibly worse
    import ml_dtypes
    _, low = ralt.ralt_update(ticks, scores, hits, 57, 0.999,
                              dtype=ml_dtypes.bfloat16)
    assert ralt.score_error(low, ns) > 1e-4


def test_fp8_control_rounds_weights():
    w = jax.random.normal(jax.random.key(0), (64, 64)) * 0.05
    q = lowp.fp8(w)
    rel = float(jnp.abs(q - w).max() / jnp.abs(w).max())
    assert 1e-3 < rel < 0.1
    assert float(jnp.abs(lowp.fp8(q) - q).max()) < 1e-6
