"""Per-kernel validation: Pallas (interpret mode on CPU) vs ref.py
pure-jnp oracles, swept over shapes and dtypes (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

jax.config.update("jax_enable_x64", False)


def rand(key, shape, dtype):
    x = jax.random.normal(jax.random.key(key), shape, jnp.float32)
    return x.astype(dtype)


TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,H,KVH,D,window",
    [(1, 128, 4, 4, 64, None),       # MHA
     (2, 256, 8, 2, 64, None),       # GQA 4:1
     (1, 256, 8, 1, 128, None),      # MQA
     (2, 256, 4, 4, 128, 96),        # windowed (SWA)
     (1, 512, 2, 2, 256, None),      # gemma-like head_dim
     (1, 128, 4, 2, 80, None)])      # stablelm-like head_dim
def test_flash_attention(B, S, H, KVH, D, window, dtype):
    q = rand(0, (B, S, H, D), dtype)
    k = rand(1, (B, S, KVH, D), dtype)
    v = rand(2, (B, S, KVH, D), dtype)
    out = ops.flash_attention(q, k, v, causal=True, window=window,
                              block_q=64, block_k=64)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        **TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,H,KVH,D,valid",
    [(2, 256, 8, 2, 64, 256),
     (2, 256, 8, 2, 64, 130),        # partial cache
     (1, 512, 4, 1, 128, 17),
     (4, 128, 4, 4, 128, 128),
     (1, 1024, 8, 4, 256, 700)])
def test_decode_attention(B, S, H, KVH, D, valid, dtype):
    q = rand(0, (B, H, D), dtype)
    k = rand(1, (B, S, KVH, D), dtype)
    v = rand(2, (B, S, KVH, D), dtype)
    out = ops.decode_attention(q, k, v, jnp.int32(valid), block_s=128)
    want = ref.decode_attention_ref(q, k, v, valid)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        **TOL[dtype])


@pytest.mark.parametrize("N", [1, 100, 128, 1000, 4096, 5000])
@pytest.mark.parametrize("alpha", [0.999, 0.9])
def test_ralt_update(N, alpha):
    rng = np.random.default_rng(N)
    ticks = jnp.asarray(rng.integers(0, 50, N), jnp.int32)
    scores = jnp.asarray(rng.random(N), jnp.float32) * 5
    hits = jnp.asarray(rng.integers(0, 2, N), jnp.int8)
    now, thresh = 57, 1.0
    nt, ns, hot = ops.ralt_update(ticks, scores, hits, now, thresh,
                                  alpha=alpha)
    want_t, want_s = ref.ralt_update_ref(ticks, scores, hits, now, alpha)
    np.testing.assert_array_equal(np.asarray(nt), np.asarray(want_t))
    np.testing.assert_allclose(np.asarray(ns), np.asarray(want_s),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(hot) != 0, np.asarray(want_s) >= thresh)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,nC,Q,nh,hp,ns",
    [(1, 4, 32, 2, 64, 16),
     (2, 2, 64, 4, 64, 128),
     (1, 8, 16, 1, 128, 64)])
def test_ssd_scan(B, nC, Q, nh, hp, ns, dtype):
    x = rand(0, (B, nC, Q, nh, hp), dtype) * 0.5
    Bm = rand(1, (B, nC, Q, ns), dtype) * 0.5
    Cm = rand(2, (B, nC, Q, ns), dtype) * 0.5
    dt = jax.nn.softplus(rand(3, (B, nC, Q, nh), jnp.float32))
    A = -jnp.exp(jax.random.normal(jax.random.key(4), (nh,)) * 0.2)
    y, hfin = ops.ssd_scan(x, Bm, Cm, dt, A)
    h0 = jnp.zeros((B, nh, ns, hp), jnp.float32)
    want_y, want_h = ref.ssd_chunk_ref(x.astype(jnp.float32),
                                       Bm.astype(jnp.float32),
                                       Cm.astype(jnp.float32), dt, A, h0)
    tol = dict(rtol=5e-4, atol=5e-4) if dtype == jnp.float32 \
        else dict(rtol=5e-2, atol=5e-2)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(want_y, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(hfin, np.float32),
                               np.asarray(want_h, np.float32),
                               rtol=5e-3, atol=5e-3)


def test_flash_matches_model_reference():
    """The model's chunked-jnp flash path and the Pallas kernel agree."""
    from repro.models.common import flash_attention as model_flash
    q = rand(0, (2, 256, 8, 64), jnp.float32)
    k = rand(1, (2, 256, 2, 64), jnp.float32)
    v = rand(2, (2, 256, 2, 64), jnp.float32)
    a = ops.flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    b = model_flash(q, k, v, causal=True, q_chunk=128, kv_chunk=128)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-5, atol=2e-5)
