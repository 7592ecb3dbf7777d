"""int8 decode KV cache (beyond-paper serving optimization)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models.transformer import (decode_step, forward, init_cache,
                                      init_params)


def _quantized_decode_against_prefill(arch, T, s_max):
    cfg = dataclasses.replace(smoke_config(arch), kv_quant=True)
    base = dataclasses.replace(cfg, kv_quant=False)
    params = init_params(jax.random.key(0), base)
    B = 2
    tokens = jax.random.randint(jax.random.key(2), (B, T), 0, cfg.vocab)
    ref = np.asarray(forward(params, base, tokens), np.float32)

    cache = init_cache(cfg, B, s_max)
    # payload really is int8 (half the cache bytes)
    leaf = cache[0]["b0"]
    assert leaf["k"].dtype == jnp.int8 and "k_scale" in leaf
    step = jax.jit(lambda c, t, p: decode_step(params, cfg, c, t, p))
    outs = []
    for t in range(T):
        logits, cache = step(cache, tokens[:, t], jnp.int32(t))
        outs.append(np.asarray(logits, np.float32))
    got = np.stack(outs, axis=1)
    # int8 KV introduces bounded error: logits stay close and the
    # greedy tokens overwhelmingly agree with the fp path
    err = np.abs(got - ref) / (np.abs(ref).max() + 1e-6)
    assert err.max() < 0.08, err.max()
    agree = (got.argmax(-1) == ref.argmax(-1)).mean()
    assert agree > 0.9, agree


@pytest.mark.parametrize("arch", ["llama3-8b", "gemma3-4b",
                                  "mixtral-8x22b"])
def test_quantized_decode_tracks_prefill(arch):
    _quantized_decode_against_prefill(arch, T=12, s_max=32)


@pytest.mark.parametrize("arch", ["llama3-8b", "gemma3-4b"])
def test_quantized_decode_at_cache_edges(arch):
    """To the cache's last position; gemma3's 16-token ring buffers wrap
    twice, scales and all.  (mixtral's smoke config is left out: at its
    32nd token the int8 noise flips one top-2 expert choice, a routing
    change that the logit bound cannot tell from a cache fault.)"""
    _quantized_decode_against_prefill(arch, T=32, s_max=32)


def test_quantized_cache_halves_bytes():
    cfg = dataclasses.replace(smoke_config("llama3-8b"), kv_quant=True)
    base = dataclasses.replace(cfg, kv_quant=False)
    q = init_cache(cfg, 4, 64)
    f = init_cache(base, 4, 64)
    qb = sum(x.nbytes for x in jax.tree.leaves(q))
    fb = sum(x.nbytes for x in jax.tree.leaves(f))
    # int8 payload + f32/hd scales: ~0.5x + 1/hd overhead
    assert qb < 0.65 * fb, (qb, fb)
