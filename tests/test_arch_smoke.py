"""Per-architecture smoke tests (deliverable f).

For each of the 10 assigned architectures: instantiate the REDUCED
same-family config, run one forward + one train step on CPU, assert
output shapes and no NaNs; run a short prefill-vs-decode consistency
check for decoder caches.  The FULL configs are exercised only via the
dry-run (ShapeDtypeStruct, no allocation).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, smoke_config
from repro.configs.shapes import SHAPES, applicable, input_specs
from repro.launch.steps import TrainOptions, make_train_step
from repro.models.transformer import (decode_step, forward, init_cache,
                                      init_params, padded_vocab)

BATCH, SEQ = 2, 64


def _frontend(cfg, batch, n=8):
    if not cfg.frontend:
        return None
    return jnp.zeros((batch, n, cfg.d_model), jnp.dtype(cfg.dtype))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_and_finite(arch):
    cfg = smoke_config(arch)
    params = init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (BATCH, SEQ), 0,
                                cfg.vocab)
    logits = forward(params, cfg, tokens, frontend_emb=_frontend(cfg, BATCH))
    assert logits.shape == (BATCH, SEQ, padded_vocab(cfg))
    assert bool(jnp.isfinite(logits).all()), arch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_decreases_loss(arch):
    cfg = smoke_config(arch)
    params = init_params(jax.random.key(0), cfg)
    topts = TrainOptions(warmup_steps=1, total_steps=100)
    step_fn = jax.jit(make_train_step(cfg, topts))
    from repro.optim import adamw_init
    opt = adamw_init(params, topts.opt)
    tok = jax.random.randint(jax.random.key(1), (BATCH, SEQ), 0, cfg.vocab)
    batch = {"tokens": tok, "labels": jnp.roll(tok, -1, axis=1)}
    fe = _frontend(cfg, BATCH)
    if fe is not None:
        batch["frontend_emb"] = fe
    losses = []
    for i in range(4):
        params, opt, metrics = step_fn(params, opt, jnp.int32(i), batch)
        losses.append(float(metrics["loss"]))
        assert np.isfinite(losses[-1]), (arch, i, losses)
    assert losses[-1] < losses[0], (arch, losses)


def _decode_against_forward(arch, T, s_max):
    """Greedy decode-with-cache must agree with teacher-forced forward."""
    cfg = smoke_config(arch)
    params = init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(2), (BATCH, T), 0, cfg.vocab)
    ref_logits = forward(params, cfg, tokens)          # (B, T, V)
    cache = init_cache(cfg, BATCH, s_max)
    step = jax.jit(lambda c, t, p: decode_step(params, cfg, c, t, p))
    outs = []
    for t in range(T):
        logits, cache = step(cache, tokens[:, t], jnp.int32(t))
        outs.append(logits)
    got = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref_logits, np.float32),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_matches_prefill(arch):
    _decode_against_forward(arch, T=12, s_max=32)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_matches_prefill_at_cache_edges(arch):
    """Decode fills the cache to its last position; the smoke configs'
    16-token windows make ring buffers wrap twice."""
    _decode_against_forward(arch, T=32, s_max=32)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_input_specs(arch):
    """Every applicable (arch x shape) cell has well-formed input specs
    and a param tree (eval_shape only — no allocation of full configs)."""
    cfg = get_config(arch)
    p = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))
    n_leaves = len(jax.tree.leaves(p))
    assert n_leaves > 3
    for shape in SHAPES.values():
        if not applicable(cfg, shape):
            assert shape.name == "long_500k" and not cfg.subquadratic
            continue
        specs = input_specs(cfg, shape)
        assert "tokens" in specs
        if shape.kind == "train":
            assert specs["labels"].shape == (shape.batch, shape.seq)
