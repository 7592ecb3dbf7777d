"""zamba2's published block against its plain reference, on the CPU.

The configuration is Zamba2-7B-Instruct's file with every width cut to a
tiny size (`tiny_config`); its weights come from the reference's weight
maker, in the program's layout.  The program's `forward` logits, and its
`decode_step` fed the same tokens one at a time through the cache (the
serving engine's path), must agree with the reference's full forward
pass.  Each fault below changes one equation and must fail that
agreement.
"""
import dataclasses
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.lib.cell import load_module  # noqa: E402
from repro.models import mamba2 as mamba2_mod  # noqa: E402
from repro.models import transformer as tf  # noqa: E402

B, T, SEED = 2, 32, 3
# Both sides compute in float32; the program's chunked SSD sums the same
# terms in another order (and takes exp of cumulative sums where the
# reference multiplies one decay per step), and its attention runs blocked
# with a running softmax: logits of rms ~1 (largest ~3.8) agree to 2e-5.
# 2e-4 leaves ten times that, and every fault below moves some logit by
# more than 2 (the least, the hd^-0.5 scale, by 2.24).
TOL = 2e-4


def tiny_config() -> dict:
    """The published configuration at tiny widths: 14 Mamba2 layers with
    six uses, so that one stage repeats both blocks."""
    cfg = json.loads((ROOT / "bench" / "configs" / "zamba2-7b-instruct.json")
                     .read_text())
    ids = [2, 4, 6, 8, 10, 12]
    cfg.update(
        name="zamba2-tiny", hidden_size=64, num_attention_heads=4,
        num_key_value_heads=4, num_query_groups=4, attention_head_dim=32,
        attention_hidden_size=128, kv_channels=16, ffn_hidden_size=128,
        intermediate_size=128, adapter_rank=8, mamba_d_state=16,
        n_mamba_heads=4, mamba_headdim=32, chunk_size=16, vocab_size=256,
        num_hidden_layers=14, hybrid_layer_ids=ids,
        layers_block_type=["hybrid" if i in ids else "mamba"
                           for i in range(14)],
        dtype="float32")
    return cfg


@pytest.fixture(scope="module")
def model():
    family = load_module("families", "zamba2")
    ref = load_module("reference", "zamba2")
    s = family.spec(tiny_config())
    cfg = family.model_config(s)
    want = jax.eval_shape(lambda: tf.init_params(jax.random.key(0), cfg))
    params = ref.make_params(s, SEED, want["embed"].shape[0])
    assert jax.tree.structure(params) == jax.tree.structure(want)
    tokens = jax.random.randint(jax.random.key(SEED), (B, T), 0, s.vocab)
    expect = np.asarray(ref.logits(params, s, tokens))
    return s, cfg, params, tokens, expect


def _forward(cfg, params, tokens):
    return np.asarray(tf.forward(params, cfg, tokens))[..., :cfg.vocab]


def _decode(cfg, params, tokens):
    cache = tf.init_cache(cfg, tokens.shape[0], tokens.shape[1])
    step = jax.jit(lambda c, tok, pos: tf.decode_step(params, cfg, c, tok,
                                                      pos))
    outs = []
    for t in range(tokens.shape[1]):
        logits, cache = step(cache, tokens[:, t], jnp.int32(t))
        outs.append(np.asarray(logits)[:, :cfg.vocab])
    return np.stack(outs, axis=1)


def test_published_block_is_exercised(model):
    s, cfg, params, _, _ = model
    kinds = [(b.kind, b.mem) for _, blocks in cfg.stages for b in blocks]
    assert ("shared_attn", 0) in kinds and ("shared_attn", 1) in kinds
    assert max(r for r, _ in cfg.stages) == 2      # uses stacked in a scan
    assert cfg.ssm_ngroups == 2 and cfg.ssm_conv_bias
    assert cfg.attn_scale == (cfg.head_dim / 2) ** -0.5
    assert len(params["shared"]) == 2


@pytest.mark.parametrize("path", ["forward", "decode"])
def test_program_matches_reference(model, path):
    s, cfg, params, tokens, expect = model
    got = (_forward if path == "forward" else _decode)(cfg, params, tokens)
    assert float(np.abs(expect).max()) > 0.5       # logits of size ~1
    np.testing.assert_allclose(got, expect, rtol=0, atol=TOL)


# ------------------------------------------------------------- faults
def _blocks_not_alternated(cfg, params, mp):
    params = dict(params, shared=[params["shared"][0]] * 2)
    return cfg, params


def _adapter_dropped(cfg, params, mp):
    stages = [{k: (dict(v, ad_b=jnp.zeros_like(v["ad_b"]))
                   if "ad_b" in v else v) for k, v in st.items()}
              for st in params["stages"]]
    return cfg, dict(params, stages=stages)


def _x0_not_concatenated(cfg, params, mp):
    for name in ("shared_block", "shared_block_decode"):
        orig = getattr(tf, name)

        def without_x0(p, use, x, x0, *a, orig=orig):
            return orig(p, use, x, jnp.zeros_like(x0), *a)
        mp.setattr(tf, name, without_x0)
    return cfg, params


def _groups_collapsed(cfg, params, mp):
    orig = mamba2_mod._heads

    def first_group(v, c):
        ns = c.ssm_state
        return orig(jnp.concatenate([v[..., :ns]] * c.ssm_ngroups, -1), c)
    mp.setattr(mamba2_mod, "_heads", first_group)
    return cfg, params


def _scale_of_head_dim(cfg, params, mp):
    return dataclasses.replace(cfg, attn_scale=None), params


def _residual_after_t(cfg, params, mp):
    for name in ("mamba2_mixer", "mamba2_step"):
        orig = getattr(tf, name)

        def residual_with_t(p, x, *a, extra=None, orig=orig, **kw):
            return orig(p, x if extra is None else x + extra, *a, **kw)
        mp.setattr(tf, name, residual_with_t)
    return cfg, params


FAULTS = {
    "blocks_not_alternated": _blocks_not_alternated,
    "adapter_dropped": _adapter_dropped,
    "x0_not_concatenated": _x0_not_concatenated,
    "groups_collapsed_to_1": _groups_collapsed,
    "scale_head_dim": _scale_of_head_dim,
    "residual_after_t": _residual_after_t,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("path", ["forward", "decode"])
def test_each_fault_fails_the_reference(model, monkeypatch, fault, path):
    s, cfg, params, tokens, expect = model
    cfg, params = FAULTS[fault](cfg, params, monkeypatch)
    got = (_forward if path == "forward" else _decode)(cfg, params, tokens)
    assert float(np.abs(got - expect).max()) > 10 * TOL


def test_parameter_counts_are_pinned():
    """The whole published model and this chip's stage of it, counted by
    hand: 78,437,456 per Mamba2 layer, 333,982,208 per shared block,
    16,973,824 per use, 114,688,000 in the tied embedding."""
    from repro.configs import get_config
    family = load_module("families", "zamba2")
    cut = family.model_config(family.spec(json.loads(
        (ROOT / "bench" / "configs" / "zamba2-7b-instruct.json")
        .read_text())))
    mamba, block, use, emb = 78_437_456, 333_982_208, 16_973_824, 114_688_000
    assert get_config("zamba2-7b").param_count() \
        == 81 * mamba + 2 * block + 13 * use + emb == 7_356_746_064
    assert cut.param_count() == 41 * mamba + 2 * block + 6 * use + emb \
        == 4_100_431_056
