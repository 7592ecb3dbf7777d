"""The `transformer` family: a dense decoder, pre-norm attention and
SwiGLU MLP.  The configuration file keeps the published config.json's
keys (hidden_size, num_hidden_layers, num_attention_heads, ...)."""
from __future__ import annotations

from bench.lib.models import Spec, served


def spec(cfg: dict) -> Spec:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    if served(cfg, "rope_pct", cfg["rope_pct"]) != 1.0:
        raise ValueError("the program and the reference rotate every "
                         "head dimension")
    return Spec(
        family=cfg["family"], name=cfg["name"], d_model=d,
        n_layers=cfg["num_hidden_layers"], vocab=cfg["vocab_size"],
        dtype=cfg["torch_dtype"],
        norm_eps=served(cfg, "norm_eps", cfg["norm_eps"]),
        tie=cfg["tie_word_embeddings"], n_heads=h,
        n_kv_heads=cfg["num_key_value_heads"], head_dim=d // h,
        d_ff=cfg["intermediate_size"], rope_theta=cfg["rope_theta"])


def model_config(s: Spec):
    """The program's ModelConfig for these sizes."""
    from repro.models.config import Block, ModelConfig
    return ModelConfig(
        name=s.name, d_model=s.d_model, n_heads=s.n_heads,
        n_kv_heads=s.n_kv_heads, head_dim=s.head_dim, d_ff=s.d_ff,
        vocab=s.vocab, stages=((s.n_layers, (Block("attn"),)),),
        rope_theta=s.rope_theta, tie_embeddings=s.tie, dtype=s.dtype)


def matmul_flops_per_token(s: Spec) -> float:
    """Multiply-adds (x2) of every weight matrix one token passes
    through, the output head over the served vocabulary included."""
    d = s.d_model
    attn = d * s.head_dim * (s.n_heads + 2 * s.n_kv_heads) \
        + s.n_heads * s.head_dim * d
    per_layer = attn + 3 * d * s.d_ff
    return 2.0 * (s.n_layers * per_layer + d * s.vocab)


def mixer_flops(s: Spec, pos: int) -> float:
    """Attention of the token at position `pos` over the pos + 1 cached
    positions: scores and values."""
    return s.n_layers * 4.0 * s.n_heads * s.head_dim * (pos + 1)


def sequence_flops(s: Spec, n_tokens: int) -> float:
    """Operations for feeding positions 0 .. n_tokens - 1 of one
    sequence."""
    mm = matmul_flops_per_token(s) * n_tokens
    # sum over pos of (pos + 1) = n (n + 1) / 2
    return mm + s.n_layers * 4.0 * s.n_heads * s.head_dim \
        * n_tokens * (n_tokens + 1) / 2
