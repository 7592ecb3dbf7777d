"""The `mamba2` family: a Mamba2 (SSD) stack.  The configuration file
keeps the published config.json's keys (d_model, n_layer, ...) and, under
`assumed`, the Mamba2 layer's defaults that it leaves in force (d_state,
d_conv, expand, headdim, chunk_size, norm_eps)."""
from __future__ import annotations

from bench.lib.models import Spec, served


def spec(cfg: dict) -> Spec:
    a = cfg["assumed"]
    d = cfg["d_model"]
    di = a["expand"] * d
    return Spec(
        family=cfg["family"], name=cfg["name"], d_model=d,
        n_layers=cfg["n_layer"], vocab=cfg["served_vocab_size"],
        dtype=cfg["dtype"],
        norm_eps=served(cfg, "norm_eps", a["norm_eps"]),
        tie=cfg["tie_embeddings"], ssm_state=a["d_state"],
        ssm_heads=di // a["headdim"], ssm_head_dim=a["headdim"],
        ssm_conv=a["d_conv"], ssm_chunk=a["chunk_size"])


def model_config(s: Spec):
    """The program's ModelConfig for these sizes."""
    from repro.models.config import Block, ModelConfig
    return ModelConfig(
        name=s.name, d_model=s.d_model, n_heads=0, n_kv_heads=0,
        head_dim=0, d_ff=0, vocab=s.vocab,
        stages=((s.n_layers, (Block("mamba2"),)),),
        ssm_state=s.ssm_state, ssm_heads=s.ssm_heads,
        ssm_head_dim=s.ssm_head_dim, ssm_conv=s.ssm_conv,
        ssm_chunk=s.ssm_chunk, tie_embeddings=s.tie, dtype=s.dtype,
        subquadratic=True)


def matmul_flops_per_token(s: Spec) -> float:
    """Multiply-adds (x2) of every weight matrix one token passes
    through, the output head over the served vocabulary included."""
    d = s.d_model
    di, ns, nh = s.d_inner, s.ssm_state, s.ssm_heads
    per_layer = d * (2 * di + 2 * ns + nh) + di * d
    return 2.0 * (s.n_layers * per_layer + d * s.vocab)


def mixer_flops(s: Spec, pos: int) -> float:
    """The Mamba2 conv, state update and read-out of one token, the same
    at every position."""
    conv_dim = s.d_inner + 2 * s.ssm_state
    state = s.ssm_heads * s.ssm_state * s.ssm_head_dim
    # conv: K multiply-adds per channel; state: decay (1), outer product
    # (2), read-out (2) per element
    return s.n_layers * (2.0 * conv_dim * s.ssm_conv + 5.0 * state)


def sequence_flops(s: Spec, n_tokens: int) -> float:
    """Operations for feeding positions 0 .. n_tokens - 1 of one
    sequence."""
    mm = matmul_flops_per_token(s) * n_tokens
    return mm + mixer_flops(s, 0) * n_tokens
