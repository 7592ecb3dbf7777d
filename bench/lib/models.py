"""A configuration file as sizes: the plain `Spec` the references and the
operation counts read, and the program's own `ModelConfig`.

Two families are known, by the file's `family` key:

  transformer   a dense decoder: pre-norm attention and SwiGLU MLP
                (keys as in the published config.json: hidden_size,
                num_hidden_layers, num_attention_heads, ...)
  mamba2        a Mamba2 (SSD) stack (d_model, n_layer, and the Mamba2
                layer's d_state, d_conv, expand, headdim)
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Spec:
    family: str
    name: str
    d_model: int
    n_layers: int
    vocab: int                  # ids the server may emit
    dtype: str
    norm_eps: float
    tie: bool
    # transformer
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 0
    d_ff: int = 0
    rope_theta: float = 0.0
    # mamba2
    ssm_state: int = 0
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_conv: int = 0
    ssm_chunk: int = 256

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim


def served(cfg: dict, key: str, published):
    """The value the program serves for `key`: the configuration file
    holds the published value, and `departures` the program's own where
    it differs."""
    return cfg.get("departures", {}).get(key, {}).get("served", published)


def spec(cfg: dict) -> Spec:
    fam = cfg["family"]
    if fam == "transformer":
        d, h = cfg["hidden_size"], cfg["num_attention_heads"]
        if served(cfg, "rope_pct", cfg["rope_pct"]) != 1.0:
            raise ValueError("the program and the reference rotate every "
                             "head dimension")
        return Spec(
            family=fam, name=cfg["name"], d_model=d,
            n_layers=cfg["num_hidden_layers"], vocab=cfg["vocab_size"],
            dtype=cfg["torch_dtype"],
            norm_eps=served(cfg, "norm_eps", cfg["norm_eps"]),
            tie=cfg["tie_word_embeddings"], n_heads=h,
            n_kv_heads=cfg["num_key_value_heads"], head_dim=d // h,
            d_ff=cfg["intermediate_size"], rope_theta=cfg["rope_theta"])
    if fam == "mamba2":
        a = cfg["assumed"]
        d = cfg["d_model"]
        di = a["expand"] * d
        return Spec(
            family=fam, name=cfg["name"], d_model=d,
            n_layers=cfg["n_layer"], vocab=cfg["served_vocab_size"],
            dtype=cfg["dtype"],
            norm_eps=served(cfg, "norm_eps", a["norm_eps"]),
            tie=cfg["tie_embeddings"], ssm_state=a["d_state"],
            ssm_heads=di // a["headdim"], ssm_head_dim=a["headdim"],
            ssm_conv=a["d_conv"], ssm_chunk=a["chunk_size"])
    raise ValueError(f"unknown family {fam!r}")


def model_config(s: Spec):
    """The program's ModelConfig for these sizes."""
    from repro.models.config import Block, ModelConfig
    if s.family == "transformer":
        return ModelConfig(
            name=s.name, d_model=s.d_model, n_heads=s.n_heads,
            n_kv_heads=s.n_kv_heads, head_dim=s.head_dim, d_ff=s.d_ff,
            vocab=s.vocab, stages=((s.n_layers, (Block("attn"),)),),
            rope_theta=s.rope_theta, tie_embeddings=s.tie, dtype=s.dtype)
    return ModelConfig(
        name=s.name, d_model=s.d_model, n_heads=0, n_kv_heads=0,
        head_dim=0, d_ff=0, vocab=s.vocab,
        stages=((s.n_layers, (Block("mamba2"),)),),
        ssm_state=s.ssm_state, ssm_heads=s.ssm_heads,
        ssm_head_dim=s.ssm_head_dim, ssm_conv=s.ssm_conv,
        ssm_chunk=s.ssm_chunk, tie_embeddings=s.tie, dtype=s.dtype,
        subquadratic=True)
