"""The `zamba2` family: a Mamba2 stack with shared transformer blocks
before the Mamba2 layers at `hybrid_layer_ids` (Zamba2).  The
configuration file keeps the published config.json's keys
(hidden_size, num_hidden_layers, hybrid_layer_ids, attention_head_dim,
mamba_ngroups, ...); `reduced` names the ones cut to this chip's stage.

Counts, per token (all from the shapes alone):
  a Mamba2 layer     d (2 di + 2 groups d_state + heads) + di d weights,
                     the conv (with its bias), A, D, dt bias, two norms
  a shared block     q/k/v 2d -> heads x head_dim, o back to d, gate/up
                     d -> 2 ffn, down ffn -> d, two norms; read and
                     computed once per use
  a use's own        the d x d linear layer and the rank-r adapter
                     (d -> r -> 2 ffn)

No `from __future__ import annotations` here: `load_module` keeps this
module in sys.modules under a key of its path, where `dataclasses` could
not look string annotations up.
"""
import dataclasses

F32_BYTES = 4


@dataclasses.dataclass(frozen=True)
class Spec:
    family: str
    name: str
    vocab: int                  # ids the server may emit
    dtype: str
    d_model: int
    n_layers: int               # Mamba2 layers held here
    hybrid_layer_ids: tuple     # depths whose Mamba2 layer a use precedes
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    adapter_rank: int
    n_mem_blocks: int
    ssm_state: int
    ssm_heads: int
    ssm_head_dim: int
    ssm_ngroups: int
    ssm_conv: int
    ssm_chunk: int
    conv_bias: bool
    norm_eps: float
    rope_theta: float
    tie: bool

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.ssm_ngroups * self.ssm_state

    @property
    def uses(self) -> int:
        return len(self.hybrid_layer_ids)

    @property
    def attn_scale(self) -> float:
        """(head_dim / 2) ** -0.5, as transformers' Zamba2Attention."""
        return (self.head_dim / 2) ** -0.5


def spec(cfg: dict) -> Spec:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    n = cfg["num_hidden_layers"]
    ids = tuple(cfg["hybrid_layer_ids"])
    served = {"hidden_act": "gelu", "add_bias_linear": False,
              "use_mem_rope": True, "use_shared_mlp_adapter": True,
              "use_shared_attention_adapter": False,
              "use_long_context": False}
    for key, want in served.items():
        if cfg[key] != want:
            raise ValueError(f"the program serves {key}={want!r}, not "
                             f"{cfg[key]!r}")
    types = cfg["layers_block_type"]
    if len(types) != n or ids != tuple(i for i, t in enumerate(types)
                                       if t == "hybrid"):
        raise ValueError("hybrid_layer_ids and layers_block_type disagree")
    if cfg["attention_hidden_size"] != 2 * d or \
            cfg["n_mamba_heads"] * cfg["mamba_headdim"] \
            != cfg["mamba_expand"] * d:
        raise ValueError("the shared block reads concat(x, x0), 2 d wide; "
                         "the Mamba2 heads span expand x d")
    return Spec(
        family=cfg["family"], name=cfg["name"], vocab=cfg["vocab_size"],
        dtype=cfg["dtype"], d_model=d, n_layers=n, hybrid_layer_ids=ids,
        n_heads=h, n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["attention_head_dim"], d_ff=cfg["ffn_hidden_size"],
        adapter_rank=cfg["adapter_rank"],
        n_mem_blocks=cfg["num_mem_blocks"],
        ssm_state=cfg["mamba_d_state"], ssm_heads=cfg["n_mamba_heads"],
        ssm_head_dim=cfg["mamba_headdim"], ssm_ngroups=cfg["mamba_ngroups"],
        ssm_conv=cfg["mamba_d_conv"], ssm_chunk=cfg["chunk_size"],
        conv_bias=cfg["use_conv_bias"], norm_eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]),
        tie=cfg["assumed"]["tie_word_embeddings"])


def stage_layout(s: Spec) -> tuple:
    """The program's stages: ((repeat, items), ...), an item "m" (a Mamba2
    layer) or ("s", block) (a use of shared block `block`, before the
    next "m").  Each use heads the group its Mamba2 layer starts, so a
    use's output never crosses a scan step; the groups after the first
    are taken a period of blocks at a time, and equal runs repeat."""
    items, use = [], 0
    for i in range(s.n_layers):
        if i in s.hybrid_layer_ids:
            items.append(("s", use % s.n_mem_blocks))
            use += 1
        items.append("m")
    heads = [i for i, it in enumerate(items) if it != "m"]
    cuts = [0] + heads[1:] + [len(items)]
    groups = [tuple(items[a:b]) for a, b in zip(cuts, cuts[1:])]
    body = [sum(groups[i:i + s.n_mem_blocks], ())
            for i in range(1, len(groups), s.n_mem_blocks)]
    stages = [[1, groups[0]]]
    for g in body:
        if g == stages[-1][1]:
            stages[-1][0] += 1
        else:
            stages.append([1, g])
    return tuple((r, g) for r, g in stages)


def model_config(s: Spec):
    """The program's ModelConfig for these sizes."""
    from repro.models.config import Block, ModelConfig

    def block(it):
        return Block("mamba2") if it == "m" else Block("shared_attn",
                                                       mem=it[1])
    return ModelConfig(
        name=s.name, d_model=s.d_model, n_heads=s.n_heads,
        n_kv_heads=s.n_kv_heads, head_dim=s.head_dim, d_ff=0,
        vocab=s.vocab,
        stages=tuple((r, tuple(block(it) for it in g))
                     for r, g in stage_layout(s)),
        ssm_state=s.ssm_state, ssm_heads=s.ssm_heads,
        ssm_head_dim=s.ssm_head_dim, ssm_conv=s.ssm_conv,
        ssm_chunk=s.ssm_chunk, ssm_ngroups=s.ssm_ngroups,
        ssm_conv_bias=s.conv_bias, shared_attn_d_ff=s.d_ff,
        adapter_rank=s.adapter_rank, norm_eps=s.norm_eps,
        attn_scale=s.attn_scale, rope_theta=s.rope_theta,
        tie_embeddings=s.tie, dtype=s.dtype, subquadratic=True)


def mamba_matmul_params(s: Spec) -> int:
    d, di = s.d_model, s.d_inner
    return d * (2 * di + 2 * s.ssm_ngroups * s.ssm_state + s.ssm_heads) \
        + di * d


def block_matmul_params(s: Spec) -> int:
    d, hd = s.d_model, s.head_dim
    return 2 * d * hd * (s.n_heads + 2 * s.n_kv_heads) \
        + s.n_heads * hd * d + 3 * d * s.d_ff


def use_params(s: Spec) -> int:
    d = s.d_model
    return d * d + s.adapter_rank * (d + 2 * s.d_ff)


def layer_params(s: Spec) -> dict:
    """Parameters of each part: a Mamba2 layer, a shared block, a use's
    own, the embedding (the tied head)."""
    d = s.d_model
    return {
        "mamba2": mamba_matmul_params(s) + s.conv_dim * (
            s.ssm_conv + s.conv_bias) + 3 * s.ssm_heads + s.d_inner + d,
        "shared_block": block_matmul_params(s) + 3 * d,
        "use": use_params(s),
        "embedding": s.vocab * d,
    }


def matmul_flops_per_token(s: Spec) -> float:
    """Multiply-adds (x2) of every weight matrix one token passes
    through, each shared block once per use, the tied output head over
    the vocabulary included."""
    return 2.0 * (s.n_layers * mamba_matmul_params(s)
                  + s.uses * (block_matmul_params(s) + use_params(s))
                  + s.d_model * s.vocab)


def _ssm_flops(s: Spec) -> float:
    # conv: K multiply-adds per channel; state: decay (1), outer product
    # (2), read-out (2) per element
    state = s.ssm_heads * s.ssm_state * s.ssm_head_dim
    return s.n_layers * (2.0 * s.conv_dim * s.ssm_conv + 5.0 * state)


def mixer_flops(s: Spec, pos: int) -> float:
    """The Mamba2 conv, state update and read-out of one token, and at
    each use its attention over the pos + 1 cached positions."""
    return _ssm_flops(s) + s.uses * 4.0 * s.n_heads * s.head_dim * (pos + 1)


def sequence_flops(s: Spec, n_tokens: int) -> float:
    """Operations for feeding positions 0 .. n_tokens - 1 of one
    sequence."""
    per_token = matmul_flops_per_token(s) + _ssm_flops(s)
    # sum over pos of (pos + 1) = n (n + 1) / 2
    return per_token * n_tokens + s.uses * 4.0 * s.n_heads * s.head_dim \
        * n_tokens * (n_tokens + 1) / 2


def step_bytes(s: Spec, live: int, pos: int) -> float:
    """HBM bytes a decode step at position `pos` needs with `live`
    sequences in flight: every weight as read (each shared block once
    per use, the tied head once), the live sequences' K and V at the
    uses for positions 0 .. pos (head_dim unpadded), and their SSM
    (float32) and conv state, read and written."""
    w = 2 if s.dtype == "bfloat16" else 4
    p = layer_params(s)
    weights = (s.n_layers * p["mamba2"]
               + s.uses * (p["shared_block"] + p["use"])
               + p["embedding"]) * w
    kv = s.uses * live * (pos + 1) * s.n_kv_heads * s.head_dim * 2 * w
    state = s.n_layers * live * (
        s.ssm_heads * s.ssm_state * s.ssm_head_dim * F32_BYTES
        + (s.ssm_conv - 1) * s.conv_dim * w) * 2
    return float(weights + kv + state)
