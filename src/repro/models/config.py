"""Architecture configuration.

A model is a list of *stages*; each stage is (repeat, [block, ...]) and
is executed as `jax.lax.scan` over the repeat dimension with the inner
blocks unrolled.  This keeps the lowered HLO small (512-way SPMD
compiles stay tractable) while expressing repeating patterns such as
gemma3's 5-local:1-global or zamba2's shared-attention-every-6.

Block kinds:
    attn        — pre-norm GQA attention (+ SwiGLU MLP) with optional
                  sliding window (cfg.window or block override)
    moe         — attention + mixture-of-experts MLP
    mamba2      — pre-norm Mamba2 SSD mixer (no MLP)
    shared_attn — zamba2's shared transformer block, run before the next
                  mamba2 block: attention + gated-GELU MLP on
                  concat(x, embedding output), weights shared by every use
                  of block `mem` (outside the scan), with a per-use
                  adapter and linear layer (stacked in the stage); its
                  output joins only that mamba2 block's norm input
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Block:
    kind: str                    # attn | moe | mamba2 | shared_attn
    window: int | None = None    # sliding-window size (None = full causal)
    mem: int = 0                 # shared_attn: the shared block this use runs


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    stages: tuple = ()           # tuple[(repeat, tuple[Block,...]), ...]
    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    # --- SSM (mamba2) ---
    ssm_state: int = 0
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_conv: int = 4
    ssm_chunk: int = 256
    ssm_ngroups: int = 1         # B/C groups: head h reads h // (heads/groups)
    ssm_conv_bias: bool = False
    # --- shared attention (zamba2) ---
    shared_attn_d_ff: int = 0
    adapter_rank: int = 0        # per-use rank of the shared MLP's adapter
    # --- misc ---
    norm_eps: float = 1e-6
    attn_scale: float | None = None   # softmax scale; None: head_dim ** -0.5
    rope_theta: float = 500_000.0
    flash_chunk: int = 1024      # q/kv tile size of the jnp flash path
    kv_quant: bool = False       # int8 decode KV cache (per-token scales)
    tie_embeddings: bool = False
    frontend: str | None = None   # "audio" | "vision" stub (input_specs)
    dtype: str = "bfloat16"
    remat: str = "block"          # none | block
    # long-context capability: archs able to run the 500k decode shape
    subquadratic: bool = False

    @property
    def n_layers(self) -> int:
        return sum(r * len(blocks) for r, blocks in self.stages)

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Channels of the Mamba2 conv: [x, B, C]."""
        return self.d_inner + 2 * self.ssm_ngroups * self.ssm_state

    @property
    def n_mem_blocks(self) -> int:
        """Shared transformer blocks (zamba2's num_mem_blocks)."""
        return max((b.mem + 1 for _, blocks in self.stages for b in blocks
                    if b.kind == "shared_attn"), default=0)

    def param_count(self) -> int:
        """Analytic parameter count (for 6ND roofline bookkeeping): every
        weight of init_params but the final norm."""
        d, hd = self.d_model, self.head_dim
        n = self.vocab * d * (1 if self.tie_embeddings else 2)

        def attn(d_in):
            return d_in * hd * (self.n_heads + 2 * self.n_kv_heads) \
                + self.n_heads * hd * d
        # the shared blocks, once each: attention on concat(x, x0)
        n += self.n_mem_blocks * (attn(2 * d) + 3 * d * self.shared_attn_d_ff
                                  + 3 * d)
        for repeat, blocks in self.stages:
            for b in blocks:
                if b.kind in ("attn", "moe"):
                    if b.kind == "moe":
                        mlp = self.n_experts * 3 * d * self.d_ff \
                            + d * self.n_experts
                    else:
                        mlp = 3 * d * self.d_ff
                    cnt = attn(d) + mlp + 2 * d
                elif b.kind == "shared_attn":
                    # per use: the linear layer and the gate/up adapter
                    cnt = d * d + self.adapter_rank * (
                        d + 2 * self.shared_attn_d_ff)
                elif b.kind == "mamba2":
                    di, ns, nh = self.d_inner, self.ssm_state, self.ssm_heads
                    cdim = self.ssm_conv_dim
                    cnt = d * (2 * di + 2 * self.ssm_ngroups * ns + nh) \
                        + di * d + cdim * (self.ssm_conv
                                           + self.ssm_conv_bias) \
                        + 3 * nh + di + d
                else:
                    raise ValueError(b.kind)
                n += cnt * repeat
        return int(n)

    def active_param_count(self) -> int:
        """Active params per token (MoE: top_k of n_experts)."""
        if not self.n_experts:
            return self.param_count()
        d = self.d_model
        total = self.param_count()
        moe_blocks = sum(r for r, blocks in self.stages
                         for b in blocks if b.kind == "moe")
        dead = moe_blocks * (self.n_experts - self.top_k) * 3 * d * self.d_ff
        return int(total - dead)
