"""The family modules count what the published configurations have
always counted: each operation count and the program's ModelConfig,
pinned to the values the harness gave them before the counts moved into
bench/families/."""
import json

import pytest

from bench.lib.cell import load_module
from bench.tests.conftest import ROOT

# name: (matmul_flops_per_token, mixer_flops(pos=511),
#        sequence_flops(512), ModelConfig.param_count())
COUNTS = {
    "stablelm-3b": (5332664320.0, 167772160.0, 2773357690880.0, 2795274240),
    "mamba2-1.3b": (2684780544.0, 127500288.0, 1439887785984.0, 1343529984),
}


def model_config(name):
    from repro.models.config import Block, ModelConfig
    if name == "stablelm-3b":
        return ModelConfig(
            name="stablelm-3b", d_model=2560, n_heads=32, n_kv_heads=32,
            head_dim=80, d_ff=6912, vocab=50304,
            stages=((32, (Block("attn"),)),), rope_theta=10000,
            tie_embeddings=False, dtype="bfloat16")
    return ModelConfig(
        name="mamba2-1.3b", d_model=2048, n_heads=0, n_kv_heads=0,
        head_dim=0, d_ff=0, vocab=50280,
        stages=((48, (Block("mamba2"),)),), ssm_state=128, ssm_heads=64,
        ssm_head_dim=64, ssm_conv=4, ssm_chunk=256, tie_embeddings=True,
        dtype="bfloat16", subquadratic=True)


@pytest.mark.parametrize("name", sorted(COUNTS))
def test_published_counts_are_pinned(name):
    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                     .read_text())
    family = load_module("families", cfg["family"])
    s = family.spec(cfg)
    mcfg = family.model_config(s)
    got = (family.matmul_flops_per_token(s), family.mixer_flops(s, 511),
           family.sequence_flops(s, 512), mcfg.param_count())
    assert got == COUNTS[name]
    assert mcfg == model_config(name)
