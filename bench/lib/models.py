"""What a configuration file becomes: the plain `Spec` that the references
and the operation counts read, and the program's own `ModelConfig`.

A configuration file's `family` key names a module,
bench/families/<family>.py, found by name like a cell's driver or
reference (`cell.load_module`: the cell's root first, then this
checkout), so a configuration of a new family joins by new files alone.
Such a module provides

  spec(cfg)                    the sizes as served: a frozen dataclass
                               (this `Spec`, or one of the family's own),
                               with the configuration's `departures`
                               applied (`served`)
  model_config(spec)           the program's ModelConfig
  matmul_flops_per_token(spec) the operations the model needs, from its
  mixer_flops(spec, pos)       shapes alone (bench/lib/flops.py keeps
  sequence_flops(spec, n)      what belongs to no family)

The harness and the drivers read only `family`, `name`, `vocab` and
`dtype` of a spec.  Its other fields are read only by the family's own
module and reference, and by a driver meant for that family (the kv
driver's page shape).
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
