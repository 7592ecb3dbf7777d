"""Production mesh construction + logical-axis bindings.

`make_production_mesh` is a FUNCTION (never a module-level constant) so
importing this module never touches jax device state.  The production
target is TPU v5e: 16x16 = 256 chips per pod, 2 pods = 512 chips for
the multi-pod dry-run.  The "pod" axis is pure data parallelism by
construction — the only inter-pod traffic is the gradient all-reduce —
so scaling 2 -> N pods changes a single mesh dimension.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(shape))


def make_debug_mesh(n_devices: int | None = None, model: int = 2):
    """Small mesh over whatever devices exist (tests, CI)."""
    n = n_devices or len(jax.devices())
    model = min(model, n)
    return jax.make_mesh((n // model, model), ("data", "model"),
                         (AxisType.Auto,) * 2)


def axis_binding(mesh, *, shape_kind: str = "train",
                 seq_over_all: bool = False, recipe: str = "tp",
                 batch: int | None = None, allow_sp: bool = True) -> dict:
    """Logical->physical bindings for a mesh (see distributed.sharding).

    Two sharding recipes (EXPERIMENTS.md §Perf compares them per cell):

    "tp" (baseline, Megatron-style):
      dp  = ("pod","data")   batch
      tp  = ("model",)       heads/ffn/experts; also KV-seq for decode
      fsdp= ("data",)        weight sharding; pods replicate weights
      sp  = tp               residual stream S-sharded (dedupes vs tp)

    "fsdp" (dense-arch hillclimb: no activation all-reduces at all):
      dp  = every mesh axis when global_batch divides mesh.size —
            attention/MLP run fully local, the only collectives left
            are the FSDP param all-gathers + grad reduce-scatters.
            Otherwise dp = ("pod","data") and, for attention archs,
            sp = ("model",) (context parallelism).  SSM archs can't
            context-shard the chunk scan (allow_sp=False).
      tp  = ()               model axis carries NO tensor parallelism
      fsdp= ("data","model") weights fully sharded over the pod's chips

    vocab/embed_d (embedding + logits) are pinned to model/data in both
    recipes.  Decode cells ignore the recipe (the model axis is needed
    for KV sharding); `seq_over_all` spreads the KV-seq over
    ("data","model") (long_500k's batch-1 cache).
    """
    names = mesh.axis_names
    dp = tuple(a for a in ("pod", "data") if a in names)
    tp = ("model",) if "model" in names else ()
    fsdp = ("data",) if "data" in names else ()
    sp: tuple = ()
    if shape_kind in ("train", "prefill"):
        if recipe == "fsdp":
            fsdp = tuple(a for a in ("data", "model") if a in names)
            if batch is not None and batch % mesh.size == 0:
                dp = tuple(names)          # pure DP: fully local layers
                tp = ()
            elif allow_sp:
                sp = tp                    # context parallelism
                tp = ()
            # else (SSM, batch doesn't divide): keep tp — mamba heads
            # shard over model (the chunk scan is per-head independent)
        elif recipe == "ep":
            # experts over model (EP); batch over *everything* when it
            # divides (attention/MLP local — per-tensor dedupe drops tp
            # wherever dp already claimed the model axis); weights FSDP
            # over data.  The MoE combine reduces over model only.
            if batch is not None and batch % mesh.size == 0:
                dp = tuple(names)
            elif allow_sp:
                sp = tp                    # context parallel attention
        else:
            sp = tp
    seq = (("data", "model") if seq_over_all else ("model",))
    seq = tuple(a for a in seq if a in names)
    # MoE token groups follow the token sharding: dp, plus the sp axes
    # under context parallelism (so expert compute is never replicated
    # across an otherwise-idle model axis)
    moe_g = dp + tuple(a for a in sp if a not in dp and a not in tp)
    return dict(dp=dp, tp=tp, fsdp=fsdp, sp=sp, seq=seq, moe_g=moe_g,
                vocab=("model",) if "model" in names else (),
                embed_d=("data",) if "data" in names else (),
                recipe=recipe)
