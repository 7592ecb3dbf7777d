"""Unit tests for the logical-axis machinery the recipes rely on.

Pure-logic tests bind with mesh=None (axes kept, dedupe active); with
a real size-1 mesh every constraint correctly collapses to None.
"""
import jax
import pytest
from jax.sharding import PartitionSpec as P
from jax.sharding import AxisType

from repro.distributed import sharding as sh
from repro.launch.mesh import axis_binding


def teardown_function(_):
    sh.clear_mesh_axes()


def test_dedupe_first_dim_wins():
    sh.set_mesh_axes(dp=("data", "model"), tp=("model",))
    spec = sh.logical_spec(sh.DP, sh.TP, None)
    assert spec == P(("data", "model"), None, None)


def test_dedupe_tp_then_sp():
    sh.set_mesh_axes(dp=("data",), tp=("model",), sp=("model",))
    spec = sh.logical_spec(sh.DP, sh.TP, sh.SP, None)
    assert spec == P("data", "model", None, None)


def test_size1_mesh_drops_constraints():
    mesh = jax.make_mesh((1, 1), ("data", "model"), (AxisType.Auto,) * 2)
    sh.set_mesh_axes(dp=("data",), tp=("model",), mesh=mesh)
    spec = sh.logical_spec(sh.DP, sh.TP, shape=(4, 4))
    assert spec == P(None, None)


def test_divisibility_fallback_without_mesh():
    sh.set_mesh_axes(tp=("model",))
    # without a mesh, divisibility can't be checked: axes kept
    assert sh.logical_spec(sh.TP, shape=(7,)) == P("model")


def test_sp_active_logic():
    sh.set_mesh_axes(dp=("data",), tp=("model",), sp=("model",))
    assert not sh.sp_active()          # sp == tp: deduped
    sh.set_mesh_axes(dp=("data",), tp=(), sp=("model",))
    assert sh.sp_active()              # no mesh: trusted
    mesh = jax.make_mesh((1, 1), ("data", "model"), (AxisType.Auto,) * 2)
    sh.set_mesh_axes(dp=("data",), tp=(), sp=("model",), mesh=mesh)
    assert not sh.sp_active()          # |model| == 1


def test_axis_binding_recipes():
    mesh = jax.make_mesh((1, 1), ("data", "model"), (AxisType.Auto,) * 2)
    b = axis_binding(mesh, shape_kind="train", recipe="tp")
    assert b["tp"] == ("model",) and b["dp"] == ("data",)
    assert b["sp"] == ("model",)
    b = axis_binding(mesh, shape_kind="train", recipe="fsdp", batch=1)
    assert b["tp"] == () and set(b["fsdp"]) == {"data", "model"}
    assert b["dp"] == ("data", "model")      # batch divides mesh
    # fallback (batch unknown -> doesn't divide): SSM keeps head TP
    b = axis_binding(mesh, shape_kind="train", recipe="fsdp",
                     batch=None, allow_sp=False)
    assert b["tp"] == ("model",)
    # attention archs get context parallelism instead
    b = axis_binding(mesh, shape_kind="train", recipe="fsdp",
                     batch=None, allow_sp=True)
    assert b["tp"] == () and b["sp"] == ("model",)
    b = axis_binding(mesh, shape_kind="train", recipe="ep", batch=1)
    assert b["tp"] == ("model",) and b["dp"] == ("data", "model")
    b = axis_binding(mesh, shape_kind="decode")
    assert b["seq"] == ("model",)
    b = axis_binding(mesh, shape_kind="decode", seq_over_all=True)
    assert b["seq"] == ("data", "model")


def test_moe_g_includes_context_parallel_axes():
    mesh = jax.make_mesh((1, 1), ("data", "model"), (AxisType.Auto,) * 2)
    b = axis_binding(mesh, shape_kind="train", recipe="fsdp",
                     batch=None, allow_sp=True)
    assert b["sp"] == ("model",)
    assert b["moe_g"] == ("data", "model")
    b = axis_binding(mesh, shape_kind="train", recipe="tp")
    assert b["moe_g"] == ("data",)           # sp == tp: not added


def test_param_specs_moe_ff_sharded():
    from repro.configs import smoke_config
    from repro.models.transformer import init_params, param_specs
    mesh = jax.make_mesh((1, 1), ("data", "model"), (AxisType.Auto,) * 2)
    cfg = smoke_config("mixtral-8x22b")
    params = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.key(0))
    specs = param_specs(params, cfg, mesh, moe_ff_sharded=True)
    wg = specs["stages"][0]["b0"]["moe"]["w_gate"]
    assert isinstance(wg, P) and len(wg) == 4
