"""Compile a cell's decode step for a described TPU v5e, without a chip,
and print its memory analysis.

    JAX_PLATFORMS=cpu python3 bench/tools/compile_rehearsal.py stablelm-3b mamba2-1.3b

Takes the batch and cache length from each configuration file's
`serving` entry.  Kept out of the test suite: it compiles whole models.
"""
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import json  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from bench.lib.cell import load_module  # noqa: E402

GiB = 2**30


def main(names):
    from jax.experimental import topologies
    from repro.models.transformer import init_cache, init_params
    from repro.serving import engine
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def on(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    for name in names:
        cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                         .read_text())
        family = load_module("families", cfg["family"])
        spec = family.spec(cfg)
        mcfg = family.model_config(spec)
        B, S = cfg["serving"]["batch"], cfg["serving"]["max_len"]
        params = on(jax.eval_shape(
            lambda: init_params(jax.random.key(0), mcfg)))
        cache = on(jax.eval_shape(lambda: init_cache(mcfg, B, S)))
        toks = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one)
        pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
        m = engine._decode.lower(params, mcfg, cache, toks, pos) \
            .compile().memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes - m.alias_size_in_bytes)
        print(f"{name} decode step B={B} S={S}: args "
              f"{m.argument_size_in_bytes / GiB:.2f} GiB, out "
              f"{m.output_size_in_bytes / GiB:.2f}, temp "
              f"{m.temp_size_in_bytes / GiB:.2f}, alias "
              f"{m.alias_size_in_bytes / GiB:.2f} -> {total / GiB:.2f} GiB",
              flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
