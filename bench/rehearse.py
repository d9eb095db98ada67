"""Compile rehearsal of each cell's executor for a described TPU v5e.

No chip is needed: the TPU compiler that JAX ships compiles for a topology
that is only described.  For each configuration and batch size this prints
whether the whole jitted ``pallas+packed`` executor compiles, how many Pallas
kernels it holds (``tpu_custom_call``) and the compiler's memory analysis
(temp + arguments + outputs), which sizes the offline cell's batch.

    JAX_PLATFORMS=cpu python bench/rehearse.py sif-8-768:8,16,32,64 sif-8-384:1

A compile that passes is not a chip run: it says nothing of times or results.
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchlib import spec  # noqa: E402

GIB = 2 ** 30


def rehearse(config: str, batches: list[int]) -> None:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchlib import cell

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    cfg = spec.load_config(config)
    plan_shapes, meta = cell.abstract_plan(cfg)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), plan_shapes)
    from repro import engine
    from repro.engine.plan import DeployPlan

    fn = jax.jit(engine.make_apply_fn(DeployPlan(meta=meta, params=plan_shapes)))
    for b in batches:
        img = jax.ShapeDtypeStruct((b, cfg["img_size"], cfg["img_size"],
                                    cfg["in_channels"]), jnp.float32, sharding=one)
        try:
            compiled = fn.lower(params, img).compile()
        except Exception as e:  # report the compiler's refusal and go on
            print(f"{config} B={b}: DOES NOT COMPILE: {type(e).__name__}: {e}"[:2000])
            continue
        mem = compiled.memory_analysis()
        total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
                 + mem.output_size_in_bytes)
        print(f"{config} B={b}: compiles, "
              f"tpu_custom_call={compiled.as_text().count('tpu_custom_call')} "
              f"temp={mem.temp_size_in_bytes / GIB:.3f}GiB "
              f"args={mem.argument_size_in_bytes / GIB:.3f}GiB "
              f"out={mem.output_size_in_bytes / GIB:.4f}GiB "
              f"total={total / GIB:.3f}GiB", flush=True)


def main(argv: list[str]) -> int:
    for item in argv:
        config, _, sizes = item.partition(":")
        rehearse(config, [int(s) for s in sizes.split(",")])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
