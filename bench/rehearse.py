"""Compile rehearsal of each configuration's executables for a described
TPU v5e.

No chip is needed: the TPU compiler that JAX ships compiles for a topology
that is only described.  For each configuration and size this prints what
its family driver's ``rehearse`` reports: for vision, whether the whole
jitted ``pallas+packed`` executor compiles at that batch, how many Pallas
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

from benchlib import spec  # noqa: E402


def rehearse(config: str, sizes: list[int]) -> None:
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    cfg = spec.load_config(config)
    spec.load_family(cfg["family"]).rehearse(cfg, sizes, topo.devices[0])


def main(argv: list[str]) -> int:
    for item in argv:
        config, _, sizes = item.partition(":")
        rehearse(config, [int(s) for s in sizes.split(",")])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
