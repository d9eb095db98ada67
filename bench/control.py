"""The control of ``correct``, and the readings that set its limits.

The control is the plain reference computed one precision below the one the
configuration states (int8 weights where it states bfloat16 operands,
bfloat16 operands where it states float32), put in the executor's place
through ``run.run_cell``'s ``fault`` hook: the window drives it at the
cell's own load, and the harness's own comparison has to find it not
correct.  For each seed this runs the cell as ``run.py`` does, for each
control seed also with the control in the program's place, and prints one
JSON line per run with its numbers and ``correct``.

    python bench/control.py --workload sif-8-768.offline --seeds 1,2,3 \
        --control-seeds 1,2,3 --seconds 2

The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import run
from benchlib import spec


def control(cfg: dict, seed: int, block: int):
    """A ``fault`` hook for ``run.run_cell``: the reference one precision
    below the configuration's, on the benchmark's weights for ``seed``,
    serving each batch in blocks of ``block`` rows."""
    import jax
    import jax.numpy as jnp

    from benchlib import cell, model

    arch = model.Arch.from_config(cfg)
    params, state = cell.make_weights(cfg, seed)
    folded = jax.jit(model.fold)(params, state)
    operands = cfg["precision"]["matmul_operands"]
    if operands == "bfloat16":
        folded, operands = jax.jit(model.quantize_int8)(folded), "float32"
    elif operands == "float32":
        operands = "bfloat16"
    else:
        raise ValueError(f"no control below {operands!r} operands")
    fn = jax.jit(lambda f, x: model.forward(None, None, x, arch, operands=operands,
                                            folded=f))

    def served(_params, x):
        return jnp.concatenate([fn(folded, x[s:s + block])
                                for s in range(0, x.shape[0], block)])

    return lambda _compiled: served


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    import jax

    if jax.default_backend() != "tpu":
        print("[control] refused: JAX found no TPU", file=sys.stderr)
        return run.EXIT_NO_CHIP
    wl = spec.workload(args.workload)
    cfg = spec.load_config(wl["config"])
    traffic = spec.load_traffic(wl["traffic"])
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]
    for seed, served_by in ([(s, "program") for s in seeds]
                            + [(s, "control") for s in control_seeds]):
        fault = (control(cfg, seed, traffic["reference_block"])
                 if served_by == "control" else None)
        res = run.run_cell(args.workload, cfg, traffic, seed, args.seconds, False,
                           fault=fault)
        print(json.dumps({"seed": seed, "served_by": served_by,
                          "correct": res["correct"], "checks": res["checks"],
                          "requests": res["attempted"] // traffic["batch"],
                          "rates": res["extras"]["rates"]}), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
