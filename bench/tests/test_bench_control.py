"""The control of ``correct``: the plain reference one precision below the
configuration's, put in the program's place, comes out not correct through
the harness's own comparison.  Run here at a small size on the CPU;
``bench/control.py`` reads it on the chip at the cells' own sizes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import control
import run
from benchlib import model, spec

SMOKE = dict(img_size=32, num_classes=10, embed_dim=64, num_layers=2, num_heads=4,
             tokenizer_pools=[False, False, True, True])
# on the CPU the program computes in float32, so the configuration states it
FLOAT32 = {"weights": "float32", "matmul": "highest", "matmul_operands": "float32",
           "accumulate": "float32"}
TRAFFIC = dict(arrival="closed", batch=2, pool=2, sample=4,
               reference_block=2, trace_seconds=0.3)


@pytest.fixture(scope="module")
def harness():
    run.enable_compile_cache = lambda: "off"      # no cache writes from tests
    return run


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 6])
def test_control_in_the_programs_place_is_not_correct(harness, seed):
    cfg = spec.load_config("sif-8-384")
    cfg.update(SMOKE, precision=FLOAT32)
    fault = control.control(cfg, seed, TRAFFIC["reference_block"])
    res = harness.run_cell("sif-8-384.edge", cfg, TRAFFIC, seed, 0.3, False,
                           interpret=True, fault=fault)
    assert not res["correct"], res["checks"]
    assert res["checks"]["gap_median"]["value"] > res["checks"]["gap_median"]["limit"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_int8_control_fails_every_configuration(seed):
    cfg = spec.load_config("sif-8-384")
    cfg.update(SMOKE)
    arch = model.Arch.from_config(cfg)
    key = model.key_from_seed(seed)
    params, state = model.init_weights(key, arch, cfg["weights"])
    cal = jax.random.uniform(jax.random.fold_in(key, 1), (2, 32, 32, 3))
    state = model.forward(params, state, cal, arch, calibrate=True)
    folded = model.fold(params, state)
    imgs = jax.random.uniform(jax.random.fold_in(key, 2), (8, 32, 32, 3))
    ref = model.forward(None, None, imgs, arch, folded=folded,
                        operands=cfg["precision"]["matmul_operands"])
    ctl = model.forward(None, None, imgs, arch, folded=model.quantize_int8(folded),
                        operands="float32")
    same = model.forward(None, None, imgs, arch, folded=folded,
                         operands=cfg["precision"]["matmul_operands"])
    assert run.gaps(np.asarray(same), np.asarray(ref))["gap_max"] == 0.0
    got = run.gaps(np.asarray(ctl), np.asarray(ref))
    for c in spec.benchmark()["configs"]:
        limits = spec.load_config(c["name"])["limits"]
        assert any(got[k] > limits[k] for k in got if k in limits), (c["name"], got, limits)


def test_int8_rounding_is_per_output_channel():
    w = jnp.asarray(np.linspace(-1, 1, 12, dtype=np.float32).reshape(3, 4))
    q = model.quantize_int8({"w": w, "b": jnp.ones(4)})
    scale = np.abs(np.asarray(w)).max(axis=0) / 127
    steps = np.asarray(q["w"]) / scale
    assert np.allclose(steps, np.round(steps), atol=1e-4)
    assert np.array_equal(np.asarray(q["b"]), np.ones(4))
