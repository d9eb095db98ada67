"""``bench/run.py`` end to end on the CPU: it refuses to run without a chip,
and with the look for a chip skipped, a sound run of a small configuration
comes out correct while a run whose timed path is broken does not."""

import json
import subprocess
import sys

import numpy as np
import pytest

from benchlib import spec

ROOT = spec.ROOT
SMOKE = dict(img_size=32, num_classes=10, embed_dim=64, num_layers=2, num_heads=4,
             tokenizer_pools=[False, False, True, True],
             precision={"weights": "float32", "matmul": "highest",
                        "matmul_operands": "float32", "accumulate": "float32"})
TRAFFIC = dict(arrival="closed", batch=2, pool=2, sample=4,
               reference_block=2, trace_seconds=0.3)


@pytest.fixture(scope="module")
def run():
    import run as run_mod

    run_mod.enable_compile_cache = lambda: "off"      # no cache writes from tests
    return run_mod


def _smoke():
    cfg = spec.load_config("sif-8-768")
    cfg.update(SMOKE)
    return cfg


def test_refuses_without_a_chip():
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sif-8-768.offline",
         "--seed", "3000000000", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "refused" in proc.stderr


def test_sound_run_is_correct(run):
    res = run.run_cell("sif-8-384.edge", _smoke(), TRAFFIC, 2 ** 31 + 99, 0.5, False,
                       interpret=True)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"latency_p50_ms", "setup_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["checks"]["gap_median"]["value"] <= res["checks"]["gap_median"]["limit"]
    json.dumps({k: v for k, v in res.items() if k != "extras"})


def _breaks(compiled, alter):
    calls = {"n": 0}

    def broken(params, x):
        calls["n"] += 1
        return alter(np.array(compiled(params, x)), calls["n"])

    return broken


def test_an_altered_answer_is_caught(run):
    def alter(logits, n):
        logits[-1, 3] += 0.5 * np.abs(logits).max() + 1e-3
        return logits

    res = run.run_cell("sif-8-384.edge", _smoke(), TRAFFIC, 7, 0.5, False,
                       interpret=True, fault=lambda c: _breaks(c, alter))
    assert not res["correct"]
    assert res["checks"]["gap_median"]["value"] > res["checks"]["gap_median"]["limit"]


def test_an_answer_altered_on_a_later_call_is_caught(run):
    def alter(logits, n):
        if n > 6:                      # warm-up and first servings stay sound
            logits[0, 0] += 1e-3
        return logits

    res = run.run_cell("sif-8-384.edge", _smoke(), TRAFFIC, 8, 0.5, False,
                       interpret=True, fault=lambda c: _breaks(c, alter))
    assert not res["correct"]
    assert res["checks"]["repeat_mismatch"]["value"] > 0


def test_traced_run_reports_per_layer_metrics(run):
    peaks = spec.peaks("TPU v5 lite")
    res = run.run_cell("sif-8-384.edge", _smoke(), TRAFFIC, 9, 0.5, True,
                       interpret=True, peaks=peaks)
    assert res["correct"]
    dev = res["device"]
    assert dev["window_s"] > 0 and "busy_s" in dev
    # the CPU trace holds no TPU operations: the kernel readers find nothing
    assert "gemm_roofline.edge" not in res["metrics"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    # the profiler records only the window's end: the first requests run untraced
    traced = sum(name == "bench.request" for name, _, _ in res["extras"]["trace"]["spans"])
    assert 0 < traced < res["attempted"] // TRAFFIC["batch"]


def test_p95_reader_takes_only_the_untraced_requests():
    read = spec.load_metric("latency_p95_ms.edge")
    rows = [(0, i * 1.0, i * 1.0 + 0.004, None) for i in range(40)]
    rows += [(0, 40.0 + i, 40.0 + i + 0.5, None) for i in range(10)]    # traced, slower
    assert read({"rows": rows, "untraced": 40}) == pytest.approx(4.0)
    assert read({"rows": rows, "untraced": 5}) is None                  # too few to rank
