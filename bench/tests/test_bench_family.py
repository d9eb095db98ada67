"""A model family is added by files alone: a toy family, copied to a
directory of its own and put on the harness's search paths, runs through
``run.run_cell`` with no edit to any harness file, reports the runner's
``setup_s`` and ``latency_p50_ms``, its own end-to-end metric and its own
per-layer reading, and an altered answer comes out not correct.  The
family-agnostic runner names no family, and its window keeps the tracing
rules."""

import re
import shutil
from pathlib import Path

import numpy as np
import pytest

from benchlib import spec

TOY = Path(__file__).parent / "toy"
CELL = "toy-64.paced"
CFG = {"name": "toy-64", "family": "toy", "width": 64, "outputs": 16,
       "limits": {"gap_max": 1e-3}}
TRAFFIC = {"arrival": "periodic", "rate_per_s": 400, "batch": 4, "pool": 3,
           "trace_seconds": 0.2}
BENCH = {
    "configs": [{"name": "toy-64"}],
    "workloads": [{"name": CELL, "config": "toy-64", "traffic": "paced", "chips": 1}],
    "end_to_end": [
        {"name": "rows_per_s", "unit": "rows/s", "workloads": [CELL]},
        {"name": "latency_p50_ms", "unit": "ms"},
        {"name": "setup_s", "unit": "s"},
    ],
    "per_layer": [{"name": "toy_request_ms", "unit": "ms", "moves": "latency_p50_ms",
                   "workloads": [CELL]}],
}


@pytest.fixture
def toy(tmp_path, monkeypatch):
    import run

    where = shutil.copytree(TOY, tmp_path / "toy")
    monkeypatch.setattr(spec, "FAMILY_DIRS", [*spec.FAMILY_DIRS, where / "families"])
    monkeypatch.setattr(spec, "METRIC_DIRS", [*spec.METRIC_DIRS, where / "metrics"])
    monkeypatch.setattr(spec, "benchmark", lambda: BENCH)
    monkeypatch.setattr(run, "enable_compile_cache", lambda: "off")
    return run


def test_a_family_is_found_on_the_search_path(toy):
    fam = spec.load_family("toy")
    assert fam.__file__.startswith(str(spec.FAMILY_DIRS[-1]))
    assert callable(spec.load_metric("toy_request_ms"))


def test_toy_family_runs_through_the_harness(toy):
    res = toy.run_cell(CELL, CFG, TRAFFIC, 2 ** 31 + 17, 0.4, False, interpret=True)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"rows_per_s", "latency_p50_ms", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["attempted"] == res["extras"]["requests"] * TRAFFIC["batch"]
    # paced at 400/s: about 160 requests in 0.4 s
    assert 40 <= res["extras"]["requests"] <= 161


def test_toy_family_reports_its_per_layer_reading(toy):
    res = toy.run_cell(CELL, CFG, TRAFFIC, 5, 0.5, True, interpret=True,
                       peaks=spec.peaks("TPU v5 lite"))
    assert res["correct"]
    assert set(res["metrics"]) == {"toy_request_ms"}
    assert res["metrics"]["toy_request_ms"]["value"] > 0
    traced = sum(n == "bench.request" for n, _, _ in res["extras"]["trace"]["spans"])
    assert 0 < traced < res["extras"]["requests"]


@pytest.mark.parametrize("which", ["first", "later"])
def test_toy_family_altered_answer_is_not_correct(toy, which):
    calls = {"n": 0}

    def fault(compiled):
        def broken(w, x):
            calls["n"] += 1
            out = np.array(compiled(w, x))
            if which == "first" or calls["n"] > 5:
                out[0, 0] += 0.01
            return out
        return broken

    res = toy.run_cell(CELL, CFG, TRAFFIC, 9, 0.3, False, interpret=True, fault=fault)
    assert not res["correct"]
    assert res["checks"]["gap_max"]["value"] > CFG["limits"]["gap_max"]


def test_toy_control_is_not_correct(toy):
    fault = spec.load_family("toy").control(CFG, 3, TRAFFIC)
    res = toy.run_cell(CELL, CFG, TRAFFIC, 3, 0.2, False, interpret=True, fault=fault)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("path", ["run.py", "benchlib/spec.py"])
def test_the_runner_names_no_family(path):
    """No family, no configuration key of one, and no end-to-end metric but
    the runner's own two."""
    src = (spec.BENCH_DIR / path).read_text()
    named = set(re.findall(r'"([a-z_0-9]+)"', src))
    assert not named & {"vision", "img_size", "embed_dim", "in_channels", "batch",
                        "images_per_s", "precision", "arch"}
    e2e = {m["name"] for m in spec.benchmark()["end_to_end"]}
    assert named & e2e <= {"setup_s", "latency_p50_ms"}
