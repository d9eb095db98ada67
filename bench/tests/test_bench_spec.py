"""The loader finds every configuration, traffic mix and metric reader that
``BENCHMARK.json`` names, and refuses a device it has no peaks for."""

import json

import pytest

from benchlib import spec


def test_every_named_file_is_found():
    bench = spec.benchmark()
    for c in bench["configs"]:
        assert spec.load_config(c["name"])["name"] == c["name"]
    for w in bench["workloads"]:
        spec.workload(w["name"])
        traffic = spec.load_traffic(w["traffic"])
        assert traffic["batch"] >= 1
    for m in bench["per_layer"]:
        assert callable(spec.load_metric(m["name"]))


def test_each_cell_reports_setup_an_end_to_end_metric_and_a_per_layer_one():
    bench = spec.benchmark()
    for w in bench["workloads"]:
        e2e = {m["name"] for m in spec.cell_metrics(w["name"], "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        per_layer = spec.cell_metrics(w["name"], "per_layer")
        assert per_layer
        for m in per_layer:
            assert m["moves"] in e2e, (w["name"], m["name"])


def test_unknown_names_and_devices_are_refused():
    with pytest.raises(KeyError):
        spec.workload("no-such-cell")
    with pytest.raises(KeyError):
        spec.load_traffic("no-such-mix")
    with pytest.raises(KeyError):
        spec.load_metric("no-such-metric")
    with pytest.raises(KeyError):
        spec.peaks("TPU v0 imaginary")
    v5e = spec.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9


def test_configs_state_their_precision_and_limits():
    for c in spec.benchmark()["configs"]:
        cfg = spec.load_config(c["name"])
        assert cfg["precision"]["matmul_operands"] in ("bfloat16", "float32")
        assert set(cfg["limits"]) >= {"gap_median", "repeat_mismatch"}
        assert cfg["limits"]["repeat_mismatch"] == 0
        json.dumps(cfg)


def test_a_split_metric_falls_back_to_the_reader_of_its_quantity():
    def where(name):
        return spec.load_metric(name).__code__.co_filename

    assert where("executor_mfu.edge") == where("executor_mfu.offline")
    assert where("executor_mfu.edge").endswith("metrics/executor_mfu.py")


CONTRACT = ("make_weights", "pool", "build", "serve", "attempted", "end_to_end",
            "check", "context", "control", "rehearse")


def test_every_configuration_names_a_family_that_keeps_the_contract():
    for c in spec.benchmark()["configs"]:
        family = spec.load_family(spec.load_config(c["name"])["family"])
        assert all(callable(getattr(family, f)) for f in CONTRACT), c["name"]
    assert spec.load_family("vision").__file__.endswith("families/vision.py")


def test_an_unknown_family_is_refused_naming_the_missing_file():
    with pytest.raises(KeyError, match=r"families/no-such-family\.py is missing"):
        spec.load_family("no-such-family")
