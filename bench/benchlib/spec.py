"""Everything the harness finds by name: the benchmark file, configuration
files, traffic mixes, per-layer metric readers and the table of peaks.

A configuration, a traffic mix or a per-layer metric is added by adding its
file and its entry in ``BENCHMARK.json``; nothing here names one.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _entry(kind: str, name: str) -> dict:
    for e in benchmark()[kind]:
        if e["name"] == name:
            return e
    raise KeyError(f"no {kind[:-1]} named {name!r} in BENCHMARK.json")


def workload(name: str) -> dict:
    return _entry("workloads", name)


def load_config(name: str) -> dict:
    return json.loads((ROOT / _entry("configs", name)["file"]).read_text())


def load_traffic(name: str) -> dict:
    path = BENCH_DIR / "traffic" / f"{name}.json"
    if not path.is_file():
        raise KeyError(f"no traffic mix {name!r} ({path} is missing)")
    return json.loads(path.read_text())


def load_metric(name: str):
    """The ``read(ctx)`` function of one per-layer metric's reader:
    ``metrics/<name>.py``, or for a quantity split by cell kind
    (``executor_mfu.edge``) the reader of the whole quantity
    (``metrics/executor_mfu.py``) where the split has none of its own."""
    candidates = [BENCH_DIR / "metrics" / f"{name}.py",
                  BENCH_DIR / "metrics" / f"{name.split('.')[0]}.py"]
    path = next((p for p in candidates if p.is_file()), None)
    if path is None:
        raise KeyError(f"no reader for metric {name!r} ({candidates[0]} is missing)")
    mod_spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> dict:
    table = json.loads((BENCH_DIR / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"peaks.json has {sorted(table)}")
    return table[device_kind]


def cell_metrics(cell: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries that this cell reports."""
    return [m for m in benchmark()[kind]
            if "workloads" not in m or cell in m["workloads"]]
