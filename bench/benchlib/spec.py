"""Everything the harness finds by name: the benchmark file, configuration
files, traffic mixes, model family drivers, per-layer metric readers and the
table of peaks.

A configuration, a traffic mix, a model family or a per-layer metric is
added by adding its file and its entry in ``BENCHMARK.json``; nothing here
names one.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
# where family drivers and metric readers are looked up, in order
FAMILY_DIRS = [BENCH_DIR / "families"]
METRIC_DIRS = [BENCH_DIR / "metrics"]


def program_on_path() -> None:
    """Put the program under test (the checkout's ``src/``) on the import
    path; family drivers call this before they import it."""
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))


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


def _load_module(kind: str, name: str, path: Path):
    mod_spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load_family(name: str):
    """The driver module of one model family: ``families/<name>.py`` in the
    first of :data:`FAMILY_DIRS` that has it.  Its contract is the docstring
    of ``bench/families/__init__.py``."""
    candidates = [d / f"{name}.py" for d in FAMILY_DIRS]
    path = next((p for p in candidates if p.is_file()), None)
    if path is None:
        raise KeyError(f"no driver for model family {name!r} ({candidates[0]} is missing)")
    return _load_module("family", name, path)


def load_metric(name: str):
    """The ``read(ctx)`` function of one per-layer metric's reader:
    ``metrics/<name>.py``, or for a quantity split by cell kind
    (``executor_mfu.edge``) the reader of the whole quantity
    (``metrics/executor_mfu.py``) where the split has none of its own,
    each looked up in :data:`METRIC_DIRS` in turn."""
    candidates = [d / f"{n}.py" for n in (name, name.split(".")[0]) for d in METRIC_DIRS]
    path = next((p for p in candidates if p.is_file()), None)
    if path is None:
        raise KeyError(f"no reader for metric {name!r} ({candidates[0]} is missing)")
    return _load_module("metric", name, path).read


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
