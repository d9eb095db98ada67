"""The reduction from a trace to numbers, on a small recorded trace: three
batch-1 requests of ``sif-8-384.edge`` on one TPU v5e (``data/``)."""

import json
from pathlib import Path

import numpy as np
import pytest

from benchlib import model, spec, traces, work

DATA = Path(__file__).parent / "data" / "edge_trace.json"


@pytest.fixture(scope="module")
def trace():
    d = json.loads(DATA.read_text())
    return {"device": [tuple(e) for e in d["device"]],
            "spans": [tuple(e) for e in d["spans"]]}


def _busy_by_sweep(device, lo, hi):
    """Busy time by a second method: sort, then extend the covered end."""
    iv = np.array(sorted((max(s, lo), min(s + d, hi)) for _, s, d in device
                         if s + d > lo and s < hi))
    total, end = 0.0, -np.inf
    for s, e in iv:
        if e > end:
            total += e - max(s, end)
            end = e
    return total


def test_window_and_requests(trace):
    lo, hi = traces.window(trace)
    assert lo == 0 and hi > 0
    total, count = traces.span_ns(trace, "bench.request", lo, hi)
    assert count == 3
    assert total == pytest.approx(sum(d for n, _, d in trace["spans"] if n == "bench.request"))
    assert traces.span_ns(trace, "bench.request", lo + 1, hi)[1] == 2


def test_busy_and_idle_share(trace):
    lo, hi = traces.window(trace)
    busy = traces.busy_ns(trace["device"], lo, hi)
    assert busy == pytest.approx(_busy_by_sweep(trace["device"], lo, hi))
    idle = sum(e - s for s, e in traces.gaps(trace["device"], lo, hi))
    assert busy + idle == pytest.approx(hi - lo)
    assert 0 < busy < hi - lo


def test_kernel_grouping_finds_every_pallas_kernel(trace):
    """Each request runs 119 Pallas kernels: 51 spike GEMMs (3 tokenizer
    convs, 8 x 6 block units), 60 LIF epilogues (4 tokenizer stages, 8 x 7)
    and 8 SSAs; the metric readers' name patterns find exactly those."""
    import fnmatch

    names = [n for n, _, _ in trace["device"]]
    gemm = [n for n in names if fnmatch.fnmatchcase(n, "*spike_matmul_op*")]
    lif = [n for n in names if fnmatch.fnmatchcase(n, "lif*_op*")]
    ssa = [n for n in names if fnmatch.fnmatchcase(n, "*ssa_op*")]
    assert (len(gemm), len(lif), len(ssa)) == (3 * 51, 3 * 60, 3 * 8)
    lo, hi = traces.window(trace)
    by_hand = sum(d for n, _, d in trace["device"] if n.startswith("packed_spike_matmul_op"))
    assert traces.kernel_ns(trace["device"], "*spike_matmul_op*", lo, hi) == pytest.approx(by_hand)


def test_idle_gaps_are_named_by_the_host_span_they_fall_in(trace):
    lo, hi = traces.window(trace)
    named = traces.idle_gaps(trace, lo, hi, n=5)
    longest = sorted((e - s for s, e in traces.gaps(trace["device"], lo, hi)), reverse=True)
    assert [g for _, g in named] == pytest.approx([x / 1e9 for x in longest[:5]])
    assert all(name.startswith("bench.") for name, _ in named)
    idle, count = traces.idle_ns_in_spans(trace, "bench.request", lo, hi)
    assert count == 3
    # the requests cover the window but for the host's gaps between them
    between = (hi - lo) - sum(d for n, _, d in trace["spans"] if n == "bench.request")
    total_idle = sum(e - s for s, e in traces.gaps(trace["device"], lo, hi))
    assert idle == pytest.approx(total_idle - between, rel=1e-6, abs=1e3)


def test_top_ops_rank_device_time(trace):
    lo, hi = traces.window(trace)
    top = traces.top_ops(trace["device"], lo, hi, n=10)
    assert len(top) == 10
    assert [t for _, t in top] == sorted((t for _, t in top), reverse=True)
    assert any(name.startswith("packed_ssa_op") for name, _ in top)


def test_op_name_takes_the_instruction_name():
    text = ('%packed_spike_matmul_op.51 = f32[4,8,256]{2,1,0} custom-call(u32[8,896] '
            '%pad.30), custom_call_target="tpu_custom_call"')
    assert traces.op_name(text) == "packed_spike_matmul_op.51"
    assert traces.op_name("copy-start.55") == "copy-start.55"


def _spread(gap_ns):
    """Two 10 us requests, each with 5 us of device work, ``gap_ns`` apart."""
    spans = [("bench.request", 0, 10_000), ("bench.request", gap_ns, 10_000)]
    device = [("op", 2_000, 5_000), ("op", gap_ns + 2_000, 5_000)]
    trace = {"spans": spans, "device": device}
    lo, hi = traces.window(trace)
    arch = model.Arch.from_config(spec.load_config("sif-8-384"))
    return {"trace": trace, "lo": lo, "hi": hi, "arch": arch, "batch": 1,
            "peaks": spec.peaks("TPU v5 lite")}


@pytest.mark.parametrize("metric", ["executor_mfu", "idle_share"])
def test_request_metrics_ignore_the_wait_between_requests(metric):
    """A client that sends at a fixed rate waits between frames: that time
    is not the executor's, so a faster executor cannot read as worse."""
    read = spec.load_metric(metric)
    near, far = read(_spread(20_000)), read(_spread(1_000_000))
    assert near == pytest.approx(far)
    if metric == "idle_share":
        assert near == pytest.approx(50.0)
    else:
        flops = 2 * work.model_flops(_spread(0)["arch"], 1)
        assert near == pytest.approx(100 * flops / (20e-6 * 197e12))
