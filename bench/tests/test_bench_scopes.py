"""From the compiled executor's HLO to the layer of each device operation
(``benchlib.scopes``), and the SSA's least time (``benchlib.ssa_work``).

The recorded pair in ``data/``: three batch-1 requests of
``sif-8-384.edge`` traced on one TPU v5e, and the op table of the same
executor compiled for a described v5e with the program's named scopes."""

import json
from pathlib import Path

import pytest

from benchlib import model, scopes, spec, ssa_work, traces, work

DATA = Path(__file__).parent / "data"

# One computation of each kind the rules meet: a compiler-made loop whose
# result a kernel reads, a weight copy, a fusion, a prefetch that nothing
# reads, TPU tilings in shapes and a tuple-shaped operand.
HLO = """\
HloModule jit_step, entry_computation_layout={(f32[8]{0}, f32[8]{0})->f32[8]{0}}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0:T(256)}) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[8]{0:T(256)}) %p), index=0
  %x = f32[8]{0:T(256)} get-tuple-element((s32[], f32[8]{0:T(256)}) %p), index=1
  %dynamic-update-slice.62 = f32[8]{0:T(256)} dynamic-update-slice(%x, %x, %i)
  %other = f32[8]{0:T(256)} add(%x, %x), metadata={op_name="jit(step)/block5/fc1/add"}
  ROOT %t = (s32[], f32[8]{0:T(256)}) tuple(%i, %dynamic-update-slice.62)
}

%cond (q: (s32[], f32[8])) -> pred[] {
  %q = (s32[], f32[8]{0:T(256)}) parameter(0)
  %j = s32[] get-tuple-element((s32[], f32[8]{0:T(256)}) %q), index=0
  %n = s32[] constant(4)
  ROOT %lt = pred[] compare(%j, %n), direction=LT
}

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %mul = f32[8]{0} multiply(%param_0, %param_0)
}

ENTRY %main.1 (w: f32[8], img: f32[8]) -> (f32[8], f32[8]) {
  %w = f32[8]{0:T(256)} parameter(0), metadata={op_name="params[\\'w\\']"}
  %img = f32[8]{0:T(256)} parameter(1), metadata={op_name="batch"}
  %zero = s32[] constant(0)
  %init = (s32[], f32[8]{0:T(256)}) tuple(%zero, %img)
  %while.35 = (s32[], f32[8]{0:T(256)}) while((s32[], f32[8]{0:T(256)}) %init), condition=%cond, body=%body
  %loop_out = f32[8]{0:T(256)} get-tuple-element((s32[], f32[8]{0:T(256)}) %while.35), index=1
  %copy.1 = f32[8]{0:T(256)} copy(%w)
  %lif_op.3 = f32[8]{0} custom-call(%loop_out, %copy.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/tokenizer/stage1/jit(_lif)/pallas_call"}, backend_config="(w) %x"
  %fusion.2 = f32[8]{0} fusion(%lif_op.3), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/shard_map/head/mul"}
  %prefetch = f32[8]{0:T(256)S(1)} copy(%w)
  ROOT %r = (f32[8]{0}, f32[8]{0:T(256)S(1)}) tuple(%fusion.2, %prefetch)
}
"""


def test_scope_of_keeps_the_executor_path():
    assert scopes.scope_of("jit(step)/block3/ssa/pallas_call") == "block3/ssa"
    assert scopes.scope_of(
        "jit(<unknown>)/shard_map/block0/q/jit(_lif)/pallas_call") == "block0/q"
    assert scopes.scope_of("jit(step)/add") is None
    # merged instructions: the first name with a scope
    assert scopes.scope_of("jit(step)/block0/q/reshape;jit(step)/block0/q/squeeze;"
                           "jit(step)/tokenizer/reshape") == "block0/q"
    assert scopes.scope_of("jit(step)/add;jit(step)/head/add") == "head"
    assert scopes.scope_of("params['blocks'][0]['q']['w']") is None
    assert scopes.scope_of("reduce_sum") is None


def test_op_scopes_rules():
    table = scopes.op_scopes(HLO)
    stage1, head = "tokenizer/stage1", "head"
    # the kernel's own op_name, and the kernel flag from its target
    assert table["lif_op.3"] == (stage1, True)
    assert not any(k for name, (_, k) in table.items() if name != "lif_op.3")
    # consumer rule: the weight copy and the loop that the kernel reads,
    # through the get-tuple-element, and the parameter the copy reads
    for name in ("copy.1", "loop_out", "while.35", "init", "zero", "img", "w"):
        assert table[name][0] == stage1, name
    # caller rule: the loop's body and condition take the loop's scope, even
    # where an instruction there names another; a fusion's computation too
    for name in ("dynamic-update-slice.62", "other", "p", "t", "q", "lt"):
        assert table[name][0] == stage1, name
    assert table["fusion.2"][0] == table["mul"][0] == table["param_0"][0] == head
    # operand rule: nothing scoped reads the prefetch or the module's result
    assert table["prefetch"][0] == stage1
    assert table["r"][0] == head


def test_scope_time_sums():
    table = scopes.op_scopes(HLO)
    device = [("copy.1", 0, 10), ("lif_op.3", 10, 30), ("fusion.2", 40, 5),
              ("unknown.9", 45, 5), ("while.35", 50, 20)]
    assert scopes.scope_ns(device, table, "tokenizer/*", 0, 100) == 60
    assert scopes.scope_ns(device, table, "tokenizer/*", 0, 100, kernel=True) == 30
    assert scopes.scope_ns(device, table, "*", 0, 100, kernel=False) == 35
    assert scopes.scope_ns(device, table, "*", 5, 60) == 5 + 30 + 5 + 10
    assert scopes.scope_ns(device, table, "*", 0, 100) == 65      # not unknown.9


def test_ssa_least_time_by_hand():
    """8-384 at batch 1: 196 tokens, d=384, T=4, 8 blocks.  FLOPs 2 x 2 x T x
    N^2 x d per block; bytes 3 q/k/v word planes of N x d uint32 and the
    T x N x d float32 drive per block."""
    arch = model.Arch.from_config(spec.load_config("sif-8-384"))
    assert work.ssa_flops(arch, 1) == 4 * 4 * 196 ** 2 * 384 * 8 == 1_888_223_232
    assert ssa_work.ssa_bytes(arch, 1) == (3 * 4 + 4 * 4) * 196 * 384 * 8 == 16_859_136
    least, bound = ssa_work.ssa_least_s(arch, 1, 197e12, 819e9)
    assert bound == "bytes"
    assert least == pytest.approx(16_859_136 / 819e9)        # 20.6 us
    assert work.ssa_flops(arch, 1) / 197e12 == pytest.approx(9.585e-6, rel=1e-3)
    # the byte count is linear in the batch
    assert ssa_work.ssa_bytes(arch, 64) == 64 * ssa_work.ssa_bytes(arch, 1)


def test_recorded_edge_trace_is_attributed_whole():
    """Every operation of the recorded trace has a scope in the recorded op
    table; the shares by layer are those of the recorded window."""
    d = json.loads((DATA / "edge_trace.json").read_text())
    trace = {"device": [tuple(e) for e in d["device"]],
             "spans": [tuple(e) for e in d["spans"]]}
    table = {k: tuple(v) for k, v in
             json.loads((DATA / "edge_op_scopes.json").read_text())["ops"].items()}
    lo, hi = traces.window(trace)
    _, calls = traces.span_ns(trace, "bench.request", lo, hi)
    assert calls == 3
    dev = trace["device"]
    total = sum(min(s + w, hi) - max(s, lo) for _, s, w in dev if s + w > lo and s < hi)
    assert all(table[n][0] for n, _, _ in dev)
    assert scopes.scope_ns(dev, table, "*", lo, hi) == pytest.approx(total)

    ssa = scopes.scope_ns(dev, table, "block*/ssa", lo, hi)
    assert 100 * ssa / total == pytest.approx(40.17, abs=0.01)
    assert ssa / calls / 1e3 == pytest.approx(1127.0, abs=0.5)            # us
    glue = scopes.scope_ns(dev, table, "*", lo, hi, kernel=False)
    assert glue / calls / 1e3 == pytest.approx(959.7, abs=0.5)
    tokenizer = [scopes.scope_ns(dev, table, f"tokenizer/stage{i}", lo, hi) / calls / 1e3
                 for i in range(4)]
    assert tokenizer == pytest.approx([280.0, 220.6, 149.1, 87.4], abs=0.1)
    # the SSA's share of its least time: bandwidth-bound, about 1.8%
    arch = model.Arch.from_config(spec.load_config("sif-8-384"))
    least, _ = ssa_work.ssa_least_s(arch, 1, 197e12, 819e9)
    assert 100 * calls * least / (ssa / 1e9) == pytest.approx(1.83, abs=0.01)
    # each block's SSA scope holds its one kernel, once per request
    kernels = [n for n, _, _ in dev if table[n] == ("block3/ssa", True)]
    assert len(kernels) == calls and len(set(kernels)) == 1
