"""Benchmark driver: one entry per paper table/figure + framework benches.

Prints ``name,us_per_call,derived`` CSV lines per the harness contract, then
each benchmark's own detailed report.

  engine  -- deploy plan (BN folded, IAND fused) vs naive eval graph
  packed  -- bit-packed spike datapath: inter-layer bytes + wall clock
  lm      -- spiking-LM deploy plan: tokens/s + activation bytes, dense vs
             packed (RMSNorm folded, backend-dispatched causal SSA)
  sparsity -- occupancy-map zero-word skipping: measured skip rates + decode
             tokens/s dense vs packed vs sparse-packed on the trained-fixture
             checkpoint (real activations)
  sharded -- cross-device spike bytes of mesh-sharded plans on a (1, 2)
             mesh: analytic ring-collective pricing per crossing edge,
             dense f32 vs packed uint32 words, cross-checked against the
             jaxpr-measured collective wire bytes on a forced 2-device host
             mesh
  table1  -- IAND vs ADD residual training proxy (paper Table I)
  table2  -- serial vs parallel tick-batching weight traffic (Table II /
             the -43.2% weight-access claim)
  kernels -- Pallas kernel microbench at paper layer shapes
  linear  -- beyond-paper linear-ordering scaling (500k-context spiking)

The roofline table (EXPERIMENTS.md S Roofline) is produced separately by
``python -m benchmarks.roofline --all`` (it compiles against the 256-chip
production mesh and takes ~1h on this CPU).
"""

from __future__ import annotations

import json
import pathlib
import time

BENCH_JSON = pathlib.Path(__file__).resolve().parent.parent / "BENCH_engine.json"


def _run(name, fn):
    t0 = time.perf_counter()
    out = fn()
    us = (time.perf_counter() - t0) * 1e6
    print(f"CSV,{name},{us:.0f},ok")
    return out


def write_bench_json(engine_result, packed_result, lm_result=None,
                     sparsity_result=None, sharded_result=None,
                     serve_result=None) -> None:
    """Persist the engine perf trajectory machine-readably: per-config
    tokens/s and inter-layer activation bytes, tracked across PRs.

    ``packed_reduction_ssa_dense`` prices the q/k/v attention edges under the
    packed Pallas deploy backend in EVERY row: its ``packed_ssa_merged_op`` kernel
    consumes the words directly (``ssa_boundary_closed`` True), so the column
    equals ``packed_reduction`` -- the full 8x/32x contract.
    ``packed_reduction_ssa_open`` is the uniform companion column pricing
    those edges dense (the jnp oracle unpacks at the attention op boundary).
    ``@T32`` rows record the 32-steps-per-word ceiling."""
    configs = {}
    for table, suffix in (("table1_t8", ""), ("table1_t32", "@T32")):
        for row in packed_result.get(table, ()):
            configs[f"{row['config']}{suffix}"] = {
                "t": row["t"],
                "activation_bytes_dense": row["dense_bytes"],
                "activation_bytes_packed": row["packed_bytes"],
                "packed_reduction": row["reduction"],
                "ssa_boundary_closed": row["ssa_boundary_closed"],
                "packed_reduction_ssa_dense": row["reduction_ssa_dense"],
                "packed_reduction_ssa_open": row["reduction_ssa_open"],
            }
    m = packed_result["measured"]
    measured_key = m["config"]
    configs[measured_key] = {
        "t": m["t"],
        "batch": m["batch"],
        "tokens_per_s_dense": m["dense_tokens_per_s"],
        "tokens_per_s_packed": m["packed_tokens_per_s"],
        "activation_bytes_dense": m["dense_bytes"],
        "activation_bytes_packed": m["packed_bytes"],
        "packed_reduction": m["reduction"],
        "ssa_boundary_closed": m["ssa_boundary_closed"],
        "packed_reduction_ssa_dense": m["reduction_ssa_dense"],
        "packed_reduction_ssa_open": m["reduction_ssa_open"],
    }
    if engine_result is not None:
        # same small config, but the engine bench runs its own batch size --
        # keep its metrics in a sub-record with that batch, not mixed into
        # the measured row's batch-4 fields
        from benchmarks import engine_fused_vs_naive

        configs[measured_key]["fused_vs_naive"] = {
            "batch": engine_fused_vs_naive.BATCH,
            "fused_wall_s": engine_result["fused"]["wall_s"],
            "naive_wall_s": engine_result["naive"]["wall_s"],
            "hlo_bytes_fused": engine_result["fused"]["bytes"],
            "hlo_bytes_naive": engine_result["naive"]["bytes"],
        }
    if lm_result is not None:
        # LM deploy-plan rows (benchmarks/lm_plan.py): analytic traffic at
        # the measured and 500k-token lengths per T, plus the measured
        # tokens/s row -- same column names as the vision rows
        for table, suffix in (("lm_t8", ""), ("lm_t32", "@T32")):
            for row in lm_result.get(table, ()):
                entry = {
                    "t": row["t"],
                    "seq_len": row["seq_len"],
                    "attn_ordering": row["ordering"],
                    "activation_bytes_dense": row["dense_bytes"],
                    "activation_bytes_packed": row["packed_bytes"],
                    "packed_reduction": row["reduction"],
                    "ssa_boundary_closed": row["ssa_boundary_closed"],
                    "packed_reduction_ssa_dense": row["reduction_ssa_dense"],
                    "packed_reduction_ssa_open": row["reduction_ssa_open"],
                }
                # @S500k rows: measured prefill+step incremental decode
                # (benchmarks/lm_plan.py measured_decode -- step cost
                # asserted flat in the prefix length)
                for key in ("batch", "prefill_seq_len", "prefill_tokens_per_s",
                            "decode_tokens_per_s", "decode_step_wall_s",
                            "decode_step_flat_ratio", "decode_state_bytes",
                            "decode_dense_bytes_per_token",
                            "decode_packed_bytes_per_token"):
                    if key in row:
                        entry[key] = row[key]
                configs[f"{row['config']}{suffix}"] = entry
        lm = lm_result["measured"]
        configs[lm["config"]] = {
            "t": lm["t"],
            "batch": lm["batch"],
            "seq_len": lm["seq_len"],
            "tokens_per_s_dense": lm["dense_tokens_per_s"],
            "tokens_per_s_packed": lm["packed_tokens_per_s"],
            "activation_bytes_dense": lm["dense_bytes"],
            "activation_bytes_packed": lm["packed_bytes"],
            "packed_reduction": lm["reduction"],
            "ssa_boundary_closed": lm["ssa_boundary_closed"],
            "packed_reduction_ssa_dense": lm["reduction_ssa_dense"],
            "packed_reduction_ssa_open": lm["reduction_ssa_open"],
        }
        # chunked resumable prefill (lm_plan.measured_chunked_prefill):
        # bit-exact C-token steps through the DecodeState carry, resident
        # bytes flat in the prompt length -- the @S500k-chunked rows
        from benchmarks import lm_plan

        configs.update(lm_plan.bench_configs(lm_result))
    if sparsity_result is not None:
        # sparsity rows (benchmarks/sparsity.py): measured occupancy skip
        # rates + bare decode-step tokens/s on the trained-fixture checkpoint
        # -- real activations, dense vs packed vs sparse-packed, logits
        # asserted bit-exact across all three (bundling off)
        for row in sparsity_result["rows"]:
            entry = {k: row[k] for k in (
                "t", "batch", "ordering",
                "prompt_len", "bit_exact", "skip_rate", "word_zero_rate",
                "occ_tile_zero_rate", "token_granule_zero_rate", "spike_rate",
                "decode_tokens_per_s_dense", "decode_tokens_per_s_packed",
                "decode_tokens_per_s_sparse_packed", "sparse_over_packed")}
            entry["checkpoint"] = sparsity_result["checkpoint"]
            entry["bundle"] = sparsity_result["bundle"]
            configs[f"{row['config']}@sparse-T{row['t']}"] = entry
    if sharded_result is not None:
        # mesh rows (benchmarks/sharded_traffic.py): analytic cross-device
        # ring-collective wire bytes per crossing spike edge on a (data,
        # model) mesh, dense f32 vs packed uint32 words -- the interconnect
        # keeps the full T/ceil(T/32) packing factor (8x at T=8) because the
        # collectives move the SAME words as the on-chip datapath
        d, m = sharded_result["mesh"]
        measured = sharded_result["measured"]
        for row in sharded_result["rows"]:
            entry = {
                "t": row["t"],
                "family": row["family"],
                "mesh": row["mesh"],
                "crossing_edges": row["crossing_edges"],
                "cross_device_bytes_dense": row["cross_device_dense_bytes"],
                "cross_device_bytes_packed": row["cross_device_packed_bytes"],
                "cross_device_reduction": row["cross_device_reduction"],
            }
            if "seq_len" in row:
                entry["seq_len"] = row["seq_len"]
            if measured is not None and row["family"] in measured:
                mm = measured[row["family"]]
                entry["measured_wire"] = {
                    "config": mm["config"], "t": mm["t"],
                    "wire_bytes": mm["wire_bytes"], "dtypes": mm["dtypes"],
                    "num_collectives": mm["num_collectives"],
                }
            configs[f"{row['config']}@mesh{d}x{m}-T{row['t']}"] = entry
    if serve_result is not None:
        # serving rows (benchmarks/serving_load.py): throughput-vs-latency of
        # the continuous-batching scheduler vs the synchronous-slots and
        # single-stream disciplines under one Poisson open-loop trace
        from benchmarks import serving_load

        configs.update(serving_load.bench_configs(serve_result))
    BENCH_JSON.write_text(json.dumps({"configs": configs}, indent=2) + "\n")
    print(f"wrote {BENCH_JSON}")


def main() -> None:
    from benchmarks import (engine_fused_vs_naive, int8_decode, kernel_bench,
                            linear_attention_scaling, lm_plan, packed_traffic,
                            perf_spiking, serving_load, sharded_traffic,
                            sparsity, table1_iand_vs_add,
                            table2_weight_traffic)

    print("name,us_per_call,derived")
    engine_result = _run("engine_fused_vs_naive", engine_fused_vs_naive.main)
    print()
    packed_result = _run("packed_traffic", packed_traffic.main)
    print()
    lm_result = _run("lm_plan", lm_plan.main)
    print()
    sparsity_result = _run("sparsity", sparsity.main)
    print()
    sharded_result = _run("sharded_traffic", sharded_traffic.main)
    print()
    serve_result = _run("serving_load", serving_load.main)
    write_bench_json(engine_result, packed_result, lm_result, sparsity_result,
                     sharded_result, serve_result)
    print()
    _run("table2_weight_traffic", table2_weight_traffic.main)
    print()
    _run("kernel_bench", kernel_bench.main)
    print()
    _run("linear_attention_scaling", linear_attention_scaling.main)
    print()
    _run("perf_spiking_schedule_ladder", perf_spiking.main)
    print()
    _run("int8_decode", int8_decode.main)
    print()
    _run("table1_iand_vs_add", table1_iand_vs_add.main)


if __name__ == "__main__":
    main()
