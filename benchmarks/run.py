"""Benchmark harness: one entry per paper table/figure + the roofline.

Prints ``name,value,derived`` CSV rows after each bench's own report.
A bench that raises fails the run.  These are CPU benches of the smoke
preset (no device numbers)::

    JAX_PLATFORMS=cpu PYTHONPATH=src:. python benchmarks/run.py
"""

from __future__ import annotations

from repro.launch.mesh import simulate_host_devices

# the serve bench's tensor-parallel sweep needs a 4-device mesh; on the
# CPU that is a simulated split, and XLA freezes the host device count at
# the first computation — so the split must happen before ANY bench
# touches a device
simulate_host_devices(4)


def main() -> None:
    from benchmarks import (
        admission_bench,
        loader_bench,
        orchestrator_bench,
        pool_bench,
        prefix_bench,
        query_latency,
        roofline,
        scheduler_bench,
        sentry_overhead,
        serve_bench,
        vma_bench,
    )

    rows = []

    print("=" * 72)
    vma = vma_bench.main()
    rows += [
        ("vma_blowup_legacy_vs_native_x", vma["blowup_x"], "paper:>500x"),
        ("vma_reduction_fix_x", vma["reduction_clean_x"], "paper:182x"),
        ("vma_legacy_crash", vma["legacy_crash"], "paper:crash@65530"),
    ]

    print("=" * 72)
    q = query_latency.main()
    rows.append(
        ("query_suite_improvement_pct", q["overall_improvement_pct"],
         "paper:+1.5pct")
    )

    print("=" * 72)
    ld = loader_bench.main()
    rows += [
        ("loader_legacy_success_pct", ld["legacy_success_pct"],
         "paper:prophet-segfault"),
        ("loader_linux_success_pct", ld["linux_success_pct"], "paper:100"),
    ]

    print("=" * 72)
    so = sentry_overhead.main()
    rows += [
        ("sentry_steady_state_overhead_pct",
         so["steady_state_overhead_pct"], "target:~0"),
        ("sentry_emulation_slowdown_x", so["emulation_slowdown_x"],
         "ptrace-mode analogue"),
    ]

    print("=" * 72)
    ab = admission_bench.main()
    rows += [
        ("admission_warm_speedup_x", ab["warm_speedup_x"], "target:>=10x"),
        ("pool_checkout_speedup_x", ab["pool_checkout_speedup_x"],
         "warm-sandbox startup hiding"),
    ]

    print("=" * 72)
    pb = pool_bench.main()
    rows += [
        ("pool_refill_warm_speedup_x", pb["warm_speedup_x"], "target:>=5x"),
        ("pool_refill_cold_checkouts", pb["warm_cold_checkout_total"],
         "steady-state target:0"),
    ]

    print("=" * 72)
    sb = scheduler_bench.main()
    rows += [
        ("scheduler_concurrent_speedup_x", sb["speedup_x"], "target:>=2x"),
        ("scheduler_steal_speedup_x", sb["steal_speedup_x"],
         "skewed tenant, target:>=2x"),
        ("scheduler_sim_deterministic", float(sb["sim_deterministic"]),
         "3 same-seed runs byte-identical"),
    ]

    print("=" * 72)
    sv = serve_bench.main()
    rows += [
        ("serve_incremental_speedup_x", sv["incremental_speedup_x"],
         "skewed admit/retire, target:>=2x"),
        ("serve_prefill_reduction_x", sv["prefill_reduction_x"],
         "prefill tokens avoided vs rebatching"),
        ("serve_incremental_tokens_per_s", sv["incremental_tokens_per_s"],
         "reduced-model CPU decode"),
        ("serve_paged_speedup_x", sv["paged_speedup_x"],
         "paged vs dense KV at the largest (slots, max_seq) cell"),
        ("serve_chunk_stall_reduction_x", sv["chunk_stall_reduction_x"],
         "p99 inter-token stall, chunked vs monolithic long-prompt "
         "admit, target:>=3x"),
        ("serve_shard_speedup_x", sv["shard_speedup_x"],
         "mesh-4 vs mesh-1 TP decode; simulated shards share one core"),
    ]

    print("=" * 72)
    ob = orchestrator_bench.main()
    rows += [
        ("orchestrator_decode_p50_protection_x",
         ob["decode_p50_protection_x"],
         "class-aware vs naive FIFO mixing, target:>1x"),
        ("orchestrator_batch_makespan_cost_x",
         ob["batch_makespan_cost_x"], "batch's bounded price, target:<5x"),
    ]

    print("=" * 72)
    pfx = prefix_bench.main()
    rows.append(
        ("serve_prefix_tokens_saved_x",
         pfx["prefix_prefill_tokens_saved_x"],
         "shared vs unshared prefill at 75% prompt overlap, target:>=2x")
    )

    print("=" * 72)
    if roofline.load_cells():
        rf = roofline.main()
        hist = rf["dominant_histogram"]
        for term, count in sorted(hist.items()):
            rows.append((f"roofline_cells_dominated_by_{term}", count,
                         f"of {rf['cells_single']}"))
    else:
        print(f"  roofline skipped: no dry-run artifacts in "
              f"{roofline.DRYRUN_DIR}")

    print("=" * 72)
    print("name,value,derived")
    for name, value, derived in rows:
        print(f"{name},{value:.4g},{derived}")


if __name__ == "__main__":
    main()
