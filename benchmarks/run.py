"""Benchmark harness entrypoint — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines.  Default preset is sized
for this CPU container (~minutes); ``--full`` widens datasets/batches.

Sections:
  table1  — storage + lookup latency, exceeds-memory pool   (paper Tab. I)
  table2  — storage + lookup latency, fits-in-memory pool   (paper Tab. II)
  table3  — insertions, same distribution                   (paper Tab. III)
  table4  — insertions, shifted distribution                (paper Tab. IV)
  table5  — deletions                                       (paper Tab. V)
  fig6    — storage breakdown                               (paper Fig. 6)
  fig7    — latency breakdown                               (paper Fig. 7)
  fig9    — MHAS search progression                         (paper Fig. 9/10)
  shards  — sharded cluster scaling: build / lookup QPS / dirty-shard retrain
  query   — plan executor vs legacy lookup (point/range/scan, projection
            pushdown, sharded sync vs async fan-out)
  query_stream — streaming operator pipeline: multi-plan pipelined vs
            serial, value-predicate pushdown vs post-hoc filter, plus
            the adaptive-execution section (warm-vs-cold plan cache,
            baseline partition pruning, adaptive vs fixed morsel
            sizing), the code-space aggregate section (count-only
            GROUP BY with rows_decoded == 0 and code-table sum/min/max
            vs the decode-then-aggregate reference) and the mesh
            shard-scatter vs thread-pool fan-out comparison; writes
            BENCH_query.json at the repo root (uploaded by the CI
            smoke-bench job alongside BENCH_lookup.json)
  lookup_pipeline — staged (seed path) vs pipelined (inference engine)
            hot-path comparison; writes BENCH_lookup.json at the repo
            root (p50/p99 latency, QPS, compile counts) — the CI
            smoke-bench job uploads it as the perf-trajectory artifact
"""

from __future__ import annotations

import argparse
import os
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized lookup_pipeline run (fewer rows/batches)")
    ap.add_argument("--sections", nargs="*", default=None)
    ap.add_argument(
        "--telemetry-dir", default=None, metavar="DIR",
        help="after the sections run, export the process telemetry "
             "there: metrics.prom (Prometheus text), metrics.json "
             "(registry snapshot), trace.json (Chrome trace — open at "
             "https://ui.perfetto.dev)",
    )
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

    from benchmarks import bench_beyond, bench_breakdown, bench_lookup
    from benchmarks import bench_mhas, bench_modify, bench_query, bench_shards
    from benchmarks import common as C

    datasets = list(C.DATASETS) if args.full else list(C.FAST_DATASETS)
    batches = (1000, 10_000, 100_000) if args.full else (1000, 10_000)

    sections = {
        "table1": lambda: bench_lookup.run(datasets=datasets, batches=batches,
                                           pool_mode="small"),
        "table2": lambda: bench_lookup.run(datasets=datasets, batches=batches,
                                           pool_mode="large"),
        "table3": lambda: bench_modify.run_inserts(shift=False),
        "table4": lambda: bench_modify.run_inserts(shift=True),
        "table5": lambda: bench_modify.run_deletes(),
        "fig6": lambda: bench_breakdown.run_storage(datasets=datasets),
        "fig7": lambda: bench_breakdown.run_latency(datasets=datasets),
        "fig9": lambda: bench_mhas.run(iters=None if args.full else 60),
        "shards": lambda: bench_shards.run(
            shard_counts=(1, 2, 4, 8) if args.full else (1, 4)
        ),
        "query": lambda: bench_query.run(
            datasets=("tpcds_customer_demographics",),
            batches=batches,
            num_shards=8 if args.full else 4,
        ),
        # scaled down by default like every section; the acceptance-
        # grade 1M-row record needs --full (CI smoke uses --smoke)
        "lookup_pipeline": lambda: bench_lookup.write_pipeline_json(
            bench_lookup.run_pipeline(
                n=1_000_000 if args.full else 150_000,
                fixed_repeats=4 if (args.smoke or not args.full) else 8,
                sweep_sizes=50,
            )
        ),
        "query_stream": lambda: bench_query.write_query_json(
            dict(
                bench_query.run_streaming(smoke=args.smoke),
                adaptive=bench_query.run_adaptive(smoke=args.smoke),
                aggregate=bench_query.run_aggregate(
                    n=1_000_000 if args.full else 150_000, smoke=args.smoke
                ),
                degraded=bench_shards.run_degraded(smoke=args.smoke),
                mesh=bench_shards.run_mesh(smoke=args.smoke),
            )
        ),
        "beyond": lambda: bench_beyond.run(),
    }
    wanted = args.sections or list(sections)
    failures = 0
    for name in wanted:
        print(f"# === {name} ===", flush=True)
        try:
            sections[name]()
        except Exception:  # noqa: BLE001 — report all sections
            failures += 1
            print(f"# SECTION {name} FAILED", flush=True)
            traceback.print_exc()
    if args.telemetry_dir:
        from repro import obs

        os.makedirs(args.telemetry_dir, exist_ok=True)
        for fname, writer in (
            ("metrics.prom", obs.write_prometheus),
            ("metrics.json", obs.write_json_snapshot),
            ("trace.json", obs.write_chrome_trace),
        ):
            print(f"# telemetry: {writer(os.path.join(args.telemetry_dir, fname))}",
                  flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
