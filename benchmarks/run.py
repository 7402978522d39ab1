import os
os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
# ^ the distributed benchmarks need 8 host devices; must precede jax init.

"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Usage:
    PYTHONPATH=src python -m benchmarks.run [--only fig4,fig7]

Artifacts land in the working directory: ``BENCH_<key>.json`` (perf
records) and, from the obs-instrumented benches (dist, serving), the
``TRACE_<key>.json`` / ``METRICS_<key>.json`` pair described in
docs/observability.md — Perfetto-loadable spans with per-row cost-model
drift, and the metrics-registry snapshot.
"""
import argparse
import sys
import time
import traceback

from repro.launch import compile_cache

MODULES = [
    ("kernels", "benchmarks.bench_kernels"),
    ("dist", "benchmarks.bench_dist"),
    ("table3", "benchmarks.bench_table3_comm"),
    ("fig4", "benchmarks.bench_fig4_weak_scaling"),
    ("fig5", "benchmarks.bench_fig5_breakdown"),
    ("fig6", "benchmarks.bench_fig6_embedding_width"),
    ("fig7", "benchmarks.bench_fig7_replication"),
    ("fig8", "benchmarks.bench_fig8_strong_scaling"),
    ("fig9", "benchmarks.bench_fig9_apps"),
    ("serving", "benchmarks.bench_serving"),
]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of benchmark keys")
    args = ap.parse_args(argv)
    only = set(args.only.split(",")) if args.only else None
    compile_cache.enable()

    print("name,us_per_call,derived")
    failures = []
    for key, modname in MODULES:
        if only and key not in only:
            continue
        t0 = time.time()
        try:
            mod = __import__(modname, fromlist=["run"])
            mod.run(print)
            print(f"# {key} done in {time.time() - t0:.1f}s",
                  file=sys.stderr)
        except Exception:
            failures.append(key)
            traceback.print_exc()
    if failures:
        print(f"# FAILED: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == '__main__':
    main()
