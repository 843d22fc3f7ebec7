"""Benchmark driver — one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV.

Runs either way:
    python benchmarks/run.py [section-prefix]
    python -m benchmarks.run [section-prefix]
    python -m benchmarks.run --list      # print section tags, run nothing

Machine-readable perf trajectories are written next to the CSV output
(cwd) whenever their sections run, so successive PRs can regress against
them: ``BENCH_groupby.json`` (``groupby/*``), ``BENCH_joins.json``
(``fig*``/``table*`` join sections), ``BENCH_groupjoin.json``
(``groupjoin/*`` fused-path sections) — each ``{name: us_per_call}``.
The serving trajectory ``BENCH_serve.json`` (warm p50/p99 + throughput +
degradation counters) is written by ``python -m repro.serve --chaos``,
not by this driver — see DESIGN.md §14.

Scale with REPRO_BENCH_SCALE (default 1.0 ~ 262k-row unit; the paper's GPU
runs use 2^27 rows — same code, larger constant)."""
import os
import sys
import time

# 8-byte key/payload experiments (paper §5.2.5) need x64 before jax init.
os.environ.setdefault("JAX_ENABLE_X64", "1")

# Script mode (`python benchmarks/run.py`) has no parent package, so the
# relative imports below must be absolute and the repo root importable.
# Both modes get src/ on the path so `repro` resolves without a PYTHONPATH
# export.
_repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_paths = [os.path.join(_repo, "src")]
if __package__ in (None, ""):
    _paths.insert(0, _repo)
for _p in _paths:
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main() -> None:
    t0 = time.time()
    from repro import compile_cache

    compile_cache.enable()
    from benchmarks import (joins, groupby_bench, groupjoin_bench,
                            integration_bench, engine_bench)
    from benchmarks.common import ROWS

    sections = [
        ("fig1", joins.fig1_time_breakdown),
        ("table4+fig7", joins.table4_fig7_gather),
        ("fig8/9", joins.fig8_9_narrow),
        ("fig10", joins.fig10_wide),
        ("fig11", joins.fig11_size_ratio),
        ("fig12", joins.fig12_payload_cols),
        ("fig13", joins.fig13_match_ratio),
        ("fig14", joins.fig14_skew),
        ("fig15", joins.fig15_dtypes),
        ("table5", joins.table5_memory),
        ("fig16", joins.fig16_join_sequences),
        ("fig17", joins.fig17_tpc),
        ("fig18", joins.fig18_planner),
        ("groupjoin/fused", groupjoin_bench.fused_vs_unfused),
        ("groupjoin/engine", groupjoin_bench.engine_fusion),
        ("groupby/cardinality", groupby_bench.cardinality_sweep),
        ("groupby/skew", groupby_bench.skew_sweep),
        ("groupby/wide", groupby_bench.wide_payload),
        ("groupby/partition", groupby_bench.partition_sweep),
        ("moe_dispatch", integration_bench.moe_dispatch),
        ("feature_pipeline", integration_bench.feature_join_pipeline),
        ("kernels", integration_bench.kernel_vs_xla),
        ("engine/star", engine_bench.tpc_star_query),
        ("engine/topk", engine_bench.filtered_topk_query),
        ("engine/calibrate", engine_bench.calibration),
    ]
    args = sys.argv[1:]
    if "--list" in args:
        for tag, _ in sections:
            print(tag)
        return
    only = args[0] if args else None
    print("name,us_per_call,derived")
    for tag, fn in sections:
        if only and not tag.startswith(only):
            continue
        print(f"# --- {tag} ---")
        fn()
    print(f"# total_wall_s,{time.time()-t0:.1f},{len(ROWS)} rows")

    # machine-readable perf trajectories, one file per operator family
    # ({name: us_per_call}); a file is written whenever any of its rows ran
    files = {
        "BENCH_groupby.json": lambda n: n.startswith("groupby"),
        "BENCH_joins.json": lambda n: n.startswith(("fig", "table")),
        "BENCH_groupjoin.json": lambda n: n.startswith("groupjoin"),
    }
    from benchmarks.common import FINGERPRINTS

    for fname, pred in files.items():
        rows = {name: us for name, us, _ in ROWS if pred(name)}
        # ride the structural fingerprints (primitive budget + peak live
        # bytes per plan) along with the timings they describe
        rows.update({k: v for k, v in FINGERPRINTS.items() if pred(k)})
        if rows:
            import json

            with open(fname, "w") as f:
                json.dump(rows, f, indent=2, sort_keys=True)
            print(f"# wrote {fname},{len(rows)},rows")


if __name__ == "__main__":
    main()
