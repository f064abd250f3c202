"""Benchmark aggregator: one section per paper table/figure + engine benches.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--bench-json DIR]

Besides the stdout tables and per-bench CSVs (results/bench/), every run
distills each area into a committed, schema-stable perf-trajectory
artifact ``BENCH_<area>.json`` (see benchmarks/artifacts.py): key metrics
with machine-normalized values, plus the raw rows. ``--bench-json ''``
skips the artifacts.
"""

from __future__ import annotations

import argparse
import statistics
import time


def _emit(title, header, rows):
    print(f"\n== {title} ==")
    print(",".join(header))
    for r in rows:
        print(",".join(str(x) for x in r))
    return header, rows


def _col(rows, header, name):
    """One column of a rows/header table as floats (non-numeric skipped)."""
    i = header.index(name)
    out = []
    for r in rows:
        try:
            out.append(float(r[i]))
        except (TypeError, ValueError):
            pass
    return out


def _mean(rows, header, name):
    vals = _col(rows, header, name)
    return statistics.fmean(vals) if vals else 0.0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="small workloads only (CI)")
    ap.add_argument("--bench-json", metavar="DIR", default=".",
                    help="directory for BENCH_<area>.json perf-trajectory "
                         "artifacts (default: repo root; '' disables)")
    args = ap.parse_args(argv)

    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    from . import bench_construction as bc
    from . import bench_paper as bp
    from . import bench_engine as be
    from . import bench_retention as br
    from . import bench_store as bst
    from . import bench_streaming as bs

    workloads = ["fb_like", "cm_like"] if args.fast else bp.WORKLOADS

    t0 = time.time()
    cons_h, cons_r = _emit(
        "Construction plane: PR-1 vs batched (cold, same run)",
        ["workload", "k", "pr1_core_s", "pr1_forest_s", "pr1_total_s",
         "batched_core_s", "batched_forest_s", "batched_total_s", "speedup"],
        bc.bench_construction_plane(workloads))
    strat_h, strat_r = _emit(
        "Stratified construction: one |K|-build vs per-k builds "
        "(equality asserted per stratum before reporting)",
        ["workload", "n_ks", "ks", "perk_build_s", "strat_build_s",
         "build_speedup", "perk_mb", "strat_mb", "bytes_ratio"],
        # the fast job smoke-runs the small workload without the em_like
        # 3x / 2x floors (CI machines are noisy); the full run asserts both
        bc.bench_stratified_construction(
            "fb_like" if args.fast else "em_like",
            assert_floors=not args.fast))
    _emit("Index space (Fig 4)",
          ["workload", "k", "pecb_bytes", "ctmsf_bytes", "ef_bytes", "ef/pecb"],
          bp.bench_index_size(workloads))
    fig5_h, fig5_r = _emit(
        "Construction time (Fig 5)",
        ["workload", "k", "pecb_s", "ctmsf_s", "ef_s", "ef/pecb"],
        bp.bench_construction(workloads))
    fig6_h, fig6_r = _emit(
        "Query time, 1000 random queries (Fig 6)",
        ["workload", "k", "pecb_us", "ctmsf_us", "ef_us"],
        bp.bench_query(workloads))
    _emit("Impact of k (Figs 7-9)",
          ["workload", "frac", "k", "pecb_bytes", "ef_bytes", "pecb_s", "ef_s",
           "pecb_us", "ef_us"],
          bp.bench_vary_k("cm_like"))
    _emit("Fine-grained timestamps (Figs 10-12)",
          ["workload", "t_max", "pecb_s", "ef_s", "pecb_bytes", "ef_bytes",
           "pecb_us", "ef_us"],
          bp.bench_fine_grained("fb_like", factor=4 if args.fast else 8))
    bq_h, bq_r = _emit(
        "Batched TCCS engine (beyond paper; CPU-interpret caveat in module doc)",
        ["workload", "batch", "batched_us_per_q", "alg1_us_per_q", "speedup"],
        be.bench_batch_query("fb_like",
                             batches=(32, 128) if args.fast else (32, 128, 512)))
    load_h, load_r = _emit(
        "Serving engine offered-load sweep + window-sweep scenario (beyond paper)",
        ["workload", "k", "offered_qps", "queries", "achieved_qps",
         "p50_ms", "p95_ms", "p99_ms", "device_batches", "host_batches"],
        be.bench_engine_load_sweep(
            "fb_like",
            loads=(2000, 0) if args.fast else (1000, 4000, 16000, 0),
            n_q=512 if args.fast else 2048))
    trace_h, trace_r = _emit(
        "Serving-plane tracing overhead (DESIGN.md §11 acceptance)",
        ["workload", "k", "arm", "queries", "qps", "p99_ms",
         "chain_coverage", "spans", "dropped"],
        # the fast job smoke-runs the A/B without the 5% p99 gate (CI
        # machines are noisy); chain coverage >= 95% is asserted always
        be.bench_trace_overhead("fb_like", n_q=256 if args.fast else 512,
                                reps=1 if args.fast else 2,
                                assert_overhead=not args.fast))
    strm_h, strm_r = _emit(
        "Streaming refresh vs cold rebuild (beyond paper; equality "
        "asserted before reporting)",
        ["workload", "k", "suffix_edges", "refresh_tab_s",
         "refresh_index_s", "refresh_device_s", "refresh_total_s",
         "cold_total_s", "speedup", "device_uploaded_bytes",
         "device_reused_bytes"],
        # the fast job smoke-runs the small workload without the em_like
        # 5x floor (CI machines are noisy); the full run asserts it
        bs.bench_refresh(("fb_like",) if args.fast else ("em_like",),
                         assert_speedup=not args.fast))
    shr_h, shr_r = _emit(
        "Retention: shrink vs cold rebuild (beyond paper; equality "
        "asserted before reporting)",
        ["workload", "k", "t_cut", "expired_edges", "shrink_tab_s",
         "shrink_index_s", "shrink_device_s", "shrink_total_s",
         "cold_total_s", "speedup", "device_freed_bytes"],
        # fast job smoke-runs the small workload without the em_like 3x
        # floor (CI machines are noisy); the full run asserts it
        br.bench_shrink(("fb_like",) if args.fast else ("em_like",),
                        assert_speedup=not args.fast))
    roll_h, roll_r = _emit(
        "Retention: rolling-window steady state (beyond paper; bounded "
        "nbytes asserted across append+expire cycles)",
        ["workload", "k", "window", "cycle", "t_max", "index_bytes",
         "tab_bytes", "cache_entries", "trim_s"],
        br.bench_rolling("fb_like" if args.fast else "em_like"))
    avail_h, avail_r = _emit(
        "Query availability during streaming refresh (beyond paper)",
        ["workload", "k", "suffix_edges", "queries_during_refresh",
         "refresh_s", "mean_ms", "worst_ms"],
        bs.bench_availability("fb_like" if args.fast else "em_like"))
    warm_h, warm_r = _emit(
        "Persistent store: warm restart vs cold build (beyond paper; "
        "equality asserted before reporting)",
        ["workload", "n_ks", "stored_bytes", "cold_total_s", "warm_open_s",
         "warm_device_s", "warm_total_s", "speedup"],
        # fast job smoke-runs the small workload without the em_like
        # sub-second / 10x floors (CI machines are noisy); the full run
        # asserts both
        bst.bench_warm_restart(("fb_like",) if args.fast else ("em_like",),
                               assert_speedup=not args.fast))
    dlt_h, dlt_r = _emit(
        "Persistent store: delta vs full commit of a suffix epoch",
        ["workload", "n_ks", "suffix_edges", "full_bytes", "full_s",
         "delta_bytes", "delta_s", "delta_bytes_ratio"],
        bst.bench_delta(("fb_like",) if args.fast else ("em_like",)))
    _emit("Pallas kernel micro (interpret mode vs jnp ref)",
          ["kernel", "pallas_interpret_ms", "jnp_ref_ms"],
          be.bench_kernels())

    if args.bench_json:
        write_artifacts(args.bench_json, args.fast, {
            "construction": (cons_h, cons_r, fig5_h, fig5_r,
                             strat_h, strat_r),
            "engine": (bq_h, bq_r, load_h, load_r, trace_h, trace_r,
                       fig6_h, fig6_r),
            "streaming": (strm_h, strm_r, avail_h, avail_r),
            "retention": (shr_h, shr_r, roll_h, roll_r),
            "sweep": (load_h, load_r),
            "store": (warm_h, warm_r, dlt_h, dlt_r),
        })
    print(f"\n[benchmarks done in {time.time()-t0:.1f}s; CSVs in results/bench/]")


def write_artifacts(out_dir: str, fast: bool, raw: dict) -> None:
    """Distill the collected rows into one BENCH_<area>.json per area,
    validate each on the way out, and print the paths."""
    from .artifacts import machine_info, validate_bench_files, write_bench_json

    machine = machine_info()
    paths = []

    cons_h, cons_r, fig5_h, fig5_r, strat_h, strat_r = raw["construction"]
    paths.append(write_bench_json(out_dir, "construction", {
        "batched_total_s": (_mean(cons_r, cons_h, "batched_total_s"), "s"),
        "speedup_vs_pr1": (_mean(cons_r, cons_h, "speedup"), "x"),
        "pecb_build_s": (_mean(fig5_r, fig5_h, "pecb_s"), "s"),
        "ef_build_s": (_mean(fig5_r, fig5_h, "ef_s"), "s"),
        "stratified_build_s": (_mean(strat_r, strat_h, "strat_build_s"),
                               "s"),
        "stratified_build_speedup": (
            _mean(strat_r, strat_h, "build_speedup"), "x"),
        "stratified_bytes_ratio": (
            _mean(strat_r, strat_h, "bytes_ratio"), "x"),
    }, {"construction_plane": (cons_h, cons_r),
        "construction_fig5": (fig5_h, fig5_r),
        "construction_stratified": (strat_h, strat_r)}, machine, fast))

    bq_h, bq_r, load_h, load_r, trace_h, trace_r, fig6_h, fig6_r = raw["engine"]
    # the window-sweep scenario rows share the load-sweep table, labeled
    # perwin_w{W} / sweep_w{W} in offered_qps; split them out
    oq = load_h.index("offered_qps")
    sweep_rows = [r for r in load_r if str(r[oq]).startswith(("perwin_",
                                                             "sweep_"))]
    pure_load = [r for r in load_r if r not in sweep_rows]
    open_rows = [r for r in pure_load if r[oq] == "open"]
    open_row = open_rows[0] if open_rows else pure_load[-1]
    mixed_rows = [r for r in pure_load if r[oq] == "mixed_k"]
    mixed_row = mixed_rows[0] if mixed_rows else open_row
    traced = [r for r in trace_r if r[trace_h.index("arm")] == "traced"]
    untraced = [r for r in trace_r if r[trace_h.index("arm")] == "untraced"]
    p99_i, qps_i = trace_h.index("p99_ms"), trace_h.index("qps")
    ratio = (float(traced[0][p99_i]) / float(untraced[0][p99_i])
             if untraced and float(untraced[0][p99_i]) > 0 else 1.0)
    paths.append(write_bench_json(out_dir, "engine", {
        "open_loop_qps": (float(open_row[load_h.index("achieved_qps")]), "qps"),
        "open_loop_p99_ms": (float(open_row[load_h.index("p99_ms")]), "ms"),
        "mixed_k_qps": (float(mixed_row[load_h.index("achieved_qps")]),
                        "qps"),
        "mixed_k_p99_ms": (float(mixed_row[load_h.index("p99_ms")]), "ms"),
        "batch_query_us_per_q": (min(_col(bq_r, bq_h, "batched_us_per_q")),
                                 "us"),
        "alg1_us_per_q": (_mean(fig6_r, fig6_h, "pecb_us"), "us"),
        "traced_qps": (float(traced[0][qps_i]), "qps"),
        "trace_overhead_p99_ratio": (ratio, "x"),
        "span_chain_coverage": (
            float(traced[0][trace_h.index("chain_coverage")]), "frac"),
    }, {"load_sweep": (load_h, pure_load), "batch_query": (bq_h, bq_r),
        "trace_overhead": (trace_h, trace_r)}, machine, fast))

    strm_h, strm_r, avail_h, avail_r = raw["streaming"]
    paths.append(write_bench_json(out_dir, "streaming", {
        "refresh_total_s": (_mean(strm_r, strm_h, "refresh_total_s"), "s"),
        "cold_total_s": (_mean(strm_r, strm_h, "cold_total_s"), "s"),
        "refresh_speedup": (_mean(strm_r, strm_h, "speedup"), "x"),
        "query_mean_ms_during_refresh": (_mean(avail_r, avail_h, "mean_ms"),
                                         "ms"),
        "query_worst_ms_during_refresh": (_mean(avail_r, avail_h, "worst_ms"),
                                          "ms"),
    }, {"refresh": (strm_h, strm_r), "availability": (avail_h, avail_r)},
        machine, fast))

    shr_h, shr_r, roll_h, roll_r = raw["retention"]
    paths.append(write_bench_json(out_dir, "retention", {
        "shrink_total_s": (_mean(shr_r, shr_h, "shrink_total_s"), "s"),
        "cold_total_s": (_mean(shr_r, shr_h, "cold_total_s"), "s"),
        "shrink_speedup": (_mean(shr_r, shr_h, "speedup"), "x"),
        "rolling_trim_s": (_mean(roll_r, roll_h, "trim_s"), "s"),
        "rolling_index_bytes_max": (max(_col(roll_r, roll_h, "index_bytes")),
                                    "bytes"),
    }, {"shrink": (shr_h, shr_r), "rolling": (roll_h, roll_r)},
        machine, fast))

    warm_h, warm_r, dlt_h, dlt_r = raw["store"]
    paths.append(write_bench_json(out_dir, "store", {
        "warm_restart_s": (_mean(warm_r, warm_h, "warm_total_s"), "s"),
        "cold_build_s": (_mean(warm_r, warm_h, "cold_total_s"), "s"),
        "warm_speedup": (_mean(warm_r, warm_h, "speedup"), "x"),
        "stored_bytes": (_mean(warm_r, warm_h, "stored_bytes"), "bytes"),
        "delta_commit_bytes_ratio": (_mean(dlt_r, dlt_h, "delta_bytes_ratio"),
                                     "frac"),
        "delta_commit_s": (_mean(dlt_r, dlt_h, "delta_s"), "s"),
    }, {"warm_restart": (warm_h, warm_r), "delta_commit": (dlt_h, dlt_r)},
        machine, fast))

    sw_h, sw_r = raw["sweep"]
    qps_i = sw_h.index("achieved_qps")
    per_win = [r for r in sw_r if str(r[oq]).startswith("perwin_")]
    one_call = [r for r in sw_r if str(r[oq]).startswith("sweep_")]
    perwin_qps = float(per_win[0][qps_i]) if per_win else 0.0
    sweep_qps = float(one_call[0][qps_i]) if one_call else 0.0
    paths.append(write_bench_json(out_dir, "sweep", {
        "sweep_windows_per_s": (sweep_qps, "qps"),
        "perwin_windows_per_s": (perwin_qps, "qps"),
        "sweep_speedup": (sweep_qps / perwin_qps if perwin_qps else 0.0, "x"),
    }, {"window_sweep": (sw_h, per_win + one_call)}, machine, fast))

    validate_bench_files(out_dir)   # what we wrote must re-load clean
    print("\n[bench artifacts]")
    for p in paths:
        print(f"  {p}")


if __name__ == "__main__":
    main()
