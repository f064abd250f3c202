"""TCCS query-serving driver — thin client of the serving engine
(repro/serving, DESIGN.md §7).

    PYTHONPATH=src python -m repro.launch.serve --workload cm_like --k 3 \\
        --queries 4096 --batch 256 --flush-ms 2

The driver owns nothing but the traffic: it warms the engine (index build +
bucket compiles), replays a random query stream of typed ``TCCSQuery``
specs through ``submit_specs`` (``--mode`` picks the result mode) batched
like independent arrivals, then prints the engine's own per-stage metrics,
compares against the sequential Algorithm 1 baseline, and verifies
exactness on a sample. All batching/routing/caching/sharding policy lives
in the engine.
"""

from __future__ import annotations

import argparse
import time

from repro.core.kcore import k_max
from repro.core.query_api import ResultMode, TCCSQuery
from repro.core.temporal_graph import BENCH_WORKLOADS, bench_graph, random_queries
from repro.runtime.compile_cache import enable_compile_cache
from repro.serving import EngineConfig, ServingEngine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="cm_like",
                    choices=sorted(BENCH_WORKLOADS))
    ap.add_argument("--k", type=int, default=None)
    ap.add_argument("--queries", type=int, default=2048)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--flush-ms", type=float, default=2.0)
    ap.add_argument("--cache", type=int, default=4096)
    ap.add_argument("--mode", default="vertices",
                    choices=[m.value for m in ResultMode])
    ap.add_argument("--verify", type=int, default=32)
    ap.add_argument("--trace-export", metavar="PATH", default=None,
                    help="write the run's query-lifecycle spans as Chrome "
                         "trace-event JSON (Perfetto / chrome://tracing)")
    ap.add_argument("--slow-query-ms", type=float, default=None,
                    help="log queries slower than this threshold with "
                         "their full span tree")
    ap.add_argument("--store-dir", metavar="DIR", default=None,
                    help="persistent index store root (DESIGN.md §13): "
                         "builds write through to it and a restart "
                         "promotes the stored index instead of rebuilding")
    ap.add_argument("--expect-warm", action="store_true",
                    help="fail unless the warmup index was promoted from "
                         "the store (warm-restart smoke assertion)")
    args = ap.parse_args(argv)

    if args.expect_warm and not args.store_dir:
        ap.error("--expect-warm requires --store-dir")

    if args.batch < 1:
        ap.error("--batch must be >= 1")
    enable_compile_cache()
    g = bench_graph(args.workload)
    k = args.k or max(2, int(0.7 * k_max(g)))
    cfg = EngineConfig(max_batch=args.batch, flush_ms=args.flush_ms,
                       cache_capacity=args.cache,
                       min_bucket=min(8, args.batch),
                       slow_query_ms=args.slow_query_ms,
                       store_dir=args.store_dir)
    print(f"[engine] workload={args.workload} n={g.n} m={g.m} "
          f"t_max={g.t_max} k={k} config={cfg}")

    with ServingEngine(cfg) as eng:
        t0 = time.perf_counter()
        # edge modes use the full-mode device program: compile it now, not
        # inside the timed replay (one warmup covers every k — the index
        # is k-stratified and k rides as a device operand)
        handle = eng.warmup(args.workload,
                            full=args.mode in ("edges", "subgraph"))
        print(f"[warmup] index {'promoted from store' if handle.source == 'disk' else 'built'} "
              f"in {handle.build_seconds:.2f}s "
              f"(nodes={handle.pecb.num_nodes} size={handle.nbytes/1e6:.2f} MB); "
              f"buckets compiled in {time.perf_counter() - t0 - handle.build_seconds:.2f}s")
        if args.store_dir:
            st = eng.store.stats()
            print(f"[store] root={st['root']} commits={st['commits']} "
                  f"(full={st['commits_full']} delta={st['commits_delta']} "
                  f"noop={st['commits_noop']}) loads={st['loads']} "
                  f"load_bytes={st['load_bytes']} "
                  f"recovered={st['recovered_commits']}")
        if args.expect_warm and handle.source != "disk":
            raise RuntimeError(
                f"--expect-warm: warmup fell back to a cold build "
                f"(source={handle.source!r}) — the store at "
                f"{args.store_dir!r} held no promotable epoch")

        queries = random_queries(g, args.queries, seed=0)
        specs = [TCCSQuery(u, ts, te, k, ResultMode(args.mode))
                 for (u, ts, te) in queries]
        t0 = time.perf_counter()
        futures = []
        for i in range(0, len(specs), args.batch):
            futures += eng.submit_specs(args.workload, specs[i:i + args.batch])
        eng.flush()
        results = [f.result(timeout=120) for f in futures]
        dt = time.perf_counter() - t0
        total = len(queries)
        print(f"[serve] {total} queries in {dt:.3f}s -> {total/dt:,.0f} q/s "
              f"({dt/total*1e6:.1f} us/query)")
        routes = {}
        for r in results:
            routes[r.provenance.route] = routes.get(r.provenance.route, 0) + 1
        print(f"[serve] result routes: {routes}")
        print(eng.format_stats())

        # sequential Algorithm 1 comparison (per-k stratum view)
        ref = handle.pecb.slice_k(k)
        n_seq = min(args.verify * 8, total)
        t0 = time.perf_counter()
        for (u, ts, te) in queries[:n_seq]:
            ref._component_vertices(u, ts, te)
        t_seq = (time.perf_counter() - t0) / n_seq
        print(f"[serve] sequential Alg 1: {t_seq*1e6:.1f} us/query "
              f"(engine speedup {t_seq/(dt/total):.1f}x)")

        # exactness spot check (COUNT mode carries sizes only)
        def matches(i):
            want = ref._component_vertices(*queries[i])
            if results[i].query.mode is ResultMode.COUNT:
                return results[i].num_vertices == len(want)
            return results[i].vertices == frozenset(want)
        bad = sum(not matches(i) for i in range(min(args.verify, total)))
        print(f"[verify] {min(args.verify, total)} queries checked, {bad} mismatches")
        if bad:
            raise RuntimeError(f"{bad} served results disagree with the "
                               "host-side PECB reference")

        if args.slow_query_ms is not None:
            print(f"[slow-queries] threshold={args.slow_query_ms}ms "
                  f"logged={len(eng.slow_queries)}")
            print(eng.slow_queries.format())
        if args.trace_export:
            doc = eng.export_trace(args.trace_export,
                                   extra={"workload": args.workload, "k": k})
            print(f"[trace] {len(doc['traceEvents'])} events -> "
                  f"{args.trace_export} (dropped="
                  f"{doc['otherData']['dropped_spans']})")
        return total / dt


if __name__ == "__main__":
    main()
