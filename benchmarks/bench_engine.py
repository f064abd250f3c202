"""Beyond-paper engine benchmarks: batched TCCS throughput + kernel micro.

CPU caveat recorded in the CSV: the batched engine's advantage is a TPU
property (dense (B,N) propagation on VPU/MXU vs pointer chasing); on this
container the Pallas kernels run in interpret mode and the dense engine
pays Python dispatch, so absolute numbers here only validate correctness
plumbing + scaling shape, not the TPU speedup claim.
"""

from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp

from .common import default_k, random_queries, timed, workload, write_csv
from repro.core.core_time import edge_core_times
from repro.core.pecb_index import build_pecb_index
from repro.core.batch_query import to_device, batch_query
from repro.core.query_api import TCCSQuery, WindowSweep
from repro.serving import EngineConfig, IndexRegistry, ServingEngine


def bench_batch_query(name: str = "fb_like", batches=(32, 128, 512)):
    g = workload(name)
    k = default_k(name)
    idx = build_pecb_index(g, k, edge_core_times(g, k))
    dix = to_device(idx)
    rows = []
    queries = random_queries(g, max(batches), seed=3)
    u = jnp.asarray([q[0] for q in queries], jnp.int32)
    ts = jnp.asarray([q[1] for q in queries], jnp.int32)
    te = jnp.asarray([q[2] for q in queries], jnp.int32)

    # sequential Algorithm 1 reference
    t0 = time.perf_counter()
    for (uu, a, b) in queries[:256]:
        idx._component_vertices(uu, a, b)
    seq_us = (time.perf_counter() - t0) / 256 * 1e6

    for B in batches:
        fn = jax.jit(batch_query)
        out, _ = fn(dix, u[:B], ts[:B], te[:B])
        out.block_until_ready()          # compile
        t0 = time.perf_counter()
        reps = 5
        for _ in range(reps):
            out, _ = fn(dix, u[:B], ts[:B], te[:B])
        out.block_until_ready()
        us_per_q = (time.perf_counter() - t0) / (reps * B) * 1e6
        rows.append([name, B, round(us_per_q, 2), round(seq_us, 2),
                     round(seq_us / us_per_q, 3)])
    write_csv("batch_query.csv",
              ["workload", "batch", "batched_us_per_q", "alg1_us_per_q",
               "speedup"], rows)
    return rows


def bench_engine_load_sweep(name: str = "fb_like",
                            loads=(1000, 4000, 16000, 0),
                            n_q: int = 2048, seed: int = 9):
    """Offered-load sweep through the full serving engine.

    Replays ``n_q`` random queries at each offered load (queries/s; 0 =
    open loop, submit as fast as the engine accepts) through a fresh
    ServingEngine sharing one warm index registry, and records achieved
    throughput plus end-to-end latency percentiles per load — the
    throughput/latency curve a capacity planner reads. The result cache is
    disabled so every query pays its true execution path.

    CSV: engine_load_sweep.csv
    """
    g = workload(name)
    k = default_k(name)
    registry = IndexRegistry(capacity=4)
    registry.register_graph(name, g)
    queries = random_queries(g, n_q, seed=seed)
    rows = bench_window_sweep(name, registry=registry)
    for load in loads:
        cfg = EngineConfig(max_batch=256, flush_ms=2.0, cache_capacity=0)
        with ServingEngine(cfg, registry=registry) as eng:
            eng.warmup(name)
            t0 = time.perf_counter()
            futures = []
            if load:
                period = 1.0 / load
                for i, q in enumerate(queries):
                    target = t0 + i * period
                    delay = target - time.perf_counter()
                    if delay > 0:
                        time.sleep(delay)
                    futures.append(eng.submit_spec(
                        name, TCCSQuery(*q, k)))
            else:
                for i in range(0, len(queries), cfg.max_batch):
                    futures += eng.submit_specs(
                        name, [TCCSQuery(u, ts, te, k) for (u, ts, te)
                               in queries[i:i + cfg.max_batch]])
            eng.flush()
            for f in futures:
                f.result(timeout=300)
            dt = time.perf_counter() - t0
            snap = eng.stats()
            e2e = snap["engine"]["latency"]["e2e"]
            counters = snap["engine"]["counters"]
            rows.append([
                name, k, load if load else "open", n_q,
                round(n_q / dt, 1),
                round(e2e["p50_ms"], 3), round(e2e["p95_ms"], 3),
                round(e2e["p99_ms"], 3),
                counters.get("device_batches", 0),
                counters.get("host_batches", 0),
            ])
    # mixed-k offered load (PR-9 tentpole): the same open-loop replay
    # with k drawn per query from the handle's supported strata — one
    # stratified handle, one device program per bucket shape, zero
    # per-k registry entries
    h = registry.get(name)
    krng = np.random.default_rng(seed + 1)
    kq = [int(krng.choice(h.supported_ks)) for _ in queries]
    cfg = EngineConfig(max_batch=256, flush_ms=2.0, cache_capacity=0)
    with ServingEngine(cfg, registry=registry) as eng:
        eng.warmup(name)
        t0 = time.perf_counter()
        futures = []
        for i in range(0, len(queries), cfg.max_batch):
            futures += eng.submit_specs(
                name, [TCCSQuery(u, ts, te, kk)
                       for (u, ts, te), kk in
                       zip(queries[i:i + cfg.max_batch],
                           kq[i:i + cfg.max_batch])])
        eng.flush()
        for f in futures:
            f.result(timeout=300)
        dt = time.perf_counter() - t0
        snap = eng.stats()
        e2e = snap["engine"]["latency"]["e2e"]
        counters = snap["engine"]["counters"]
        rows.append([
            name, "mix", "mixed_k", n_q,
            round(n_q / dt, 1),
            round(e2e["p50_ms"], 3), round(e2e["p95_ms"], 3),
            round(e2e["p99_ms"], 3),
            counters.get("device_batches", 0),
            counters.get("host_batches", 0),
        ])
    write_csv("engine_load_sweep.csv",
              ["workload", "k", "offered_qps", "queries", "achieved_qps",
               "p50_ms", "p95_ms", "p99_ms", "device_batches", "host_batches"],
              rows)
    return rows


def bench_window_sweep(name: str = "fb_like", W: int = 64, seed: int = 11,
                       registry: IndexRegistry | None = None):
    """Window-sweep scenario (query API v2): one vertex, ``W`` sliding
    windows — the contact-tracing trajectory query.

    Compares the pre-v2 client pattern (``W`` independent single-query
    round trips, each paying batcher deadline + its own route) against ONE
    ``WindowSweep`` engine call (a single ``window_sweep`` device launch
    for all cache-missing windows). Results are asserted identical; rows
    land in the offered-load CSV with offered_qps labels ``perwin_w{W}`` /
    ``sweep_w{W}``.
    """
    g = workload(name)
    k = default_k(name)
    if registry is None:
        registry = IndexRegistry(capacity=4)
        registry.register_graph(name, g)
    rng = np.random.default_rng(seed)
    u = int(rng.integers(0, g.n))
    span = max(2, g.t_max // 10)
    starts = np.linspace(1, max(1, g.t_max - span), W).astype(int)
    windows = [(int(s), min(int(s) + span, g.t_max)) for s in starts]
    rows = []

    # -- W independent submits (the pre-v2 client loop) -------------------
    cfg = EngineConfig(max_batch=256, flush_ms=2.0, cache_capacity=0)
    with ServingEngine(cfg, registry=registry) as eng:
        eng.warmup(name)
        t0 = time.perf_counter()
        per_win = [eng.submit_spec(name, TCCSQuery(u, ts, te, k))
                      .result(timeout=300).vertices
                   for (ts, te) in windows]
        dt_perwin = time.perf_counter() - t0
        snap = eng.stats()
        e2e = snap["engine"]["latency"]["e2e"]
        counters = snap["engine"]["counters"]
        rows.append([name, k, f"perwin_w{W}", W, round(W / dt_perwin, 1),
                     round(e2e["p50_ms"], 3), round(e2e["p95_ms"], 3),
                     round(e2e["p99_ms"], 3),
                     counters.get("device_batches", 0),
                     counters.get("host_batches", 0)])

    # -- one WindowSweep call --------------------------------------------
    with ServingEngine(cfg, registry=registry) as eng:
        # compile outside the measurement (the swept k's stratum only)
        eng.warmup(name, sweep=True, sweep_ks=(k,))
        t0 = time.perf_counter()
        swept = eng.sweep(name, WindowSweep(u, k, windows), timeout=300)
        dt_sweep = time.perf_counter() - t0
        snap = eng.stats()
        e2e = snap["engine"]["latency"]["sweep_exec"]
        counters = snap["engine"]["counters"]
        rows.append([name, k, f"sweep_w{W}", W, round(W / dt_sweep, 1),
                     round(e2e["p50_ms"], 3), round(e2e["p95_ms"], 3),
                     round(e2e["p99_ms"], 3),
                     counters.get("sweep_launches", 0),
                     counters.get("host_batches", 0)])

    for res, want in zip(swept, per_win):
        assert res.vertices == want, "sweep/per-window mismatch"
    # the acceptance bar: one sweep launch beats W independent submits
    assert dt_sweep < dt_perwin, (dt_sweep, dt_perwin)
    print(f"[sweep] {name} k={k} u={u} W={W}: per-window {dt_perwin:.3f}s "
          f"vs sweep {dt_sweep:.3f}s ({dt_perwin/dt_sweep:.1f}x)")
    return rows


def bench_trace_overhead(name: str = "fb_like", n_q: int = 512,
                         seed: int = 13, reps: int = 2,
                         assert_overhead: bool = True):
    """Tracing-overhead A/B (DESIGN.md §11 acceptance): replay the same
    open-loop query stream through an untraced and a traced engine
    sharing one warm registry (cache off so every query pays its real
    path), best-of-``reps`` per arm.

    Asserts on every run that >= 95% of completed queries carry the full
    span chain (query -> queue -> execute) and that the traced
    arm's Chrome trace export validates; on full runs additionally
    asserts traced p99 <= 1.05x untraced p99. Rows: one per arm,
    ``[workload, k, arm, queries, qps, p99_ms, chain_coverage, spans,
    dropped]``; the traced arm's export lands in
    ``results/bench/trace_engine.json``.
    """
    from collections import defaultdict

    from repro.obs.export import validate_chrome_trace
    from .common import RESULTS_DIR

    g = workload(name)
    k = default_k(name)
    registry = IndexRegistry(capacity=4)
    registry.register_graph(name, g)
    queries = random_queries(g, n_q, seed=seed)

    def run_arm(trace: bool):
        best = None
        for _ in range(max(1, reps)):
            cfg = EngineConfig(max_batch=256, flush_ms=2.0,
                               cache_capacity=0, trace=trace)
            with ServingEngine(cfg, registry=registry) as eng:
                eng.warmup(name)
                t0 = time.perf_counter()
                futures = []
                for i in range(0, len(queries), cfg.max_batch):
                    futures += eng.submit_specs(
                        name, [TCCSQuery(u, ts, te, k) for (u, ts, te)
                               in queries[i:i + cfg.max_batch]])
                eng.flush()
                results = [f.result(timeout=300) for f in futures]
                dt = time.perf_counter() - t0
                p99 = eng.stats()["engine"]["latency"]["e2e"]["p99_ms"]
                coverage, spans, dropped, doc = 0.0, 0, 0, None
                if trace:
                    by_trace = defaultdict(set)
                    for s in eng.tracer.spans():
                        by_trace[s.trace_id].add(s.name)
                    full = sum(
                        1 for r in results
                        if {"query", "queue", "execute"}
                        <= by_trace.get(r.provenance.trace_id, set()))
                    coverage = full / len(results)
                    spans = len(eng.tracer)
                    dropped = eng.tracer.dropped
                    import os
                    os.makedirs(RESULTS_DIR, exist_ok=True)
                    doc = eng.export_trace(
                        os.path.join(RESULTS_DIR, "trace_engine.json"),
                        extra={"bench": "trace_overhead", "workload": name})
                arm = (dt, p99, coverage, spans, dropped, doc)
                if best is None or arm[1] < best[1]:
                    best = arm
        return best

    dt_off, p99_off, _, _, _, _ = run_arm(False)
    dt_on, p99_on, coverage, spans, dropped, doc = run_arm(True)
    validate_chrome_trace(doc)
    assert coverage >= 0.95, f"span chain coverage {coverage:.3f} < 0.95"
    ratio = p99_on / p99_off if p99_off > 0 else 1.0
    if assert_overhead:
        assert ratio <= 1.05, (
            f"tracing p99 overhead {ratio:.3f}x exceeds 1.05x "
            f"(off={p99_off:.3f}ms on={p99_on:.3f}ms)")
    print(f"[trace-overhead] {name} k={k}: p99 off={p99_off:.3f}ms "
          f"on={p99_on:.3f}ms ({ratio:.3f}x), chain coverage "
          f"{coverage:.1%}, {spans} spans ({dropped} dropped)")
    rows = [
        [name, k, "untraced", n_q, round(n_q / dt_off, 1),
         round(p99_off, 3), "", 0, 0],
        [name, k, "traced", n_q, round(n_q / dt_on, 1),
         round(p99_on, 3), round(coverage, 4), spans, dropped],
    ]
    write_csv("trace_overhead.csv",
              ["workload", "k", "arm", "queries", "qps", "p99_ms",
               "chain_coverage", "spans", "dropped"], rows)
    return rows


def bench_kernels():
    """Per-kernel micro: interpret-mode Pallas vs jnp reference (CPU)."""
    from repro.kernels import ops, ref
    rng = np.random.default_rng(0)
    rows = []

    def run(tag, f_kernel, f_ref, *args):
        out = f_kernel(*args)
        jax.block_until_ready(out)
        out, dt_k = timed(lambda: jax.block_until_ready(f_kernel(*args)))
        out, dt_r = timed(lambda: jax.block_until_ready(f_ref(*args)))
        rows.append([tag, round(dt_k * 1e3, 3), round(dt_r * 1e3, 3)])

    n, m = 2000, 8000
    src = jnp.asarray(rng.integers(0, n, m), jnp.int32)
    dst = jnp.asarray(rng.integers(0, n, m), jnp.int32)
    alive = jnp.ones(m, bool)
    run("degree_count(2k,8k)", ops.degree_count, ref.degree_count, src, dst, alive, n)

    a = jnp.asarray(rng.normal(size=(512, 512)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(512, 512)), jnp.float32)
    run("matmul(512)", ops.matmul, ref.matmul, a, b)

    vals = jnp.asarray(rng.normal(size=(4096, 64)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, 512, 4096), jnp.int32)
    run("segment_sum(4k,64)", lambda *xs: ops.segment_sum(*xs),
        lambda *xs: ref.segment_sum_sorted(*xs), vals, ids, 512)

    q = jnp.asarray(rng.normal(size=(1, 256, 4, 64)), jnp.float32)
    run("flash_attn(256)", lambda q_: ops.flash_attention(q_, q_, q_, causal=True),
        lambda q_: ref.flash_attention(q_, q_, q_, causal=True), q)

    write_csv("kernels.csv", ["kernel", "pallas_interpret_ms", "jnp_ref_ms"], rows)
    return rows
