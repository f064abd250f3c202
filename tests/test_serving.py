"""Serving-engine tests: exactness vs Algorithm 1 on every route, cache
semantics, shape-bucketed compile stability, planner routing, registry
lifecycle, batcher flush behaviour, metrics."""

import os
import subprocess
import sys
import textwrap
import time
from concurrent.futures import Future

import numpy as np
import pytest

from repro.core.temporal_graph import gen_temporal_graph
from repro.serving import (
    EngineConfig, IndexRegistry, LatencyHistogram, MicroBatcher, Request,
    ServingEngine, ShardedExecutor, TCCSQuery, bucket_size, pad_queries,
)
from repro.core.query_api import EMPTY_WINDOW


def lenient_spec(u, ts, te, k):
    """v2 spec with the legacy streams' lenient window semantics: a
    malformed window (ts > te) folds onto the canonical empty marker
    instead of raising at validation."""
    if ts > te:
        ts, te = EMPTY_WINDOW
    return TCCSQuery(u, ts, te, k)


def alg1(pecb, u, ts, te, k=2):
    """Algorithm-1 reference through the non-deprecated component
    routine (the deprecated .query shim wrapped exactly this). Accepts
    either a per-k PECBIndex or the registry's stratified index (sliced
    to the requested stratum)."""
    if hasattr(pecb, "slice_k"):
        pecb = pecb.slice_k(k)
    return frozenset(pecb._component_vertices(u, ts, te))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def random_stream(g, n_q, rng, oob_frac=0.2):
    """Random (u, ts, te) stream including out-of-range windows: te < ts
    and ts beyond t_max."""
    qs = []
    for _ in range(n_q):
        u = int(rng.integers(0, g.n))
        if rng.random() < oob_frac:
            ts = int(rng.integers(1, 2 * g.t_max))
            te = int(rng.integers(0, 2 * g.t_max))   # may be < ts
        else:
            ts = int(rng.integers(1, g.t_max + 1))
            te = int(rng.integers(ts, g.t_max + 1))
        qs.append((u, ts, te))
    return qs


def run_engine(eng, workload, k, queries, chunk=64):
    futs = []
    for i in range(0, len(queries), chunk):
        futs += eng.submit_specs(
            workload,
            [lenient_spec(u, ts, te, k) for (u, ts, te) in queries[i:i + chunk]])
    eng.flush()
    return [f.result(timeout=60).vertices for f in futs]


class TestEngineExactness:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_device_route_matches_alg1(self, seed):
        rng = np.random.default_rng(seed)
        g = gen_temporal_graph(n=35, m=260, t_max=16, seed=seed + 70)
        cfg = EngineConfig(max_batch=64, flush_ms=500.0, host_threshold=0,
                           min_bucket=8, cache_capacity=0)
        with ServingEngine(cfg) as eng:
            eng.register_graph("g", g)
            h = eng.registry.get("g")
            qs = random_stream(g, 120, rng)
            got = run_engine(eng, "g", 2, qs)
            assert eng.metrics.counter("device_batches") > 0
            assert eng.metrics.counter("host_batches") == 0
        for (u, ts, te), res in zip(qs, got):
            assert res == alg1(h.pecb, u, ts, te), (u, ts, te)

    def test_host_route_matches_alg1(self):
        rng = np.random.default_rng(3)
        g = gen_temporal_graph(n=30, m=220, t_max=14, seed=41)
        cfg = EngineConfig(max_batch=64, flush_ms=500.0,
                           host_threshold=10**9, cache_capacity=0)
        with ServingEngine(cfg) as eng:
            eng.register_graph("g", g)
            h = eng.registry.get("g")
            qs = random_stream(g, 80, rng)
            got = run_engine(eng, "g", 3, qs)
            assert eng.metrics.counter("host_batches") > 0
            assert eng.metrics.counter("device_batches") == 0
        for (u, ts, te), res in zip(qs, got):
            assert res == alg1(h.pecb, u, ts, te, k=3)

    def test_unsupported_k_returns_empty(self):
        """k above the graph's k-max is outside every stratum: the engine
        answers exactly-empty host-side, no device launch."""
        g = gen_temporal_graph(n=20, m=60, t_max=8, seed=9)
        with ServingEngine(EngineConfig(flush_ms=500.0)) as eng:
            eng.register_graph("g", g)
            h = eng.registry.get("g")
            assert 50 not in h.pecb.supported_ks
            assert 50 > h.pecb.k_max_graph
            qs = [(u, 1, g.t_max) for u in range(g.n)]
            got = run_engine(eng, "g", 50, qs)
            assert all(r == frozenset() for r in got)
            # trivially-empty k always routes host (nothing to launch)
            assert eng.metrics.counter("device_batches") == 0
            assert eng.metrics.counter("unsupported_k_queries") == g.n

    def test_mixed_k_one_engine(self):
        """One engine serves several k values off ONE stratified build;
        answers stay per-k exact and no rebuild happens between ks."""
        g = gen_temporal_graph(n=30, m=240, t_max=12, seed=5)
        rng = np.random.default_rng(5)
        qs = random_stream(g, 40, rng, oob_frac=0.0)
        with ServingEngine(EngineConfig(max_batch=64, flush_ms=500.0,
                                        host_threshold=0)) as eng:
            eng.register_graph("g", g)
            for k in (2, 3):
                got = run_engine(eng, "g", k, qs)
                h = eng.registry.get("g")
                for (u, ts, te), res in zip(qs, got):
                    assert res == alg1(h.pecb, u, ts, te, k=k), (k, u, ts, te)
            assert eng.registry.builds == 1

    def test_mixed_k_single_batch(self):
        """Queries with different k share one flushed batch (one device
        launch) and each resolves against its own stratum."""
        g = gen_temporal_graph(n=30, m=240, t_max=12, seed=6)
        rng = np.random.default_rng(6)
        qs = random_stream(g, 48, rng, oob_frac=0.0)
        cfg = EngineConfig(max_batch=64, flush_ms=500.0, host_threshold=0,
                           cache_capacity=0)
        with ServingEngine(cfg) as eng:
            eng.register_graph("g", g)
            h = eng.registry.get("g")
            ks = [int(rng.choice(h.pecb.supported_ks)) for _ in qs]
            futs = eng.submit_specs(
                "g", [TCCSQuery(u, ts, te, k)
                      for (u, ts, te), k in zip(qs, ks)])
            eng.flush()
            got = [f.result(timeout=60).vertices for f in futs]
            assert eng.metrics.counter("device_batches") == 1
            for (u, ts, te), k, res in zip(qs, ks, got):
                assert res == alg1(h.pecb, u, ts, te, k=k), (k, u, ts, te)


class TestCache:
    def test_cache_hit_is_exact_and_instant(self):
        g = gen_temporal_graph(n=25, m=180, t_max=10, seed=21)
        with ServingEngine(EngineConfig(flush_ms=500.0, host_threshold=0,
                                        cache_capacity=64)) as eng:
            eng.register_graph("g", g)
            h = eng.registry.get("g")
            qs = [(u, 2, 9) for u in range(10)]
            first = run_engine(eng, "g", 2, qs)
            assert eng.metrics.counter("cache_hits") == 0
            futs = eng.submit_specs(
                "g", [TCCSQuery(u, ts, te, 2) for (u, ts, te) in qs])  # all hits
            assert all(f.done() for f in futs)   # resolved on submit path
            second = [f.result().vertices for f in futs]
            assert first == second
            assert eng.metrics.counter("cache_hits") == len(qs)
            for (u, ts, te), res in zip(qs, second):
                assert res == alg1(h.pecb, u, ts, te)

    def test_cache_lru_eviction(self):
        from repro.serving import ResultCache
        c = ResultCache(capacity=2)
        c.put("a", frozenset({1})); c.put("b", frozenset({2}))
        assert c.get("a") == frozenset({1})      # refreshes "a"
        c.put("c", frozenset({3}))               # evicts "b"
        assert c.get("b") is None
        assert c.get("a") is not None and c.get("c") is not None
        assert c.stats()["evictions"] == 1

    def test_cache_disabled(self):
        g = gen_temporal_graph(n=20, m=120, t_max=8, seed=2)
        with ServingEngine(EngineConfig(flush_ms=500.0,
                                        cache_capacity=0)) as eng:
            eng.register_graph("g", g)
            run_engine(eng, "g", 2, [(1, 1, 5)] * 3)
            assert eng.metrics.counter("cache_hits") == 0


class TestBucketing:
    def test_bucket_size(self):
        assert bucket_size(1) == 8
        assert bucket_size(8) == 8
        assert bucket_size(9) == 16
        assert bucket_size(100) == 128
        assert bucket_size(200, max_batch=256) == 256
        assert bucket_size(255, min_bucket=8, max_batch=256) == 256
        assert bucket_size(3, min_bucket=4, max_batch=16) == 4

    def test_pad_queries_inert(self):
        u, ts, te = pad_queries([5, 6], [2, 3], [7, 8], 8)
        assert u.shape == ts.shape == te.shape == (8,)
        assert list(u[:2]) == [5, 6]
        assert (te[2:] < ts[2:]).all()           # pad windows are empty

    def test_no_recompile_within_bucket(self):
        """Batch sizes 3/5/6/8 all pad to one bucket: exactly one compile;
        size 13 moves to the next bucket: exactly one more."""
        g = gen_temporal_graph(n=30, m=200, t_max=12, seed=33)
        rng = np.random.default_rng(0)
        cfg = EngineConfig(max_batch=64, flush_ms=1000.0, host_threshold=0,
                           min_bucket=8, cache_capacity=0)
        with ServingEngine(cfg) as eng:
            eng.register_graph("g", g)
            eng.registry.get("g")             # build outside measurement

            def wave(n_q):
                qs = random_stream(g, n_q, rng, oob_frac=0.0)
                futs = eng.submit_specs(
                    "g", [TCCSQuery(u, ts, te, 2) for (u, ts, te) in qs])
                eng.flush()
                [f.result(timeout=60) for f in futs]
                eng.drain()

            c0 = ShardedExecutor.compile_count()
            wave(3)
            c1 = ShardedExecutor.compile_count()
            assert c1 == c0 + 1                  # first touch of bucket 8
            for n_q in (5, 6, 8):
                wave(n_q)
            assert ShardedExecutor.compile_count() == c1   # no recompiles
            wave(13)                             # bucket 16
            assert ShardedExecutor.compile_count() == c1 + 1

    def test_warmup_non_power_of_two_max_batch(self):
        g = gen_temporal_graph(n=25, m=150, t_max=10, seed=35)
        cfg = EngineConfig(max_batch=100, flush_ms=500.0, host_threshold=0)
        with ServingEngine(cfg) as eng:
            eng.register_graph("g", g)
            eng.warmup("g")                   # must not assert on 128 > 100
            got = run_engine(eng, "g", 2, [(0, 1, 9), (1, 2, 8)])
            h = eng.registry.get("g")
            assert got[0] == alg1(h.pecb, 0, 1, 9)

    def test_warmup_precompiles_all_buckets(self):
        g = gen_temporal_graph(n=30, m=200, t_max=12, seed=34)
        cfg = EngineConfig(max_batch=32, flush_ms=1000.0, host_threshold=0,
                           min_bucket=8, cache_capacity=0)
        with ServingEngine(cfg) as eng:
            eng.register_graph("g", g)
            eng.warmup("g")                   # buckets 8, 16, 32
            c0 = ShardedExecutor.compile_count()
            rng = np.random.default_rng(1)
            for n_q in (2, 7, 12, 20, 32):
                futs = eng.submit_specs(
                    "g", [TCCSQuery(u, ts, te, 2)
                          for (u, ts, te) in random_stream(g, n_q, rng, 0.0)])
                eng.flush()
                [f.result(timeout=60) for f in futs]
                eng.drain()
            assert ShardedExecutor.compile_count() == c0


class TestPlannerRouting:
    def test_straggler_goes_host_big_goes_device(self):
        g = gen_temporal_graph(n=30, m=200, t_max=12, seed=11)
        rng = np.random.default_rng(4)
        cfg = EngineConfig(max_batch=64, flush_ms=1000.0, host_threshold=8,
                           cache_capacity=0)
        with ServingEngine(cfg) as eng:
            eng.register_graph("g", g)
            h = eng.registry.get("g")
            small = random_stream(g, 3, rng, 0.0)
            futs = eng.submit_specs(
                "g", [TCCSQuery(u, ts, te, 2) for (u, ts, te) in small])
            eng.flush(); res_small = [f.result(timeout=60).vertices for f in futs]
            eng.drain()
            assert eng.metrics.counter("host_batches") == 1
            assert eng.metrics.counter("device_batches") == 0
            big = random_stream(g, 40, rng, 0.0)
            futs = eng.submit_specs(
                "g", [TCCSQuery(u, ts, te, 2) for (u, ts, te) in big])
            eng.flush(); res_big = [f.result(timeout=60).vertices for f in futs]
            eng.drain()
            assert eng.metrics.counter("device_batches") == 1
            # both routes exact
            for (u, ts, te), r in zip(small + big, res_small + res_big):
                assert r == alg1(h.pecb, u, ts, te)


class TestRegistry:
    def test_memoize_and_evict(self):
        reg = IndexRegistry(capacity=1)
        g1 = gen_temporal_graph(n=20, m=100, t_max=8, seed=1)
        g2 = gen_temporal_graph(n=20, m=100, t_max=8, seed=2)
        reg.register_graph("g1", g1); reg.register_graph("g2", g2)
        h = reg.get("g1")
        assert reg.get("g1") is h             # memoized
        assert reg.builds == 1
        reg.get("g2")                         # evicts "g1": LRU
        assert reg.evictions == 1
        assert "g1" not in reg
        h2 = reg.get("g1")                    # rebuild (evicts "g2")
        assert h2 is not h and reg.builds == 3

    def test_rebinding_graph_name_raises(self):
        reg = IndexRegistry()
        g1 = gen_temporal_graph(n=15, m=60, t_max=6, seed=1)
        g2 = gen_temporal_graph(n=15, m=60, t_max=6, seed=2)
        reg.register_graph("g", g1)
        reg.register_graph("g", g1)              # same object: no-op
        with pytest.raises(ValueError, match="immutable"):
            reg.register_graph("g", g2)

    def test_eviction_hook_fires_outside_lock(self):
        evicted = []
        reg = IndexRegistry(capacity=1,
                            on_evict=lambda k, h: evicted.append((k, reg.stats())))
        g = gen_temporal_graph(n=15, m=80, t_max=6, seed=3)
        reg.register_graph("g", g)
        reg.get("g")
        reg.register_graph("g2",
                           gen_temporal_graph(n=15, m=80, t_max=6, seed=4))
        reg.get("g2")                         # evicts "g"
        assert [k for (k, _) in evicted] == ["g"]
        # the hook could re-enter the registry (stats() takes the lock)

    def test_engine_retires_batcher_on_eviction(self):
        g1 = gen_temporal_graph(n=20, m=100, t_max=8, seed=1)
        g2 = gen_temporal_graph(n=20, m=100, t_max=8, seed=2)
        cfg = EngineConfig(flush_ms=200.0, registry_capacity=1,
                           cache_capacity=0)
        with ServingEngine(cfg) as eng:
            eng.register_graph("g1", g1)
            eng.register_graph("g2", g2)
            eng.answer("g1", TCCSQuery(0, 1, 6, 2))
            assert "g1" in eng._batchers
            eng.answer("g2", TCCSQuery(0, 1, 6, 2))  # evicts "g1"
            assert "g1" not in eng._batchers
            assert "g2" in eng._batchers
            # re-query after eviction: rebuild + fresh batcher, exact answer
            h1 = eng.registry.get("g1")
            assert eng.answer("g1", TCCSQuery(3, 1, 6, 2)).vertices == \
                alg1(h1.pecb, 3, 1, 6)

    def test_shared_registry_retires_batchers_in_every_engine(self):
        g1 = gen_temporal_graph(n=20, m=100, t_max=8, seed=1)
        g2 = gen_temporal_graph(n=20, m=100, t_max=8, seed=2)
        reg = IndexRegistry(capacity=1)
        reg.register_graph("g1", g1); reg.register_graph("g2", g2)
        cfg = EngineConfig(flush_ms=100.0, cache_capacity=0)
        with ServingEngine(cfg, registry=reg) as a, \
             ServingEngine(cfg, registry=reg) as b:
            a.answer("g1", TCCSQuery(0, 1, 6, 2))
            b.answer("g1", TCCSQuery(1, 1, 6, 2))
            assert "g1" in a._batchers and "g1" in b._batchers
            a.answer("g2", TCCSQuery(0, 1, 6, 2))  # evicts "g1"
            assert "g1" not in a._batchers
            assert "g1" not in b._batchers        # B's listener fired too

    def test_unknown_workload_raises(self):
        with pytest.raises(KeyError):
            IndexRegistry().get("no_such_graph")

    def test_bench_workload_resolves_by_name(self):
        reg = IndexRegistry()
        g = reg.resolve_graph("fb_like")
        assert g.n == 300


class TestBatcher:
    def test_deadline_flush(self):
        b = MicroBatcher(lambda reqs: [len(reqs)] * len(reqs),
                         max_batch=64, flush_ms=30.0)
        try:
            fut = b.submit(Request(0, 1, 1, Future(), time.perf_counter()))
            assert fut.result(timeout=5) == 1    # deadline fired, batch of 1
        finally:
            b.close()

    def test_full_batch_flushes_immediately(self):
        b = MicroBatcher(lambda reqs: [len(reqs)] * len(reqs),
                         max_batch=4, flush_ms=10_000.0)
        try:
            t0 = time.perf_counter()
            futs = b.submit_many([Request(i, 1, 1, Future(), t0) for i in range(4)])
            assert [f.result(timeout=5) for f in futs] == [4] * 4
            assert time.perf_counter() - t0 < 5.0   # did not wait 10s
        finally:
            b.close()

    def test_idle_flush_does_not_leak_into_next_deadline(self):
        b = MicroBatcher(lambda reqs: [len(reqs)] * len(reqs),
                         max_batch=64, flush_ms=500.0)
        try:
            b.flush()                            # idle: must be a no-op
            fut = b.submit(Request(0, 1, 1, Future(), time.perf_counter()))
            time.sleep(0.1)
            assert not fut.done()                # still inside the window
            b.flush()
            assert fut.result(timeout=5) == 1
        finally:
            b.close()

    def test_execute_error_fails_futures(self):
        def boom(reqs):
            raise ValueError("kaput")
        b = MicroBatcher(boom, max_batch=4, flush_ms=5.0)
        try:
            fut = b.submit(Request(0, 1, 1, Future(), time.perf_counter()))
            with pytest.raises(ValueError, match="kaput"):
                fut.result(timeout=5)
        finally:
            b.close()

    def test_close_flushes_pending(self):
        b = MicroBatcher(lambda reqs: [r.u for r in reqs],
                         max_batch=64, flush_ms=10_000.0)
        futs = b.submit_many([Request(i, 1, 1, Future(), time.perf_counter())
                              for i in range(3)])
        b.close()
        assert [f.result(timeout=1) for f in futs] == [0, 1, 2]


class TestMetrics:
    def test_histogram_percentiles(self):
        h = LatencyHistogram()
        for i in range(1, 101):
            h.add(i / 1e3)                       # 1..100 ms
        s = h.summary()
        assert s["count"] == 100
        assert abs(s["p50_ms"] - 50) <= 2
        assert abs(s["p95_ms"] - 95) <= 2
        assert abs(s["p99_ms"] - 99) <= 2
        assert abs(s["mean_ms"] - 50.5) < 0.1

    def test_engine_records_stages(self):
        g = gen_temporal_graph(n=20, m=120, t_max=8, seed=6)
        with ServingEngine(EngineConfig(flush_ms=200.0, host_threshold=0,
                                        cache_capacity=8)) as eng:
            eng.register_graph("g", g)
            run_engine(eng, "g", 2, [(1, 1, 5), (2, 1, 5)])
            eng.submit_spec("g", TCCSQuery(1, 1, 5, 2)).result(timeout=10)  # cache hit
            snap = eng.stats()
            lat = snap["engine"]["latency"]
            assert lat["e2e"]["count"] == 3
            assert lat["queue_wait"]["count"] == 2
            assert "device_exec" in lat
            assert snap["engine"]["counters"]["cache_hits"] == 1
            assert snap["cache"]["size"] == 2
            assert snap["devices"] >= 1


@pytest.mark.slow
def test_engine_multi_device_sharded():
    """The whole engine under a forced 8-CPU-device topology: the executor
    takes the sharded path and answers stay exact."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np
        import jax
        assert jax.device_count() == 8
        from repro.core.temporal_graph import gen_temporal_graph
        from repro.serving import EngineConfig, ServingEngine
        g = gen_temporal_graph(n=40, m=250, t_max=15, seed=1)
        cfg = EngineConfig(max_batch=64, flush_ms=500.0, host_threshold=0,
                           cache_capacity=0)
        with ServingEngine(cfg) as eng:
            assert eng.executor.num_devices == 8
            assert eng.executor.batch_sharding is not None
            eng.register_graph("g", g)
            h = eng.registry.get("g")
            rng = np.random.default_rng(0)
            qs = [(int(rng.integers(0, g.n)), int(rng.integers(1, g.t_max)),
                   int(rng.integers(1, g.t_max + 1))) for _ in range(48)]
            from repro.serving import TCCSQuery
            futs = eng.submit_specs(
                "g", [TCCSQuery(u, ts, te, 2) if ts <= te
                      else TCCSQuery(u, 1, 0, 2) for (u, ts, te) in qs])
            eng.flush()
            got = [f.result(timeout=120).vertices for f in futs]
            for (u, ts, te), res in zip(qs, got):
                assert res == frozenset(
                    h.pecb.slice_k(2)._component_vertices(u, ts, te))
        print("sharded engine ok")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    assert "sharded engine ok" in res.stdout


class TestAsyncRegistry:
    """PR-2: background index builds (DESIGN.md §7.4)."""

    def test_builds_counter_survives_concurrent_cold_keys(self):
        """The builds counter is a read-modify-write under the registry
        lock; hammering many distinct cold keys from many threads must not
        lose updates."""
        import threading

        reg = IndexRegistry(capacity=32, build_workers=8)
        names = []
        for i in range(8):
            name = f"g{i}"
            reg.register_graph(name, gen_temporal_graph(
                n=12, m=50, t_max=5, seed=i))
            names.append(name)
        start = threading.Barrier(16)

        def hammer(name):
            start.wait()
            for _ in range(4):
                reg.get(name)

        threads = [threading.Thread(target=hammer, args=(name,))
                   for name in names for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert reg.builds == len(names)
        reg.close()

    def test_get_nowait_miss_then_hit(self):
        reg = IndexRegistry()
        reg.register_graph("g", gen_temporal_graph(n=12, m=50, t_max=5, seed=0))
        assert reg.get_nowait("g", start_build=False) is None
        assert "g" not in reg
        h = reg.get_nowait("g")              # miss, but schedules the build
        assert h is None
        built = reg.get_async("g").result(timeout=60)
        assert reg.get_nowait("g") is built
        reg.close()

    def test_get_async_coalesces_thundering_herd(self):
        reg = IndexRegistry()
        reg.register_graph("g", gen_temporal_graph(n=14, m=60, t_max=6, seed=1))
        futs = [reg.get_async("g") for _ in range(6)]
        handles = {id(f.result(timeout=60)) for f in futs}
        assert len(handles) == 1 and reg.builds == 1
        reg.close()

    def test_build_failure_surfaces_on_future(self):
        reg = IndexRegistry()
        with pytest.raises(KeyError):
            reg.get_async("no_such_graph").result(timeout=60)
        assert reg.builds == 0
        # the failed key is not stuck pending: a later register succeeds
        reg.register_graph("no_such_graph",
                           gen_temporal_graph(n=10, m=40, t_max=4, seed=2))
        assert reg.get("no_such_graph").pecb is not None
        reg.close()

    def test_build_stage_metrics_recorded(self):
        from repro.serving.metrics import EngineMetrics

        metrics = EngineMetrics()
        reg = IndexRegistry(metrics=metrics)
        reg.register_graph("g", gen_temporal_graph(n=14, m=70, t_max=6, seed=3))
        h = reg.get("g")
        subs = {f"core_times.{s}" for s in
                ("prepare", "dispatch", "sweep", "compress")}
        assert set(h.build_stages) == {"core_times", "forest",
                                       "device"} | subs
        assert all(v >= 0 for v in h.build_stages.values())
        assert sum(h.build_stages[s] for s in subs) <= \
            h.build_stages["core_times"]
        snap = metrics.snapshot()
        for stage in ("core_times", "forest", "device"):
            assert snap["latency"][f"index_build_{stage}"]["count"] == 1
        reg.close()

    def test_cold_submit_does_not_block_on_build(self):
        """A cold (workload, k) submit returns before the build completes;
        the queries resolve once the background build installs the index."""
        import threading

        release = threading.Event()

        class SlowRegistry(IndexRegistry):
            def _build(self, key):
                release.wait(timeout=60)        # simulate a long offline build
                return super()._build(key)

        g = gen_temporal_graph(n=15, m=70, t_max=6, seed=4)
        reg = SlowRegistry()
        reg.register_graph("g", g)
        cfg = EngineConfig(flush_ms=5.0)
        with ServingEngine(cfg, registry=reg) as eng:
            t0 = time.perf_counter()
            fut = eng.submit_spec("g", TCCSQuery(0, 1, 6, 2))
            submitted_in = time.perf_counter() - t0
            assert submitted_in < 30            # returned while build blocked
            assert not fut.done()
            release.set()
            want = alg1(reg.get("g").pecb, 0, 1, 6)
            assert fut.result(timeout=60).vertices == want
        reg.close()

    def test_engine_prefetch_warms_registry(self):
        g = gen_temporal_graph(n=15, m=70, t_max=6, seed=5)
        with ServingEngine(EngineConfig()) as eng:
            eng.register_graph("g", g)
            eng.prefetch("g").result(timeout=60)
            assert "g" in eng.registry
            assert eng.registry.stats()["pending"] == []
