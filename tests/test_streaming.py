"""Streaming epoch plane (DESIGN.md §9): graph epochs, incremental
core-time/index refresh, serving-path epoch swap, and the bugfix-sweep
regressions that rode along (batcher flush flag, cache re-stamp copy,
empty-graph canonicalization, deprecation warnings)."""

import time
from concurrent.futures import Future

import numpy as np
import pytest

from repro.core.batch_query import refresh_device, to_device
from repro.core.core_time import edge_core_times, extend_core_times
from repro.core.ctmsf_index import CTMSFIndex
from repro.core.ef_index import EFIndex
from repro.core.pecb_index import build_pecb_index, build_stratified_index
from repro.core.query_api import (EMPTY_WINDOW, ResultMode, TCCSQuery,
                                  WindowSweep)
from repro.core.streaming import extend_pecb_index
from repro.core.temporal_graph import (TemporalGraph, gen_temporal_graph,
                                       random_queries)
from repro.serving import EngineConfig, ServingEngine
from repro.serving.batcher import MicroBatcher, Request
from repro.serving.metrics import EngineMetrics

PECB_FIELDS = ("node_u", "node_v", "node_ct", "node_edge", "node_live_from",
               "node_live_to", "row_ptr", "ent_ts", "ent_left", "ent_right",
               "ent_parent", "vrow_ptr", "vent_ts", "vent_node")


def assert_pecb_identical(a, b):
    """Bit-identity for either a per-k PECBIndex or a StratifiedPECB
    (same packed field names; the stratified form adds the k-block
    offset tables and global version endpoints)."""
    for f in PECB_FIELDS:
        assert np.array_equal(getattr(a, f), getattr(b, f)), f
    assert (a.n, a.m, a.t_max) == (b.n, b.m, b.t_max)
    if hasattr(a, "supported_ks"):
        assert a.supported_ks == b.supported_ks
        assert a.k_max_graph == b.k_max_graph
        for f in ("knode_ptr", "kent_ptr", "kvent_ptr",
                  "ver_src", "ver_dst", "ver_t"):
            assert np.array_equal(getattr(a, f), getattr(b, f)), f
    else:
        assert a.k == b.k
    assert a.versions == b.versions


def split_epoch(g, frac):
    t_old = max(1, int(g.t_max * frac))
    g0, suffix = g.split_at(t_old)
    return g0, [tuple(e) for e in suffix.tolist()]


# ----------------------------------------------------------------------
# TemporalGraph.extend / split_at
# ----------------------------------------------------------------------

class TestExtend:
    def test_suffix_append_roundtrips_split(self):
        g = gen_temporal_graph(n=30, m=240, t_max=16, seed=1)
        g0, suffix = split_epoch(g, 0.6)
        g1 = g0.extend(suffix)
        assert g1.m == g.m and g1.t_max == g.t_max
        assert np.array_equal(g1.src, g.src)
        assert np.array_equal(g1.dst, g.dst)
        assert np.array_equal(g1.t, g.t)

    def test_historical_edges_rejected(self):
        g = gen_temporal_graph(n=20, m=100, t_max=10, seed=2)
        with pytest.raises(ValueError, match="suffix"):
            g.extend([(0, 1, g.t_max)])
        with pytest.raises(ValueError, match="suffix"):
            g.extend([(0, 1, 1), (2, 3, g.t_max + 5)])

    def test_out_of_range_vertices_rejected(self):
        g = gen_temporal_graph(n=20, m=100, t_max=10, seed=3)
        with pytest.raises(ValueError, match="endpoints"):
            g.extend([(0, g.n, g.t_max + 1)])

    def test_empty_append_returns_self_and_loops_dropped(self):
        g = gen_temporal_graph(n=20, m=100, t_max=10, seed=4)
        assert g.extend([]) is g
        assert g.extend([(5, 5, g.t_max + 1)]) is g
        g2 = g.extend([(1, 2, g.t_max + 2), (3, 3, g.t_max + 2)])
        assert g2.m == g.m + 1


# ----------------------------------------------------------------------
# incremental refresh == cold rebuild, bit-identically
# ----------------------------------------------------------------------

class TestIncrementalRefresh:
    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    @pytest.mark.parametrize("k", [2, 3])
    @pytest.mark.parametrize("frac", [0.3, 0.7])
    def test_bit_identical_to_cold(self, seed, k, frac):
        g = gen_temporal_graph(n=30, m=260, t_max=15, seed=seed)
        g0, suffix = split_epoch(g, frac)
        if g0.m == 0 or not suffix:
            pytest.skip("degenerate split")
        tab0 = edge_core_times(g0, k)
        idx0 = build_pecb_index(g0, k, tab0)
        g1 = g0.extend(suffix)
        tab1 = extend_core_times(g1, k, tab0)
        tab_cold = edge_core_times(g, k)
        for f in ("edge_id", "ts_from", "ts_to", "ct", "vertex_ct"):
            assert np.array_equal(getattr(tab1, f), getattr(tab_cold, f)), f
        assert_pecb_identical(extend_pecb_index(g1, k, tab1, idx0),
                              build_pecb_index(g, k, tab_cold))

    def test_chained_epochs(self):
        g = gen_temporal_graph(n=50, m=700, t_max=30, seed=7)
        k = 3
        cuts = [10, 18, 24, g.t_max]
        cur, _ = g.split_at(cuts[0])
        tab = edge_core_times(cur, k)
        idx = build_pecb_index(cur, k, tab)
        for t_cut in cuts[1:]:
            gn, _ = g.split_at(t_cut)
            suffix = np.stack([gn.src[cur.m:], gn.dst[cur.m:],
                               gn.t[cur.m:]], axis=1)
            cur = cur.extend([tuple(e) for e in suffix.tolist()])
            tab = extend_core_times(cur, k, tab)
            idx = extend_pecb_index(cur, k, tab, idx)
        assert_pecb_identical(idx, build_pecb_index(g, k))

    def test_build_pecb_index_resume_from(self):
        g = gen_temporal_graph(n=30, m=220, t_max=12, seed=11)
        g0, suffix = split_epoch(g, 0.5)
        tab0 = edge_core_times(g0, 2)
        idx0 = build_pecb_index(g0, 2, tab0)
        g1 = g0.extend(suffix)
        tab1 = extend_core_times(g1, 2, tab0)
        assert_pecb_identical(
            build_pecb_index(g1, 2, tab1, resume_from=idx0),
            build_pecb_index(g, 2))
        with pytest.raises(ValueError, match="extend_core_times"):
            build_pecb_index(g1, 2, resume_from=idx0)

    def test_mismatched_epoch_inputs_raise(self):
        g = gen_temporal_graph(n=30, m=220, t_max=12, seed=12)
        g0, suffix = split_epoch(g, 0.5)
        tab0 = edge_core_times(g0, 2)
        idx0 = build_pecb_index(g0, 2, tab0)
        g1 = g0.extend(suffix)
        tab1 = extend_core_times(g1, 2, tab0)
        with pytest.raises(ValueError, match="k="):
            extend_pecb_index(g1, 3, tab1, idx0)
        with pytest.raises(ValueError, match="core-time table"):
            extend_pecb_index(g1, 2, tab0, idx0)
        # an index of a *different* graph must be refused, not absorbed
        g_other = gen_temporal_graph(n=30, m=220, t_max=6, seed=99)
        idx_other = build_pecb_index(g_other, 2)
        with pytest.raises(ValueError):
            extend_pecb_index(g1, 2, tab1, idx_other)

    def test_refresh_answers_match_oracle_on_new_windows(self):
        from repro.core.kcore import tccs_oracle
        g = gen_temporal_graph(n=30, m=300, t_max=14, seed=13)
        k = 2
        g0, suffix = split_epoch(g, 0.6)
        tab0 = edge_core_times(g0, k)
        idx0 = build_pecb_index(g0, k, tab0)
        g1 = g0.extend(suffix)
        tab1 = extend_core_times(g1, k, tab0)
        idx1 = extend_pecb_index(g1, k, tab1, idx0)
        rng = np.random.default_rng(0)
        for _ in range(40):
            u = int(rng.integers(0, g.n))
            ts = int(rng.integers(1, g.t_max + 1))
            te = int(rng.integers(ts, g.t_max + 1))
            got = idx1.answer(TCCSQuery(u, ts, te, k)).vertices
            assert got == frozenset(tccs_oracle(g, k, u, ts, te))


# ----------------------------------------------------------------------
# device mirror refresh
# ----------------------------------------------------------------------

class TestDeviceRefresh:
    def test_refresh_device_equals_fresh_upload(self):
        from repro.core.batch_query import batch_query
        import jax.numpy as jnp
        g = gen_temporal_graph(n=30, m=260, t_max=14, seed=21)
        k = 2
        g0, suffix = split_epoch(g, 0.6)
        tab0 = edge_core_times(g0, k)
        idx0 = build_pecb_index(g0, k, tab0)
        dix0 = to_device(idx0)
        g1 = g0.extend(suffix)
        tab1 = extend_core_times(g1, k, tab0)
        idx1 = extend_pecb_index(g1, k, tab1, idx0)
        dix1, stats = refresh_device(idx0, dix0, idx1)
        fresh = to_device(idx1)
        from repro.core.batch_query import _ARRAY_FIELDS, _META_FIELDS
        for f in _ARRAY_FIELDS:
            assert np.array_equal(np.asarray(getattr(dix1, f)),
                                  np.asarray(getattr(fresh, f))), f
        for f in _META_FIELDS:
            assert getattr(dix1, f) == getattr(fresh, f), f
        assert stats["reused"] + stats["suffix"] + stats["full"] == len(_ARRAY_FIELDS)
        qs = random_queries(g1, 16, seed=1)
        u = jnp.asarray([q[0] for q in qs], jnp.int32)
        ts = jnp.asarray([q[1] for q in qs], jnp.int32)
        te = jnp.asarray([q[2] for q in qs], jnp.int32)
        assert np.array_equal(np.asarray(batch_query(dix1, u, ts, te)[0]),
                              np.asarray(batch_query(fresh, u, ts, te)[0]))

    def test_noop_refresh_reuses_everything(self):
        g = gen_temporal_graph(n=20, m=150, t_max=10, seed=22)
        idx = build_pecb_index(g, 2)
        dix = to_device(idx)
        dix2, stats = refresh_device(idx, dix, idx)
        assert stats["full"] == 0 and stats["uploaded_bytes"] == 0
        assert stats["suffix"] == 0


# ----------------------------------------------------------------------
# registry epochs + engine ingest
# ----------------------------------------------------------------------

class TestServingEpochs:
    def _graph(self, seed=31):
        return gen_temporal_graph(n=40, m=420, t_max=18, seed=seed)

    def test_ingest_refreshes_and_swaps_atomically(self):
        g = self._graph()
        g0, suffix = split_epoch(g, 0.6)
        with ServingEngine(EngineConfig(flush_ms=0.5)) as eng:
            eng.register_graph("feed", g0)
            h0 = eng.registry.get("feed")
            assert h0.epoch == 0 and h0.tab is not None
            futures = eng.ingest("feed", suffix, wait=True)
            assert set(futures) == {"feed"}
            h1 = futures["feed"].result()
            assert h1.epoch == 1
            assert h1.graph.t_max == g.t_max
            assert eng.registry.get_nowait("feed") is h1
            # the refreshed index is bit-identical to a cold rebuild
            assert_pecb_identical(h1.pecb, build_stratified_index(g))
            # old handle still answers (old epoch pinned for in-flight use)
            q = TCCSQuery(3, 1, g0.t_max, 2)
            assert h0.pecb.answer(q).vertices == h1.pecb.answer(q).vertices
            assert eng.registry.stats()["refreshes"] == 1
            assert eng.registry.stats()["epochs"] == {"feed": 1}

    def test_ingest_without_resident_index_is_lazy(self):
        g = self._graph(32)
        g0, suffix = split_epoch(g, 0.5)
        with ServingEngine() as eng:
            eng.register_graph("feed", g0)
            assert eng.ingest("feed", suffix) == {}
            h = eng.registry.get("feed")   # cold build sees new epoch
            assert h.graph.t_max == g.t_max and h.epoch == 1

    def test_targeted_purge_preserves_old_window_cache(self):
        g = self._graph(33)
        g0, suffix = split_epoch(g, 0.6)
        with ServingEngine(EngineConfig(flush_ms=0.5)) as eng:
            eng.register_graph("feed", g0)
            eng.registry.get("feed")   # resident, no XLA warmup needed
            q = TCCSQuery(5, 1, g0.t_max // 2, 2)
            first = eng.answer("feed", q)
            hit = eng.answer("feed", q)
            assert hit.provenance.route == "cache"
            cached = len(eng.cache)
            assert cached >= 1
            eng.ingest("feed", suffix, wait=True)
            # suffix epochs invalidate nothing: every cached canonical
            # window predates the appended range
            assert len(eng.cache) == cached
            again = eng.answer("feed", q)
            assert again.provenance.route == "cache"
            assert again.vertices == first.vertices

    def test_queries_answer_throughout_refresh(self):
        g = self._graph(34)
        g0, suffix = split_epoch(g, 0.7)
        with ServingEngine(EngineConfig(flush_ms=0.5)) as eng:
            eng.register_graph("feed", g0)
            eng.registry.get("feed")   # resident, no XLA warmup needed
            futures = eng.ingest("feed", suffix)
            refresh_fut = futures["feed"]
            qs = random_queries(g0, 64, seed=2)
            answered = 0
            while not refresh_fut.done() or answered < 64:
                u, ts, te = qs[answered % len(qs)]
                res = eng.answer("feed", TCCSQuery(u, ts, te, 2))
                assert res is not None
                answered += 1
                if answered >= 256:
                    break
            refresh_fut.result(timeout=60)
            assert answered >= 64

    def test_post_refresh_queries_reach_new_range(self):
        from repro.core.kcore import tccs_oracle
        g = self._graph(35)
        g0, suffix = split_epoch(g, 0.6)
        with ServingEngine(EngineConfig(flush_ms=0.5)) as eng:
            eng.register_graph("feed", g0)
            eng.registry.get("feed")
            eng.ingest("feed", suffix, wait=True)
            rng = np.random.default_rng(3)
            for _ in range(20):
                u = int(rng.integers(0, g.n))
                ts = int(rng.integers(1, g.t_max + 1))
                te = int(rng.integers(ts, g.t_max + 1))
                res = eng.answer("feed", TCCSQuery(u, ts, te, 2))
                assert res.vertices == frozenset(
                    tccs_oracle(g, 2, u, ts, te)), (u, ts, te)

    def test_chained_nonblocking_ingests_land_the_last_epoch(self):
        """Two ingests issued back-to-back without waiting: both refreshes
        may grow from the same epoch-0 handle, and the second must still
        swap in (the registry serving epoch 1 forever was a real bug)."""
        g = self._graph(38)
        gA, _ = g.split_at(int(g.t_max * 0.5))
        gB, _ = g.split_at(int(g.t_max * 0.75))
        day1 = [tuple(e) for e in np.stack(
            [gB.src[gA.m:], gB.dst[gA.m:], gB.t[gA.m:]], axis=1).tolist()]
        day2 = [tuple(e) for e in np.stack(
            [g.src[gB.m:], g.dst[gB.m:], g.t[gB.m:]], axis=1).tolist()]
        with ServingEngine(EngineConfig(flush_ms=0.5)) as eng:
            eng.register_graph("feed", gA)
            eng.registry.get("feed")
            f1 = eng.ingest("feed", day1)
            f2 = eng.ingest("feed", day2)
            for f in list(f1.values()) + list(f2.values()):
                f.result(timeout=120)
            h = eng.registry.get_nowait("feed", start_build=False)
            assert h is not None and h.epoch == 2
            assert h.graph.t_max == g.t_max
            assert_pecb_identical(h.pecb, build_stratified_index(g))

    def test_cold_build_racing_ingest_catches_up(self):
        """An ingest that lands while a cold build is in flight finds no
        resident entry to refresh; the build's completion must notice the
        newer graph epoch and catch the stored handle up, or queries would
        serve pre-ingest data indefinitely."""
        import threading
        from repro.serving import IndexRegistry
        g = self._graph(37)
        g0, suffix = split_epoch(g, 0.6)
        reg = IndexRegistry()
        reg.register_graph("feed", g0)
        built = threading.Event()
        proceed = threading.Event()
        orig = reg._build

        def stalling_build(key):
            h = orig(key)
            built.set()
            assert proceed.wait(30)
            return h

        reg._build = stalling_build
        try:
            fut = reg.get_async("feed")
            assert built.wait(30)
            assert reg.extend_graph("feed", suffix) == {}  # nothing resident
            proceed.set()
            stale = fut.result(timeout=60)
            assert stale.graph.t_max == g0.t_max          # built pre-ingest
            deadline = time.perf_counter() + 60
            while time.perf_counter() < deadline:
                h = reg.get_nowait("feed", start_build=False)
                if h is not None and h.graph.t_max == g.t_max:
                    break
                time.sleep(0.01)
            h = reg.get_nowait("feed", start_build=False)
            assert h is not None and h.graph.t_max == g.t_max
            assert h.epoch == 1
            assert_pecb_identical(h.pecb, build_stratified_index(g))
        finally:
            reg.close()

    def test_sweep_after_ingest(self):
        g = self._graph(36)
        g0, suffix = split_epoch(g, 0.6)
        with ServingEngine(EngineConfig(flush_ms=0.5)) as eng:
            eng.register_graph("feed", g0)
            eng.registry.get("feed")
            eng.ingest("feed", suffix, wait=True)
            windows = [(d, d + 4) for d in range(1, g.t_max - 3)]
            res = eng.sweep("feed", WindowSweep(u=1, k=2, windows=windows))
            h = eng.registry.get("feed")
            for r, (ts, te) in zip(res, windows):
                assert r.vertices == h.pecb.answer(
                    TCCSQuery(1, ts, te, 2)).vertices


# ----------------------------------------------------------------------
# satellite regressions
# ----------------------------------------------------------------------

class TestBatcherFlushFlag:
    def test_empty_flush_does_not_leak_into_next_batch(self):
        """A flush() with nothing pending must not force-flush the next
        unrelated batch (or miscount it as flush_forced)."""
        metrics = EngineMetrics()
        b = MicroBatcher(lambda reqs: [None] * len(reqs),
                         max_batch=64, flush_ms=40.0, metrics=metrics)
        try:
            b.flush()                      # nothing pending: must be a no-op
            time.sleep(0.05)               # give the worker a chance to spin
            t0 = time.perf_counter()
            fut = b.submit(Request(0, 1, 1, Future(), t_submit=t0))
            fut.result(timeout=5)
            waited = time.perf_counter() - t0
            snap = metrics.snapshot()["counters"]
            assert snap.get("flush_forced", 0) == 0
            assert waited >= 0.03          # dispatched by deadline, not force
        finally:
            b.close()

    def test_flush_with_pending_still_forces(self):
        metrics = EngineMetrics()
        b = MicroBatcher(lambda reqs: [None] * len(reqs),
                         max_batch=64, flush_ms=60.0, metrics=metrics)
        try:
            t0 = time.perf_counter()
            fut = b.submit(Request(0, 1, 1, Future(), t_submit=t0))
            b.flush()
            fut.result(timeout=5)
            assert time.perf_counter() - t0 < 0.5
            assert metrics.snapshot()["counters"].get("flush_forced", 0) == 1
        finally:
            b.close()


class TestCacheHitRestamp:
    def test_cache_hit_is_a_copy_not_shared_state(self):
        g = gen_temporal_graph(n=25, m=200, t_max=10, seed=41)
        with ServingEngine(EngineConfig(flush_ms=0.5)) as eng:
            eng.register_graph("g", g)
            eng.registry.get("g")      # resident, no XLA warmup needed
            q = TCCSQuery(1, 1, g.t_max, 2)
            first = eng.answer("g", q)
            hit1 = eng.answer("g", q)
            hit2 = eng.answer("g", q)
            assert hit1.provenance.route == "cache"
            assert hit1 is not hit2
            assert hit1.provenance is not hit2.provenance
            assert hit1.provenance.timings is not hit2.provenance.timings
            # mutating a caller's copy must not corrupt the stored result
            hit1.provenance.timings["poison"] = 1.0
            hit3 = eng.answer("g", q)
            assert "poison" not in hit3.provenance.timings
            assert first.provenance.route != "cache"  # original unchanged


class TestEmptyGraphWindows:
    def test_canonical_folds_t_max_zero(self):
        q = TCCSQuery(0, 5, 9, 2).canonical(0)
        assert (q.ts, q.te) == EMPTY_WINDOW
        assert q.validate() is q            # the marker is valid, not [1,0]
        assert TCCSQuery(0, 1, 3, 2).canonical(0).is_empty_window

    def test_random_queries_on_empty_graph(self):
        g = TemporalGraph.from_edges(4, [])
        qs = random_queries(g, 8, seed=0)
        assert all(ts > te for (_, ts, te) in qs)

    def test_engine_serves_empty_graph(self):
        g = TemporalGraph.from_edges(4, [])
        with ServingEngine() as eng:
            eng.register_graph("empty", g)
            res = eng.answer("empty", TCCSQuery(2, 1, 5, 2))
            assert res.vertices == frozenset()
            assert res.provenance.route == "trivial"
            sub = eng.answer("empty",
                             TCCSQuery(2, 1, 5, 2, ResultMode.SUBGRAPH))
            assert sub.subgraph.m == 0


class TestDeprecationWarnings:
    def _stack(self):
        g = gen_temporal_graph(n=20, m=140, t_max=8, seed=51)
        tab = edge_core_times(g, 2)
        return g, (build_pecb_index(g, 2, tab), EFIndex(g, 2, tab),
                   CTMSFIndex(g, 2, tab))

    def test_backend_query_shims_warn(self):
        _, backends = self._stack()
        for b in backends:
            with pytest.warns(DeprecationWarning, match="deprecated"):
                b.query(0, 1, 5)

    def test_engine_shims_warn_and_match_v2(self):
        g, _ = self._stack()
        with ServingEngine(EngineConfig(flush_ms=0.5)) as eng:
            eng.register_graph("g", g)
            want = eng.answer("g", TCCSQuery(1, 1, g.t_max, 2)).vertices
            with pytest.warns(DeprecationWarning, match="submit_spec"):
                fut = eng.submit("g", 2, 1, 1, g.t_max)
            assert fut.result(timeout=30) == want
            with pytest.warns(DeprecationWarning, match="submit_specs"):
                futs = eng.submit_many("g", 2, [(1, 1, g.t_max)])
            assert futs[0].result(timeout=30) == want
            with pytest.warns(DeprecationWarning, match="answer"):
                got = eng.query("g", 2, 1, 1, g.t_max)
            assert got == want
