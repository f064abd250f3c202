"""Query API v2 tests: spec validation/canonicalization, the TCCSBackend
protocol across all three indexes, EDGES/SUBGRAPH/COUNT exactness on host
and device routes (vs the brute-force oracle), window sweeps, canonical
cache keys, and result-cache purging on index eviction."""

import numpy as np
import pytest

from repro.core.batch_query import (batch_query, batch_query_edges_np,
                                    batch_query_np, to_device, window_sweep)
from repro.core.core_time import edge_core_times
from repro.core.ctmsf_index import CTMSFIndex
from repro.core.ef_index import EFIndex
from repro.core.kcore import tccs_oracle, tccs_oracle_edges
from repro.core.pecb_index import build_pecb_index
from repro.core.query_api import (EMPTY_WINDOW, InvalidQueryError, ResultMode,
                                  TCCSBackend, TCCSQuery, WindowSweep)
from repro.core.temporal_graph import gen_temporal_graph
from repro.serving import EngineConfig, ServingEngine


@pytest.fixture(scope="module")
def stack():
    g = gen_temporal_graph(n=35, m=280, t_max=16, seed=8)
    k = 2
    tab = edge_core_times(g, k)
    return (g, k, build_pecb_index(g, k, tab), EFIndex(g, k, tab),
            CTMSFIndex(g, k, tab))


def random_windows(g, n_q, rng, beyond=False):
    out = []
    for _ in range(n_q):
        u = int(rng.integers(0, g.n))
        ts = int(rng.integers(1, g.t_max + 1))
        hi = 2 * g.t_max if beyond else g.t_max
        te = int(rng.integers(ts, hi + 1))
        out.append((u, ts, te))
    return out


class TestSpec:
    def test_validation_errors(self, stack):
        g, k, pecb, *_ = stack
        with pytest.raises(InvalidQueryError, match="ts > te"):
            TCCSQuery(0, 5, 3, k).validate()
        with pytest.raises(InvalidQueryError, match="k must be"):
            TCCSQuery(0, 1, 5, 1).validate()
        with pytest.raises(InvalidQueryError, match="out of range"):
            TCCSQuery(g.n, 1, 5, k).validate(n=g.n)
        with pytest.raises(InvalidQueryError, match="out of range"):
            TCCSQuery(-1, 1, 5, k).validate(n=g.n)
        # a valid spec validates through, including the canonical empty
        TCCSQuery(0, 1, 5, k).validate(n=g.n)
        TCCSQuery(0, *EMPTY_WINDOW, k).validate(n=g.n)

    def test_backend_answer_raises_not_empty(self, stack):
        """The satellite contract: malformed queries raise a dedicated
        error instead of silently answering the empty set."""
        g, k, pecb, ef, cm = stack
        for backend in (pecb, ef, cm):
            with pytest.raises(InvalidQueryError):
                backend.answer(TCCSQuery(0, 9, 4, k))
            with pytest.raises(InvalidQueryError):
                backend.answer(TCCSQuery(g.n + 7, 1, 4, k))
            with pytest.raises(InvalidQueryError):
                backend.answer(TCCSQuery(0, 1, 4, 1))
            with pytest.raises(InvalidQueryError, match="does not match"):
                backend.answer(TCCSQuery(0, 1, 4, k + 1))

    def test_canonicalization(self, stack):
        g, k, *_ = stack
        t_max = g.t_max
        # clamp beyond-range te; fold empty windows; idempotence
        assert (TCCSQuery(3, 2, 10 * t_max, k).canonical(t_max)
                == TCCSQuery(3, 2, t_max, k))
        assert TCCSQuery(3, -4, 5, k).canonical(t_max) == TCCSQuery(3, 1, 5, k)
        folded = TCCSQuery(3, t_max + 2, t_max + 9, k).canonical(t_max)
        assert (folded.ts, folded.te) == EMPTY_WINDOW
        c = TCCSQuery(3, 2, 9, k).canonical(t_max)
        assert c.canonical(t_max) is c
        # equivalent raw windows share one cache key
        a = TCCSQuery(3, 2, t_max + 5, k).canonical(t_max).cache_key()
        b = TCCSQuery(3, 2, t_max, k).canonical(t_max).cache_key()
        assert a == b
        # mode is part of the key (an EDGES result is not a VERTICES result)
        e = TCCSQuery(3, 2, t_max, k, ResultMode.EDGES).canonical(t_max)
        assert e.cache_key() != b


class TestBackendProtocol:
    def test_all_three_implement_protocol(self, stack):
        _, _, pecb, ef, cm = stack
        for backend in (pecb, ef, cm):
            assert isinstance(backend, TCCSBackend)

    def test_all_modes_match_oracle_on_all_backends(self, stack):
        g, k, pecb, ef, cm = stack
        rng = np.random.default_rng(0)
        for (u, ts, te) in random_windows(g, 25, rng, beyond=True):
            want_v = frozenset(tccs_oracle(g, k, u, ts, te))
            want_e = frozenset(tccs_oracle_edges(g, k, u, ts, te))
            for backend in (pecb, ef, cm):
                r = backend.answer(TCCSQuery(u, ts, te, k, ResultMode.EDGES))
                assert r.vertices == want_v, (backend.backend_name, u, ts, te)
                assert r.edges.edge_ids() == want_e, (backend.backend_name,)
                assert r.edges.vertex_projection() == want_v
                assert r.num_edges == len(want_e)
                rs = backend.answer(TCCSQuery(u, ts, te, k,
                                              ResultMode.SUBGRAPH))
                assert rs.subgraph.m == len(want_e)
                assert rs.edges.edge_ids() == want_e
                rc = backend.answer(TCCSQuery(u, ts, te, k, ResultMode.COUNT))
                assert rc.num_vertices == len(want_v)
                assert rc.vertices == frozenset()

    def test_legacy_shims_agree_with_v2(self, stack):
        g, k, pecb, ef, cm = stack
        rng = np.random.default_rng(1)
        for (u, ts, te) in random_windows(g, 10, rng):
            for backend in (pecb, ef, cm):
                with pytest.warns(DeprecationWarning, match="deprecated"):
                    legacy = backend.query(u, ts, te)
                assert legacy == set(
                    backend.answer(TCCSQuery(u, ts, te, k)).vertices)


class TestDeviceModes:
    def test_device_edge_membership_matches_oracle(self, stack):
        """The tentpole device derivation: version membership from the
        converged component labels equals the brute-force induced edges."""
        g, k, pecb, *_ = stack
        rng = np.random.default_rng(2)
        qs = random_windows(g, 40, rng, beyond=True)
        got_e = batch_query_edges_np(pecb, qs)
        got_v = batch_query_np(pecb, qs)
        for (u, ts, te), ev, vv in zip(qs, got_e, got_v):
            assert ev == tccs_oracle_edges(g, k, u, ts, te), (u, ts, te)
            assert vv == tccs_oracle(g, k, u, ts, te), (u, ts, te)

    def test_per_k_mirror_window_is_its_whole_forest(self, stack):
        """The control of the stratum window: a per-k mirror is one
        stratum, so its node-to-lane buffer spans all of its nodes, while
        each row propagates over its live list alone."""
        import jax
        import jax.numpy as jnp
        from test_stratified import _jaxpr_shapes
        g, k, pecb, *_ = stack
        dix = to_device(pecb)
        N = pecb.num_nodes
        M = dix.max_live_nodes
        assert dix.max_stratum_nodes == dix.num_nodes == N
        assert np.asarray(dix.knode_ptr).tolist() == [0, N]
        assert M < g.n < N
        q = jnp.zeros((8,), jnp.int32)
        shapes = _jaxpr_shapes(jax.make_jaxpr(batch_query)(dix, q, q, q).jaxpr)
        assert (8, N) in shapes and (8, M) in shapes

    @pytest.mark.parametrize("program", ["batch_query", "batch_query_full",
                                         "window_sweep"])
    def test_per_k_mirror_edge_times_match_algorithm_1(self, stack,
                                                       program):
        """A per-k mirror's programs at the edges of time — before the
        first day, on it, on the last, past it — and on the start time
        of its longest live list answer as Algorithm 1 (vertices) and the
        induced-edge oracle (edges)."""
        import jax.numpy as jnp
        from repro.core.batch_query import batch_query_full
        g, k, pecb, *_ = stack
        dix = to_device(pecb)
        counts = np.diff(np.asarray(dix.live_ptr))
        t_m = int(np.argmax(counts))
        assert counts[t_m] == dix.max_live_nodes
        assert counts[0] == counts[g.t_max + 1] == 0
        wins = [(-1, 4), (0, g.t_max), (1, 1), (1, g.t_max),
                (g.t_max, g.t_max), (g.t_max + 1, g.t_max + 1),
                (t_m, t_m), (t_m, g.t_max)]
        ts = jnp.asarray([a for a, _ in wins], jnp.int32)
        te = jnp.asarray([b for _, b in wins], jnp.int32)
        for u in range(0, g.n, 5):
            if program == "window_sweep":
                out = window_sweep(dix, jnp.int32(u), ts, te)
            else:
                fn = batch_query if program == "batch_query" \
                    else batch_query_full
                out = fn(dix, jnp.full(len(wins), u, jnp.int32), ts, te)
            vmask = np.asarray(out[0])
            for i, (a, b) in enumerate(wins):
                assert set(np.flatnonzero(vmask[i]).tolist()) == \
                    pecb._component_vertices(u, a, b) == \
                    tccs_oracle(g, k, u, a, b), (u, a, b)
                if program == "batch_query_full":
                    row = np.asarray(out[1])[i][:dix.num_versions]
                    got = set(pecb.versions.edge_id[np.flatnonzero(row)]
                              .tolist())
                    assert got == tccs_oracle_edges(g, k, u, a, b), (u, a, b)

    def test_engine_device_route_edge_modes(self, stack):
        g, k, *_ = stack
        rng = np.random.default_rng(3)
        cfg = EngineConfig(max_batch=64, flush_ms=500.0, host_threshold=0,
                           cache_capacity=0)
        with ServingEngine(cfg) as eng:
            eng.register_graph("g", g)
            qs = random_windows(g, 24, rng)
            specs = [TCCSQuery(u, ts, te, k, ResultMode.SUBGRAPH)
                     for (u, ts, te) in qs]
            futs = eng.submit_specs("g", specs)
            eng.flush()
            got = [f.result(timeout=60) for f in futs]
            assert eng.metrics.counter("device_batches") > 0
        for (u, ts, te), r in zip(qs, got):
            assert r.provenance.route == "device"
            assert r.vertices == frozenset(tccs_oracle(g, k, u, ts, te))
            want_e = frozenset(tccs_oracle_edges(g, k, u, ts, te))
            assert r.edges.edge_ids() == want_e
            assert r.subgraph.m == len(want_e)
            # the induced snapshot's edges are the member edges verbatim
            assert (frozenset(zip(r.subgraph.src.tolist(),
                                  r.subgraph.dst.tolist(),
                                  r.subgraph.t.tolist()))
                    == frozenset(zip(r.edges.u.tolist(), r.edges.v.tolist(),
                                     r.edges.t.tolist())))


class TestEngineV2:
    def test_submit_spec_validates_at_boundary(self, stack):
        g, k, *_ = stack
        with ServingEngine(EngineConfig(flush_ms=100.0)) as eng:
            eng.register_graph("g", g)
            with pytest.raises(InvalidQueryError):
                eng.submit_spec("g", TCCSQuery(0, 9, 3, k))
            with pytest.raises(InvalidQueryError):
                eng.submit_spec("g", TCCSQuery(g.n + 1, 1, 3, k))
            with pytest.raises(InvalidQueryError):
                eng.sweep("g", WindowSweep(g.n + 1, k, [(1, 3)]))

    def test_mixed_k_validation_is_all_or_nothing(self, stack):
        """A malformed spec in a later k-group must not leave earlier
        groups already enqueued: nothing executes when any spec fails."""
        g, k, *_ = stack
        with ServingEngine(EngineConfig(flush_ms=100.0)) as eng:
            eng.register_graph("g", g)
            with pytest.raises(InvalidQueryError):
                eng.submit_specs("g", [TCCSQuery(0, 1, 5, 2),
                                       TCCSQuery(0, 9, 3, 3)])
            assert eng.metrics.counter("queries") == 0

    def test_canonical_windows_share_cache_entry(self, stack):
        g, k, *_ = stack
        with ServingEngine(EngineConfig(flush_ms=200.0, host_threshold=0,
                                        cache_capacity=64)) as eng:
            eng.register_graph("g", g)
            r1 = eng.answer("g", TCCSQuery(2, 3, g.t_max, k))
            assert eng.metrics.counter("cache_hits") == 0
            # equivalent (beyond-t_max) window: canonical key -> cache hit
            r2 = eng.answer("g", TCCSQuery(2, 3, 5 * g.t_max, k))
            assert eng.metrics.counter("cache_hits") == 1
            assert r2.provenance.route == "cache"
            assert r1.vertices == r2.vertices

    def test_empty_window_short_circuits(self, stack):
        g, k, *_ = stack
        with ServingEngine(EngineConfig(flush_ms=200.0)) as eng:
            eng.register_graph("g", g)
            fut = eng.submit_spec("g", TCCSQuery(0, g.t_max + 4,
                                                 g.t_max + 9, k))
            assert fut.done()               # resolved on the submit path
            res = fut.result()
            assert res.vertices == frozenset()
            assert res.provenance.route == "trivial"
            assert eng.metrics.counter("trivial_queries") == 1

    def test_mixed_k_and_modes_in_one_call(self, stack):
        g, _, *_ = stack
        rng = np.random.default_rng(5)
        with ServingEngine(EngineConfig(max_batch=64, flush_ms=300.0,
                                        host_threshold=0)) as eng:
            eng.register_graph("g", g)
            specs = []
            for (u, ts, te) in random_windows(g, 16, rng):
                k = int(rng.choice([2, 3]))
                mode = (ResultMode.EDGES if rng.random() < 0.5
                        else ResultMode.VERTICES)
                specs.append(TCCSQuery(u, ts, te, k, mode))
            futs = eng.submit_specs("g", specs)
            eng.flush()
            got = [f.result(timeout=60) for f in futs]
        for s, r in zip(specs, got):
            assert r.query.k == s.k and r.query.mode is s.mode
            assert r.vertices == frozenset(tccs_oracle(g, s.k, s.u, s.ts, s.te))
            if s.mode is ResultMode.EDGES:
                assert (r.edges.edge_ids()
                        == frozenset(tccs_oracle_edges(g, s.k, s.u, s.ts, s.te)))


class TestWindowSweep:
    def test_sweep_matches_per_window_and_fills_cache(self, stack):
        g, k, pecb, *_ = stack
        u = 4
        windows = [(d, min(d + 4, g.t_max)) for d in range(1, g.t_max + 1)]
        cfg = EngineConfig(max_batch=64, flush_ms=300.0, host_threshold=4,
                           cache_capacity=256)
        with ServingEngine(cfg) as eng:
            eng.register_graph("g", g)
            got = eng.sweep("g", WindowSweep(u, k, windows))
            assert eng.metrics.counter("sweep_launches") >= 1
            for (ts, te), r in zip(windows, got):
                assert r.vertices == frozenset(
                    pecb._component_vertices(u, ts, te)), (ts, te)
                assert r.provenance.route == "sweep"
            # the sweep filled the cache: a re-sweep is all hits
            misses0 = eng.metrics.counter("cache_misses")
            again = eng.sweep("g", WindowSweep(u, k, windows))
            assert eng.metrics.counter("cache_misses") == misses0
            assert all(r.provenance.route == "cache" for r in again)
            # ...and point queries for the same windows hit too
            res = eng.answer("g", TCCSQuery(u, *windows[0], k))
            assert res.provenance.route == "cache"

    def test_sweep_edges_mode(self, stack):
        g, k, *_ = stack
        u = 7
        windows = [(d, min(d + 5, g.t_max)) for d in range(1, g.t_max, 2)]
        with ServingEngine(EngineConfig(flush_ms=300.0,
                                        host_threshold=4)) as eng:
            eng.register_graph("g", g)
            got = eng.sweep("g", WindowSweep(u, k, windows,
                                             ResultMode.EDGES))
        for (ts, te), r in zip(windows, got):
            assert (r.edges.edge_ids()
                    == frozenset(tccs_oracle_edges(g, k, u, ts, te)))

    def test_sweep_beyond_range_windows_fold(self, stack):
        g, k, *_ = stack
        windows = [(1, 4), (g.t_max + 2, g.t_max + 6)]
        with ServingEngine(EngineConfig(flush_ms=300.0)) as eng:
            eng.register_graph("g", g)
            got = eng.sweep("g", WindowSweep(3, k, windows))
            assert got[1].vertices == frozenset()
            assert got[1].provenance.route == "trivial"

    def test_device_sweep_function_matches_alg1(self, stack):
        g, k, pecb, *_ = stack
        import jax.numpy as jnp
        dix = to_device(pecb)
        u = 11
        wins = [(d, min(d + 3, g.t_max)) for d in range(1, g.t_max + 1)]
        ts = jnp.asarray([w[0] for w in wins], jnp.int32)
        te = jnp.asarray([w[1] for w in wins], jnp.int32)
        mask = np.asarray(window_sweep(dix, jnp.int32(u), ts, te)[0])
        for (a, b), row in zip(wins, mask):
            assert set(np.nonzero(row)[0].tolist()) == \
                pecb._component_vertices(u, a, b)


class TestCachePurge:
    def test_eviction_purges_result_cache(self):
        """Satellite: stale cache keys of an evicted (workload, k) index
        must not occupy LRU capacity forever."""
        g1 = gen_temporal_graph(n=20, m=110, t_max=8, seed=1)
        g2 = gen_temporal_graph(n=20, m=110, t_max=8, seed=2)
        cfg = EngineConfig(flush_ms=150.0, registry_capacity=1,
                           cache_capacity=64)
        with ServingEngine(cfg) as eng:
            eng.register_graph("g1", g1)
            eng.register_graph("g2", g2)
            eng.answer("g1", TCCSQuery(0, 1, 6, 2))
            eng.answer("g1", TCCSQuery(1, 1, 6, 2))
            assert len(eng.cache) == 2
            eng.answer("g2", TCCSQuery(0, 1, 6, 2))  # evicts ("g1", 2)
            assert eng.registry.evictions == 1
            # the dead handle's entries are gone; only g2's remains
            assert len(eng.cache) == 1
            assert eng.cache.stats()["purges"] == 2
            assert eng.metrics.counter("cache_purged") == 2


class TestLegacyEngineShims:
    def test_positional_submit_is_lenient_and_exact(self, stack):
        g, k, pecb, *_ = stack
        with ServingEngine(EngineConfig(flush_ms=200.0)) as eng:
            eng.register_graph("g", g)
            # malformed windows answer empty, pre-v2 style (no raise)
            with pytest.warns(DeprecationWarning, match="deprecated"):
                assert eng.query("g", k, 0, 9, 3) == frozenset()
            with pytest.warns(DeprecationWarning, match="deprecated"):
                got = eng.query("g", k, 5, 2, 9)
            assert got == frozenset(pecb._component_vertices(5, 2, 9))
            with pytest.warns(DeprecationWarning, match="deprecated"):
                futs = eng.submit_many("g", k, [(1, 1, 8), (2, 3, 7)])
            eng.flush()
            for (u, ts, te), f in zip([(1, 1, 8), (2, 3, 7)], futs):
                assert f.result(timeout=30) == frozenset(
                    pecb._component_vertices(u, ts, te))
