"""End-to-end behaviour tests for the paper's system (PECB + baselines)."""

import numpy as np
import pytest

from repro.core.temporal_graph import TemporalGraph, gen_temporal_graph
from repro.core.kcore import tccs_oracle, k_max, temporal_kcore_edges
from repro.core.core_time import edge_core_times, edge_core_time_naive
from repro.core.ctmsf import kruskal_msf, boruvka_msf_np
from repro.core.ecb_forest import active_versions, build_forest_at, IncrementalBuilder
from repro.core.pecb_index import build_pecb_index
from repro.core.ctmsf_index import CTMSFIndex
from repro.core.ef_index import EFIndex
from repro.core.batch_query import batch_query_np


def paper_graph() -> TemporalGraph:
    """Figure 1 of the paper (v1..v8 -> ids 0..7)."""
    return TemporalGraph.from_edges(8, [
        (0, 1, 4), (0, 2, 4), (1, 2, 4),
        (2, 7, 2), (3, 4, 3),
        (5, 6, 4), (5, 7, 5), (6, 7, 5),
        (1, 3, 6), (1, 4, 6), (4, 5, 7),
    ])


class TestPaperExamples:
    def test_example_2_3_two_components(self):
        g = paper_graph()
        ids = temporal_kcore_edges(g, 2, 4, 5)
        verts = set(g.src[ids]) | set(g.dst[ids])
        assert verts == {0, 1, 2, 5, 6, 7}          # v1,v2,v3 + v6,v7,v8
        assert tccs_oracle(g, 2, 1, 4, 5) == {0, 1, 2}
        assert tccs_oracle(g, 2, 6, 4, 5) == {5, 6, 7}

    def test_example_4_4_core_times(self):
        g = paper_graph()
        tab = edge_core_times(g, 2)
        # CT((v1,v2,4))_{ts=4} = 4 ; CT((v6,v7,4))_{ts=4} = 5
        e1 = int(np.nonzero((g.src == 0) & (g.dst == 1) & (g.t == 4))[0][0])
        e2 = int(np.nonzero((g.src == 5) & (g.dst == 6) & (g.t == 4))[0][0])
        assert tab.ct_at(e1, 4) == 4
        assert tab.ct_at(e2, 4) == 5

    def test_table_1_incremental_core_times(self):
        g = paper_graph()
        tab = edge_core_times(g, 2)
        INF = tab.INF
        # (v2,v5,6): <1,6>, <4,7>, <5,inf>
        e = int(np.nonzero((g.src == 1) & (g.dst == 4) & (g.t == 6))[0][0])
        for ts, want in [(1, 6), (2, 6), (3, 6), (4, 7), (5, INF), (6, INF)]:
            assert tab.ct_at(e, ts) == want, (ts, tab.ct_at(e, ts), want)
        # (v3,v8,2): <1,5>, <3,inf>
        e = int(np.nonzero((g.src == 2) & (g.dst == 7))[0][0])
        for ts, want in [(1, 5), (2, 5), (3, INF)]:
            assert tab.ct_at(e, ts) == want

    def test_example_4_14_query(self):
        g = paper_graph()
        idx = build_pecb_index(g, 2)
        assert idx._component_vertices(1, 3, 5) == {0, 1, 2}  # v2, [3,5] -> {v1,v2,v3}


class TestCoreTimes:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    @pytest.mark.parametrize("k", [2, 3])
    def test_vs_naive(self, seed, k):
        g = gen_temporal_graph(n=25, m=120, t_max=12, seed=seed)
        tab = edge_core_times(g, k)
        for ts in range(1, g.t_max + 1):
            naive = edge_core_time_naive(g, k, ts)
            for e in range(g.m):
                assert tab.ct_at(e, ts) == naive[e], (ts, e)

    def test_monotone_in_ts(self):
        g = gen_temporal_graph(n=40, m=300, t_max=20, seed=3)
        tab = edge_core_times(g, 2)
        for e in range(g.m):
            prev = -1
            for ts in range(1, g.t_max + 1):
                ct = tab.ct_at(e, ts)
                assert ct >= prev
                prev = ct


class TestMSF:
    @pytest.mark.parametrize("seed", [0, 1])
    def test_boruvka_equals_kruskal(self, seed):
        g = gen_temporal_graph(n=40, m=300, t_max=25, seed=seed)
        tab = edge_core_times(g, 2)
        for ts in range(1, g.t_max + 1, 4):
            e_ids, cts = active_versions(tab, ts)
            if e_ids.size == 0:
                continue
            u = g.src[e_ids].astype(np.int64)
            v = g.dst[e_ids].astype(np.int64)
            km = kruskal_msf(u, v, cts.astype(np.int64), g.n)
            bm = boruvka_msf_np(u.astype(np.int32), v.astype(np.int32),
                                cts.astype(np.int32), g.n)
            assert np.array_equal(km, bm)


class TestECBForest:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_binary_bound_and_rank_order(self, seed):
        g = gen_temporal_graph(n=30, m=200, t_max=15, seed=seed)
        tab = edge_core_times(g, 2)
        for ts in range(1, g.t_max + 1, 3):
            f = build_forest_at(g, tab, ts)
            nn = f.ct.shape[0]
            child_count = np.zeros(nn, int)
            for i in range(nn):
                if not f.in_forest[i]:
                    continue
                for c in (f.left[i], f.right[i]):
                    if c >= 0:
                        child_count[i] += 1
                        # child ranks strictly below the parent
                        assert (f.ct[c], f.edge_id[c]) < (f.ct[i], f.edge_id[i])
            assert (child_count <= 2).all()

    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_incremental_equals_from_scratch(self, seed):
        """The builder's live node set at each ts equals the Def-4.9
        from-scratch construction's forest node set."""
        g = gen_temporal_graph(n=25, m=150, t_max=12, seed=seed)
        tab = edge_core_times(g, 2)
        idx = build_pecb_index(g, 2, tab)
        for ts in range(1, g.t_max + 1):
            f = build_forest_at(g, tab, ts)
            scratch = {(int(f.edge_id[i]), int(f.ct[i]))
                       for i in range(f.ct.shape[0]) if f.in_forest[i]}
            inc = {(int(idx.node_edge[x]), int(idx.node_ct[x]))
                   for x in range(idx.num_nodes)
                   if idx.node_live_from[x] <= ts <= idx.node_live_to[x]}
            assert scratch == inc, ts


class TestQueries:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    @pytest.mark.parametrize("k", [2, 3])
    def test_all_indexes_match_oracle(self, seed, k):
        rng = np.random.default_rng(seed)
        g = gen_temporal_graph(n=30, m=220, t_max=18, seed=seed + 40)
        tab = edge_core_times(g, k)
        pecb = build_pecb_index(g, k, tab)
        ef = EFIndex(g, k, tab)
        cm = CTMSFIndex(g, k, tab)
        for _ in range(120):
            u = int(rng.integers(0, g.n))
            ts = int(rng.integers(1, g.t_max + 1))
            te = int(rng.integers(ts, g.t_max + 1))
            want = tccs_oracle(g, k, u, ts, te)
            assert pecb._component_vertices(u, ts, te) == want
            assert ef._component_vertices(u, ts, te) == want
            assert cm._component_vertices(u, ts, te) == want

    def test_batched_engine_matches_host(self):
        rng = np.random.default_rng(11)
        g = gen_temporal_graph(n=35, m=260, t_max=16, seed=77)
        idx = build_pecb_index(g, 2)
        qs = [(int(rng.integers(0, g.n)), *sorted(int(x) for x in rng.integers(1, g.t_max + 1, 2)))
              for _ in range(96)]
        got = batch_query_np(idx, qs)
        for (u, ts, te), res in zip(qs, got):
            assert res == idx._component_vertices(u, ts, te)

    def test_kmax_positive(self):
        g = gen_temporal_graph(n=60, m=600, t_max=30, seed=5)
        assert k_max(g) >= 2


class TestConstructionEngines:
    """Seeded (non-hypothesis) engine-equivalence coverage, so the batched
    plane is exercised even where hypothesis is not installed."""

    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_three_engines_bit_identical(self, seed):
        g = gen_temporal_graph(n=30, m=180, t_max=14, seed=seed)
        for k in (2, 3):
            legacy = edge_core_times(g, k, engine="legacy")
            host = edge_core_times(g, k, engine="host")
            jaxed = edge_core_times(g, k, engine="jax")
            for f in ("edge_id", "ts_from", "ts_to", "ct", "vertex_ct"):
                assert np.array_equal(getattr(legacy, f), getattr(host, f)), f
                assert np.array_equal(getattr(legacy, f), getattr(jaxed, f)), f

    def test_jax_pallas_engine_matches_host(self):
        g = gen_temporal_graph(n=14, m=60, t_max=6, seed=7)
        host = edge_core_times(g, 2, engine="host")
        pallas = edge_core_times(g, 2, engine="jax_pallas")
        for f in ("edge_id", "ts_from", "ts_to", "ct", "vertex_ct"):
            assert np.array_equal(getattr(host, f), getattr(pallas, f)), f

    def test_auto_is_the_host_sweep_on_a_tpu_backend(self, monkeypatch):
        """On a TPU-reporting backend the default build runs the host sweep:
        no jitted sweep is dispatched or compiled, and the table is the
        jitted engine's, bit for bit."""
        import dataclasses
        import jax
        from repro.core import core_time

        g = gen_temporal_graph(n=30, m=180, t_max=14, seed=4)
        ks = (2, 3)
        want = core_time.stratified_core_times(g, ks, engine="jax")
        compiled = core_time._sweep_block._cache_size()
        timings = {}
        with monkeypatch.context() as m:
            m.setattr(jax, "default_backend", lambda: "tpu")
            assert core_time.resolve_engine("auto") == "host"
            got = core_time.stratified_core_times(g, ks, timings=timings)
        assert timings["core_times.dispatch"] == 0
        assert core_time._sweep_block._cache_size() == compiled
        for f in dataclasses.fields(want):
            assert np.array_equal(getattr(got, f.name),
                                  getattr(want, f.name)), f.name

    def test_self_loops_do_not_corrupt_builder(self):
        """Directly-constructed graphs may carry self-loops (from_edges
        drops them); the builder must treat them as degenerate on both
        prefilter paths instead of corrupting the forest."""
        import dataclasses
        from repro.core.ecb_forest import IncrementalBuilder
        from repro.core.pecb_index import pack_index

        base = gen_temporal_graph(n=12, m=60, t_max=6, seed=3)
        g = TemporalGraph(
            base.n,
            np.concatenate([base.src, np.int32([1, 4])]),
            np.concatenate([base.dst, np.int32([1, 4])]),
            np.concatenate([base.t, np.int32([2, 5])]),
        )
        tab = edge_core_times(g, 2)
        a = pack_index(g, 2, IncrementalBuilder(g, tab, prefilter=True).run())
        b = pack_index(g, 2, IncrementalBuilder(g, tab, prefilter=False).run())
        for f in dataclasses.fields(a):
            va, vb = getattr(a, f.name), getattr(b, f.name)
            same = (np.array_equal(va, vb) if isinstance(va, np.ndarray)
                    else va == vb)
            assert same, f.name

    def test_unknown_engine_raises(self):
        g = gen_temporal_graph(n=10, m=30, t_max=5, seed=0)
        with pytest.raises(ValueError, match="engine"):
            edge_core_times(g, 2, engine="warp")

    def test_nbytes_counts_actual_version_bytes(self):
        g = gen_temporal_graph(n=25, m=120, t_max=10, seed=1)
        tab = edge_core_times(g, 2)
        assert tab.nbytes() == (tab.edge_id.nbytes + tab.ts_from.nbytes
                                + tab.ts_to.nbytes + tab.ct.nbytes)
        assert tab.nbytes() == 16 * tab.num_versions   # 4 int32 words

    def test_builder_prefilter_identical_index(self):
        import dataclasses
        from repro.core.ecb_forest import IncrementalBuilder
        from repro.core.pecb_index import pack_index

        g = gen_temporal_graph(n=30, m=200, t_max=12, seed=5)
        tab = edge_core_times(g, 2)
        a = pack_index(g, 2, IncrementalBuilder(g, tab, prefilter=True).run())
        b = pack_index(g, 2, IncrementalBuilder(g, tab, prefilter=False).run())
        for f in dataclasses.fields(a):
            va, vb = getattr(a, f.name), getattr(b, f.name)
            same = (np.array_equal(va, vb) if isinstance(va, np.ndarray)
                    else va == vb)
            assert same, f.name

    def test_query_invariant_error_not_assert(self):
        """The reachable-state guard must survive `python -O`: it raises an
        explicit error instead of asserting."""
        from repro.core.ecb_forest import ForestInvariantError
        from repro.core.pecb_index import build_pecb_index

        g = gen_temporal_graph(n=20, m=120, t_max=8, seed=2)
        idx = build_pecb_index(g, 2)
        if idx.num_nodes == 0:
            pytest.skip("degenerate graph")
        # corrupt the index: point an entry's left child at a node that has
        # no entry covering ts (simulates the exact state a bare assert hid)
        idx.ent_left[:] = idx.num_nodes - 1
        idx.row_ptr[-1] = idx.row_ptr[-2]       # last node: no entries at all
        u = int(idx.node_u[0])
        with pytest.raises(ForestInvariantError):
            for ts in range(1, g.t_max + 1):
                idx._component_vertices(u, ts, g.t_max)

    def test_t_max_cached(self):
        g = gen_temporal_graph(n=10, m=40, t_max=6, seed=0)
        assert g.t_max == int(g.t.max())
        assert g._t_max == g.t_max              # computed once in __post_init__
