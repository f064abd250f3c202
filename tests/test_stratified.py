"""K-agnostic index plane (DESIGN.md §14): one k-stratified build serves
every k.

Three-backend equality (stratified vs per-k PECB vs the brute-force
k-core oracle) across every query mode, k-monotonicity as a property
(hypothesis where installed, seeded sweep everywhere), interleaved
extend/shrink epoch chains against cold stratified rebuilds, the
workload-level cache purge (one purge clears every k stratum, touches no
other workload), and the deprecation shims that keep the old
(workload, k) registry surface importable."""

import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.batch_query import (_ARRAY_FIELDS, _host_layout,
                                    batch_query, batch_query_full,
                                    batch_query_full_mixed, mixed_slots,
                                    stratum_device, to_device, window_sweep)
from repro.core.core_time import (default_ks, extend_stratified_core_times,
                                  shrink_stratified_core_times,
                                  stratified_core_times)
from repro.core.kcore import k_max as graph_k_max
from repro.core.kcore import tccs_oracle, tccs_oracle_edges
from repro.core.pecb_index import build_pecb_index, build_stratified_index
from repro.core.query_api import (InvalidQueryError, ResultMode, TCCSQuery,
                                  WindowSweep)
from repro.core.streaming import (extend_stratified_index,
                                  shrink_stratified_index)
from repro.core.temporal_graph import gen_temporal_graph, random_queries
from repro.serving import EngineConfig, IndexRegistry, ServingEngine
from repro.serving.cache import ResultCache
from repro.serving.executor import pad_queries

from test_streaming import assert_pecb_identical


def _jaxpr_shapes(jaxpr) -> set:
    """Every shape a jaxpr and its sub-jaxprs (jit, while) compute."""
    shapes = set()
    for eqn in jaxpr.eqns:
        shapes.update(tuple(v.aval.shape) for v in eqn.outvars)
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else (p,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    shapes |= _jaxpr_shapes(inner)
    return shapes


def graphs():
    return [gen_temporal_graph(n=18, m=70, t_max=7, seed=3),
            gen_temporal_graph(n=30, m=240, t_max=12, seed=5),
            gen_temporal_graph(n=40, m=420, t_max=18, seed=31)]


# ----------------------------------------------------------------------
# three-backend equality: stratified == per-k PECB == brute-force oracle
# ----------------------------------------------------------------------

class TestThreeBackendEquality:
    @pytest.mark.parametrize("gi", [0, 1, 2])
    def test_all_modes_all_ks(self, gi):
        g = graphs()[gi]
        sx = build_stratified_index(g)
        km = graph_k_max(g)
        assert sx.supported_ks == tuple(range(2, km + 1)) == default_ks(g)
        rng = np.random.default_rng(gi)
        for k in list(sx.supported_ks) + [km + 1, km + 3]:
            per_k = build_pecb_index(g, k) if k <= km else None
            for _ in range(10):
                u = int(rng.integers(0, g.n))
                ts = int(rng.integers(1, g.t_max + 1))
                te = int(rng.integers(ts, g.t_max + 1))
                want_v = frozenset(tccs_oracle(g, k, u, ts, te))
                want_e = tccs_oracle_edges(g, k, u, ts, te)
                for mode in ResultMode:
                    q = TCCSQuery(u, ts, te, k, mode)
                    r = sx.answer(q)
                    assert r.num_vertices == len(want_v)
                    if mode is not ResultMode.COUNT:
                        assert r.vertices == want_v, (k, u, ts, te)
                    if mode is ResultMode.EDGES:
                        assert r.edges.edge_ids() == want_e
                    if mode is ResultMode.SUBGRAPH:
                        assert r.subgraph.m == len(want_e)
                    if per_k is not None:
                        rp = per_k.answer(q)
                        assert rp.vertices == r.vertices
                        assert rp.num_vertices == r.num_vertices
                        if mode is ResultMode.EDGES:
                            assert rp.edges.edge_ids() == r.edges.edge_ids()

    def test_slice_k_reconstructs_per_k_bit_identically(self):
        g = graphs()[1]
        sx = build_stratified_index(g)
        for k in sx.supported_ks:
            assert_pecb_identical(sx.slice_k(k), build_pecb_index(g, k))

    def test_unsupported_in_range_k_raises(self):
        g = graphs()[0]
        sx = build_stratified_index(g, ks=(2, 4))
        with pytest.raises(InvalidQueryError, match="supported_ks"):
            sx.answer(TCCSQuery(0, 1, 5, 3))
        with pytest.raises(KeyError):
            sx.k_index(3)
        with pytest.raises(KeyError):
            mixed_slots(sx, [(0, 3)])

    def test_k_above_graph_k_max_is_trivially_empty(self):
        g = graphs()[0]
        sx = build_stratified_index(g)
        r = sx.answer(TCCSQuery(0, 1, g.t_max, sx.k_max_graph + 7))
        assert r.vertices == frozenset()
        assert r.provenance.route == "trivial"


# ----------------------------------------------------------------------
# device plane: one compiled program serves mixed-k batches
# ----------------------------------------------------------------------

class TestMixedKDevice:
    @pytest.mark.parametrize("gi", [1, 2])
    @pytest.mark.parametrize("program", ["batch_query",
                                         "batch_query_full_mixed"])
    def test_mixed_k_batch_matches_each_stratum(self, program, gi):
        # one launch mixes every stratum, the widest (smallest k) and the
        # last (largest k, whose window is moved left to fit the mirror),
        # plus inert pad lanes; each row answers from its own stratum
        g = graphs()[gi]
        sx = build_stratified_index(g)
        dix = to_device(sx)
        sizes = np.diff(sx.knode_ptr)
        W = dix.max_stratum_nodes
        assert W == sizes[0] == sizes.max() < dix.num_nodes
        assert sx.knode_ptr[-2] + W > dix.num_nodes
        qs = random_queries(g, 29, seed=7 + gi)
        ks = [sx.supported_ks[i % len(sx.supported_ks)]
              for i in range(len(qs))]
        slot = mixed_slots(sx, [(u, k) for (u, _, _), k in zip(qs, ks)])
        ts = np.asarray([q[1] for q in qs], np.int32)
        te = np.asarray([q[2] for q in qs], np.int32)
        slot, ts, te = pad_queries(slot, ts, te, 32)
        kq = np.zeros(32, np.int32)
        kq[:len(ks)] = ks
        if program == "batch_query":
            vmask = np.asarray(batch_query(dix, slot, ts, te)[0])
        else:
            vmask, vermask, _ = batch_query_full_mixed(dix, slot, ts, te, kq)
            vmask, vermask = np.asarray(vmask), np.asarray(vermask)
            assert not vermask[len(qs):].any()
        assert not vmask[len(qs):].any()
        store = sx.versions
        for i, ((u, a, b), k) in enumerate(zip(qs, ks)):
            want = sx.slice_k(k)._component_vertices(u, a, b)
            assert frozenset(np.nonzero(vmask[i])[0].tolist()) == \
                frozenset(want), (u, a, b, k)
            if program == "batch_query_full_mixed":
                got = {int(store.edge_id[j])
                       for j in np.nonzero(vermask[i])[0].tolist()}
                assert got == tccs_oracle_edges(g, k, u, a, b), (u, a, b, k)

    @pytest.mark.parametrize("program", ["batch_query", "batch_query_full",
                                         "batch_query_full_mixed",
                                         "window_sweep"])
    def test_fused_programs_hold_no_all_strata_row(self, program):
        # every (B, .) array of a launch on the fused mirror is one
        # stratum window wide: none spans the nodes of all strata
        g = graphs()[2]
        sx = build_stratified_index(g)
        dix = to_device(sx)
        B, N, W = 16, dix.num_nodes, dix.max_stratum_nodes
        q = jnp.zeros((B,), jnp.int32)
        fn = {"batch_query": batch_query, "batch_query_full": batch_query_full,
              "batch_query_full_mixed": batch_query_full_mixed,
              "window_sweep": window_sweep}[program]
        args = (dix, q, q, q, q) if program == "batch_query_full_mixed" \
            else (dix, q, q, q)
        shapes = _jaxpr_shapes(jax.make_jaxpr(fn)(*args).jaxpr)
        assert (B, W) in shapes
        assert not any(len(sh) == 2 and sh[1] in (N, N + 1)
                       for sh in shapes), program

    def test_window_sweep_slot_selects_stratum(self):
        g = graphs()[0]
        sx = build_stratified_index(g)
        dix = to_device(sx)
        windows = [(d, min(d + 3, g.t_max)) for d in range(1, g.t_max)]
        ts = np.asarray([w[0] for w in windows], np.int32)
        te = np.asarray([w[1] for w in windows], np.int32)
        u = 1
        for k in sx.supported_ks:
            slot = np.full(len(windows), sx.k_index(k) * g.n + u, np.int32)
            vmask = np.asarray(window_sweep(dix, slot, ts, te)[0])
            for i, (a, b) in enumerate(windows):
                want = frozenset(sx.slice_k(k)._component_vertices(u, a, b))
                assert frozenset(np.nonzero(vmask[i])[0].tolist()) == want

    def test_stratum_device_matches_per_k_mirror(self):
        # the single-k sweep path: every stratum's device slice must be
        # array-for-array what uploading the per-k slice would give, and
        # a sweep on the slice must match the fused-mirror slot sweep
        g = graphs()[0]
        sx = build_stratified_index(g)
        dix = to_device(sx)
        windows = [(d, min(d + 3, g.t_max)) for d in range(1, g.t_max)]
        ts = np.asarray([w[0] for w in windows], np.int32)
        te = np.asarray([w[1] for w in windows], np.int32)
        u = 1
        for k in sx.supported_ks:
            sd = stratum_device(dix, sx, k)
            ref = to_device(sx.slice_k(k))
            for f in _ARRAY_FIELDS:
                assert np.array_equal(np.asarray(getattr(sd, f)),
                                      np.asarray(getattr(ref, f))), (k, f)
            assert sd.num_versions == ref.num_versions
            # one stratum: the node window is the whole slice
            assert sd.max_stratum_nodes == ref.max_stratum_nodes \
                == ref.num_nodes == int(np.diff(sx.knode_ptr)[sx.k_index(k)])
            slot = np.full(len(windows), sx.k_index(k) * g.n + u, np.int32)
            fused = np.asarray(window_sweep(dix, slot, ts, te)[0])
            sliced = np.asarray(window_sweep(
                sd, np.full(len(windows), u, np.int32), ts, te)[0])
            assert np.array_equal(fused, sliced), k
        with pytest.raises(KeyError):
            stratum_device(dix, sx, 99)

    def test_engine_sweep_uses_stratum_mirror(self):
        # end-to-end: the engine's sweep route answers from the stratum
        # slice and stays oracle-exact; the handle memoizes the slice
        g = graphs()[0]
        with ServingEngine(EngineConfig(flush_ms=0.5,
                                        host_threshold=1)) as eng:
            eng.register_graph("g", g)
            h = eng.warmup("g", sweep=True, sweep_ks=(2,))
            assert 2 in h._stratum_dev
            assert h._stratum_dev[2].num_nodes == \
                h.stratum_device(2).num_nodes
            windows = [(d, min(d + 4, g.t_max)) for d in range(1, 8)]
            res = eng.sweep("g", WindowSweep(u=1, k=2, windows=windows))
            assert any(r.provenance.route == "sweep" for r in res)
            for r, (a, b) in zip(res, windows):
                assert r.vertices == tccs_oracle(g, 2, 1, a, b)


# ----------------------------------------------------------------------
# live lists: each row propagates over its stratum's nodes live at ts
# ----------------------------------------------------------------------

PROGRAMS = {"batch_query": batch_query, "batch_query_full": batch_query_full,
            "batch_query_full_mixed": batch_query_full_mixed,
            "window_sweep": window_sweep}


def _live_counts(dix) -> np.ndarray:
    """int[K, t_max + 2]: the length of each (stratum, start time) list."""
    return np.diff(np.asarray(dix.live_ptr)).reshape(-1, dix.t_max + 2)


def _edge_time_windows(dix, ki, t_max):
    """Windows at the edges of time (before the first day, the first and
    the last day, past the last), the start time whose list of stratum
    ki is the mirror's longest, and one whose list is empty."""
    c = _live_counts(dix)
    out = [(-2, 3), (0, 2), (0, t_max), (1, t_max), (1, 1),
           (t_max, t_max), (t_max + 1, t_max + 1), (t_max + 1, t_max + 4)]
    for t in np.flatnonzero(c[ki] == dix.max_live_nodes)[:1]:
        out += [(int(t), t_max), (int(t), int(t))]
    for t in np.flatnonzero(c[ki, 1:t_max + 1] == 0)[:1]:
        out.append((int(t) + 1, t_max))
    return out


def _run(program, dix, slots, ts, te, k, bucket=32):
    """(vertex masks, version masks or None) of one padded launch."""
    b = len(ts)
    if program == "window_sweep":
        out = window_sweep(dix, jnp.int32(slots[0]),
                           jnp.asarray(ts, jnp.int32),
                           jnp.asarray(te, jnp.int32))
        return np.asarray(out[0]), None
    slot, tsp, tep = pad_queries(np.asarray(slots, np.int32),
                                 np.asarray(ts, np.int32),
                                 np.asarray(te, np.int32), bucket)
    if program == "batch_query_full_mixed":
        out = batch_query_full_mixed(dix, slot, tsp, tep,
                                     np.full(bucket, k, np.int32))
    else:
        out = PROGRAMS[program](dix, slot, tsp, tep)
    vmask = np.asarray(out[0])
    assert not vmask[b:].any()              # pad lanes stay empty
    if program == "batch_query":
        return vmask[:b], None
    vermask = np.asarray(out[1])
    assert not vermask[b:].any()
    return vmask[:b], vermask[:b]


class TestLiveLists:
    @pytest.mark.parametrize("gi", [0, 1])
    @pytest.mark.parametrize("mirror", ["fused", "per_k"])
    def test_layout_matches_brute_force(self, gi, mirror):
        # row ki*(t_max+2)+t lists stratum ki's nodes with
        # live_from <= t <= live_to, ascending; M is the longest row
        g = graphs()[gi]
        sx = build_stratified_index(g)
        indexes = ([sx] if mirror == "fused"
                   else [sx.slice_k(k) for k in sx.supported_ks])
        T2 = g.t_max + 2
        for index in indexes:
            meta, a = _host_layout(index)
            kp, ptr = a["knode_ptr"], a["live_ptr"]
            lf, lt = a["live_from"], a["live_to"]
            assert ptr.shape == ((len(kp) - 1) * T2 + 1,)
            assert ptr[-1] == np.maximum(lt - lf + 1, 0).sum()
            longest = 0
            for ki in range(len(kp) - 1):
                x = np.arange(kp[ki], kp[ki + 1])
                for t in range(T2):
                    r = ki * T2 + t
                    want = x[(lf[x] <= t) & (t <= lt[x])]
                    assert np.array_equal(
                        a["live_node"][ptr[r]:ptr[r + 1]], want), (ki, t)
                    longest = max(longest, want.size)
                # the two end rows are empty
                assert ptr[ki * T2 + 1] == ptr[ki * T2]
                assert ptr[(ki + 1) * T2] == ptr[(ki + 1) * T2 - 1]
            assert meta["max_live_nodes"] == max(longest, 1)
            # a spanning forest of n vertices: fewer than n live nodes
            assert longest < g.n and longest <= meta["max_stratum_nodes"]

    @pytest.mark.parametrize("mirror", ["fused", "per_k", "stratum"])
    @pytest.mark.parametrize("program", list(PROGRAMS))
    def test_programs_match_algorithm_1_at_the_edges(self, program, mirror):
        # every program on every kind of mirror answers as Algorithm 1
        # before the first day, on it, on the last, past it, on the start
        # time of the longest list and on one whose list is empty
        g = graphs()[0]
        sx = build_stratified_index(g)
        fused = to_device(sx)
        store = sx.versions
        empty_seen = longest_seen = False
        for k in sx.supported_ks:
            ki = sx.k_index(k)
            if mirror == "fused":
                dix, base, row_ki = fused, ki * g.n, ki
            else:
                dix = (to_device(sx.slice_k(k)) if mirror == "per_k"
                       else stratum_device(fused, sx, k))
                base, row_ki = 0, 0
                store = sx.slice_k(k).versions
            windows = _edge_time_windows(dix, row_ki, g.t_max)
            c = _live_counts(dix)[row_ki]
            empty_seen |= any(1 <= a <= g.t_max and c[a] == 0
                              for a, _ in windows)
            longest_seen |= any(0 <= a < c.size
                                and c[a] == dix.max_live_nodes
                                for a, _ in windows)
            for u in (0, 3, 7):
                ts = [a for a, _ in windows]
                te = [b for _, b in windows]
                vmask, vermask = _run(program, dix,
                                      [base + u] * len(ts), ts, te, k)
                for i, (a, b) in enumerate(windows):
                    want = sx.slice_k(k)._component_vertices(u, a, b)
                    assert set(np.flatnonzero(vmask[i]).tolist()) == want, \
                        (k, u, a, b)
                    if vermask is None or (mirror == "fused"
                                           and program == "batch_query_full"):
                        continue   # no k filter over the fused versions
                    got = set(store.edge_id[np.flatnonzero(
                        vermask[i][:store.num_versions])].tolist())
                    assert got == tccs_oracle_edges(g, k, u, a, b), \
                        (k, u, a, b)
        assert empty_seen and longest_seen

    @pytest.mark.parametrize("mirror", ["fused", "per_k"])
    @pytest.mark.parametrize("program", list(PROGRAMS))
    def test_while_body_gathers_live_lists(self, program, mirror):
        # the pointer-jump rounds gather from (B, M), never from (B, W)
        g = graphs()[1]
        sx = build_stratified_index(g)
        dix = to_device(sx if mirror == "fused" else sx.slice_k(2))
        B, M, W = 16, dix.max_live_nodes, dix.max_stratum_nodes
        assert M < W
        q = jnp.ones((B,), jnp.int32)
        args = (dix, q, q, q, q) if program == "batch_query_full_mixed" \
            else (dix, q, q, q)
        jaxpr = jax.make_jaxpr(PROGRAMS[program])(*args).jaxpr
        loops = _eqns(jaxpr, "while")
        assert len(loops) == 1
        gathers = _eqns(loops[0].params["body_jaxpr"].jaxpr, "gather")
        assert gathers
        assert {tuple(e.invars[0].aval.shape) for e in gathers} == {(B, M)}


def _eqns(jaxpr, primitive) -> list:
    """Every equation of ``primitive`` in a jaxpr and its sub-jaxprs."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            out.append(eqn)
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else (p,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out += _eqns(inner, primitive)
    return out


# ----------------------------------------------------------------------
# k-monotonicity: cores are nested in k (property + seeded sweep)
# ----------------------------------------------------------------------

def _assert_monotone(sx, u, ts, te):
    prev = None
    for k in sx.supported_ks:
        cur = sx.answer(TCCSQuery(u, ts, te, k)).vertices
        if prev is not None:
            # u's component can only shrink as k rises: the (k+1)-core is
            # a subgraph of the k-core, so u's (k+1)-component sits inside
            # u's k-component (or u has dropped out entirely)
            assert cur <= prev, (u, ts, te, k)
        prev = cur


class TestKMonotonicity:
    def test_seeded_sweep(self):
        for g in graphs():
            sx = build_stratified_index(g)
            rng = np.random.default_rng(11)
            for _ in range(30):
                u = int(rng.integers(0, g.n))
                ts = int(rng.integers(1, g.t_max + 1))
                te = int(rng.integers(ts, g.t_max + 1))
                _assert_monotone(sx, u, ts, te)

    def test_membership_count_monotone_nonincreasing(self):
        """|core_k| over all vertices is non-increasing in k for a fixed
        window (k-stratification's defining invariant)."""
        g = graphs()[0]
        sx = build_stratified_index(g)
        rng = np.random.default_rng(12)
        for _ in range(10):
            ts = int(rng.integers(1, g.t_max + 1))
            te = int(rng.integers(ts, g.t_max + 1))
            sizes = []
            for k in sx.supported_ks:
                member = set()
                for u in range(g.n):
                    member |= sx.answer(TCCSQuery(u, ts, te, k)).vertices
                sizes.append(len(member))
            assert all(a >= b for a, b in zip(sizes, sizes[1:])), (ts, te)


try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    _G = gen_temporal_graph(n=24, m=160, t_max=10, seed=19)
    _SX = build_stratified_index(_G)

    class TestKMonotonicityProperty:
        @settings(max_examples=100, deadline=None,
                  suppress_health_check=[HealthCheck.too_slow])
        @given(u=st.integers(0, _G.n - 1),
               ts=st.integers(1, _G.t_max),
               span=st.integers(0, _G.t_max))
        def test_component_nested_in_k(self, u, ts, span):
            _assert_monotone(_SX, u, ts, min(ts + span, _G.t_max))

        @settings(max_examples=100, deadline=None,
                  suppress_health_check=[HealthCheck.too_slow])
        @given(u=st.integers(0, _G.n - 1),
               ts=st.integers(1, _G.t_max),
               span=st.integers(0, _G.t_max),
               k=st.integers(2, 12))
        def test_matches_oracle(self, u, ts, span, k):
            te = min(ts + span, _G.t_max)
            r = _SX.answer(TCCSQuery(u, ts, te, k))
            assert r.vertices == frozenset(tccs_oracle(_G, k, u, ts, te))
except ImportError:  # pragma: no cover - hypothesis absent in minimal envs
    pass


# ----------------------------------------------------------------------
# interleaved extend/shrink epoch chain == cold stratified rebuild
# ----------------------------------------------------------------------

class TestEpochChain:
    def _suffix(self, g, rng, n_edges, t_span):
        return [(int(rng.integers(0, g.n)), int(rng.integers(0, g.n)),
                 int(g.t_max + 1 + rng.integers(0, t_span)))
                for _ in range(n_edges)]

    def test_interleaved_extend_shrink_chain(self):
        rng = np.random.default_rng(23)
        cur = gen_temporal_graph(n=28, m=220, t_max=10, seed=23)
        tab = stratified_core_times(cur)
        sx = build_stratified_index(cur, strata=tab)
        plan = [("extend", 120), ("shrink", 4), ("extend", 90),
                ("shrink", 6), ("extend", 150), ("shrink", 5)]
        for step, (op, arg) in enumerate(plan):
            if op == "extend":
                suffix = self._suffix(cur, rng, arg, t_span=5)
                cur = cur.extend(suffix)
                # appended edges may raise k_max: pass the grown ks so the
                # fresh strata are built cold alongside the incremental ones
                ks = default_ks(cur)
                tab = extend_stratified_core_times(cur, tab, ks)
                sx = extend_stratified_index(cur, sx, ks, strata=tab)
            else:
                cur = cur.expire_before(arg)
                # expiry may lower k_max; shrink must never add strata
                ks = tuple(k for k in default_ks(cur) if k in tab.ks)
                tab = shrink_stratified_core_times(cur, tab, ks)
                sx = shrink_stratified_index(cur, sx, ks, strata=tab)
            assert_pecb_identical(sx, build_stratified_index(cur))
            qrng = np.random.default_rng(100 + step)
            for _ in range(6):
                u = int(qrng.integers(0, cur.n))
                ts = int(qrng.integers(1, cur.t_max + 1))
                te = int(qrng.integers(ts, cur.t_max + 1))
                for k in list(sx.supported_ks)[:3] + [sx.k_max_graph + 2]:
                    r = sx.answer(TCCSQuery(u, ts, te, k))
                    assert r.vertices == \
                        frozenset(tccs_oracle(cur, k, u, ts, te)), \
                        (step, u, ts, te, k)


# ----------------------------------------------------------------------
# satellite 2: ONE workload-level purge clears every k stratum
# ----------------------------------------------------------------------

class TestWorkloadPurge:
    def test_purge_index_clears_all_k_strata_only(self):
        c = ResultCache(capacity=64)
        for k in (2, 3, 5, 9):
            c.put(("w", (0, 1, 5, k, "vertices")), frozenset({k}))
            c.put(("other", (0, 1, 5, k, "vertices")), frozenset({k}))
        c.put("foreign-key", frozenset({1}))
        assert c.purge_index("w") == 4
        for k in (2, 3, 5, 9):
            assert c.get(("w", (0, 1, 5, k, "vertices"))) is None
            assert c.get(("other", (0, 1, 5, k, "vertices"))) is not None
        assert c.get("foreign-key") is not None
        assert c.stats()["purges"] == 4

    def test_engine_eviction_purges_every_k_of_one_workload(self):
        g1 = gen_temporal_graph(n=20, m=120, t_max=8, seed=1)
        g2 = gen_temporal_graph(n=20, m=120, t_max=8, seed=2)
        cfg = EngineConfig(flush_ms=5.0, registry_capacity=1,
                           cache_capacity=64)
        with ServingEngine(cfg) as eng:
            eng.register_graph("g1", g1)
            eng.register_graph("g2", g2)
            for k in (2, 3):
                eng.answer("g1", TCCSQuery(0, 1, 6, k))
            n_g1 = len(eng.cache)
            assert n_g1 == 2
            eng.answer("g2", TCCSQuery(0, 1, 6, 2))   # evicts workload g1
            # the eviction listener purged BOTH of g1's k strata at once,
            # leaving g2's fresh entry alone
            assert eng.cache.stats()["purges"] == n_g1
            assert len(eng.cache) == 1
            r = eng.answer("g2", TCCSQuery(0, 1, 6, 2))
            assert r.provenance.route == "cache"


# ----------------------------------------------------------------------
# satellite 6: deprecation shims for the old (workload, k) surface
# ----------------------------------------------------------------------

class TestPerKKeyShims:
    def _registry(self):
        reg = IndexRegistry()
        reg.register_graph("g", gen_temporal_graph(n=14, m=60, t_max=6,
                                                   seed=1))
        return reg

    def test_registry_get_with_k_warns_and_serves(self):
        reg = self._registry()
        try:
            with pytest.warns(DeprecationWarning, match="deprecated"):
                h = reg.get("g", 2)
            assert 2 in h.supported_ks
            with warnings.catch_warnings():
                warnings.simplefilter("error")
                assert reg.get("g") is h       # new surface: no warning
        finally:
            reg.close()

    def test_registry_get_nowait_and_async_with_k_warn(self):
        reg = self._registry()
        try:
            with pytest.warns(DeprecationWarning, match="deprecated"):
                reg.get_nowait("g", 3, start_build=False)
            with pytest.warns(DeprecationWarning, match="deprecated"):
                h = reg.get_async("g", 3).result(timeout=60)
            assert 3 in h.supported_ks
        finally:
            reg.close()

    def test_tuple_membership_warns_and_matches_workload(self):
        reg = self._registry()
        try:
            reg.get("g")
            with pytest.warns(DeprecationWarning, match="deprecated"):
                assert ("g", 2) in reg
            with pytest.warns(DeprecationWarning, match="deprecated"):
                assert ("g", 9) in reg         # k ignored: workload-level
            assert "g" in reg
        finally:
            reg.close()

    def test_engine_warmup_prefetch_with_k_warn(self):
        g = gen_temporal_graph(n=14, m=60, t_max=6, seed=2)
        with ServingEngine(EngineConfig(flush_ms=5.0)) as eng:
            eng.register_graph("g", g)
            with pytest.warns(DeprecationWarning, match="deprecated"):
                h = eng.warmup("g", 2)
            assert h.supported_ks
            with pytest.warns(DeprecationWarning, match="deprecated"):
                eng.prefetch("g", 3).result(timeout=60)

    def test_registry_ks_policy_guard(self):
        reg = self._registry()
        try:
            reg.get("g")
            with pytest.raises(RuntimeError, match="resident"):
                reg.set_ks("g", (2, 3))
        finally:
            reg.close()


class TestLayoutOverflowGuard:
    """§15.2 satellite: the packed slot/row-pointer math raises a typed
    error instead of silently wrapping past int32."""

    def test_checked_caster_roundtrip_and_raise(self):
        from repro.core.batch_query import LayoutOverflowError, _i32
        ok = _i32(np.array([0, 7, 2**31 - 1], np.int64))
        assert ok.dtype == np.int32
        with pytest.raises(LayoutOverflowError, match="exceeds int32"):
            _i32(np.array([2**31], np.int64), "fused entry slots")
        with pytest.raises(LayoutOverflowError, match="exceeds int32"):
            _i32(np.array([-2**31 - 1], np.int64))
        # the typed error stays catchable as the stdlib family
        assert issubclass(LayoutOverflowError, OverflowError)

    def test_mixed_slots_computes_in_int64_first(self):
        """k_index * n + u must not wrap *before* the guard sees it: a
        fake stratified view with a huge n keeps the intermediate exact
        and the guard raises rather than returning a wrapped slot."""
        from repro.core.batch_query import LayoutOverflowError

        class FakeSx:
            n = 2**30
            ks = (2, 3, 4)

            def k_index(self, k):
                return self.ks.index(k)

        with pytest.raises(LayoutOverflowError, match="mixed-k entry"):
            mixed_slots(FakeSx(), [(5, 4)])   # 2*2^30 + 5 > int32 max
