"""The engine's layer instrumentation: the query programs' pointer-jump
round counter against a numpy reference, the executor's round counters,
the device route's launch spans and histograms, the spans in a JAX
profiler trace, the core-time build's sub-stages, and what stays
recorded with the tracer off."""

import pathlib
import subprocess
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.batch_query import (_host_layout, batch_query,
                                    batch_query_full,
                                    batch_query_full_mixed, mixed_slots,
                                    to_device, window_sweep)
from repro.core.core_time import SWEEP_STAGES, stratified_core_times
from repro.core.pecb_index import build_pecb_index, build_stratified_index
from repro.core.query_api import TCCSQuery, WindowSweep
from repro.core.temporal_graph import gen_temporal_graph, random_queries
from repro.obs import Tracer
from repro.serving import EngineConfig, ServingEngine
from repro.serving.executor import ShardedExecutor
from repro.serving.metrics import EngineMetrics

SUBS = tuple(f"core_times.{s}" for s in SWEEP_STAGES)


# ----------------------------------------------------------------------
# numpy pointer-jumping reference of the query programs
# ----------------------------------------------------------------------

def _first_at_or_after(keys, lo, hi, t):
    """Smallest i in [lo, hi) with keys[i] >= t, else hi."""
    return lo + int(np.searchsorted(keys[lo:hi], t, side="left"))


def reference(index, slot, ts, te, kq=None):
    """(bool[B, n] vertex masks, bool[B, V] version masks, rounds) of one
    launch over ``index``'s device layout: entry lookup at each query's
    slot, parent links at ts, activity within the slot's stratum, pointer
    jumping to the batch's fixpoint (the last, unchanged round counted),
    membership."""
    meta, a = _host_layout(index)
    N, n = a["node_u"].shape[0], meta["n"]
    E = a["ent_ts"].shape[0]
    B = len(ts)
    slot = np.asarray(slot)
    vlo, vhi = a["vrow_ptr"][slot], a["vrow_ptr"][slot + 1]
    kp = a["knode_ptr"]
    x = np.arange(N)[None]
    stratum = ((kp[slot // n][:, None] <= x) & (x < kp[slot // n + 1][:, None]))
    parent = np.empty((B, N), np.int64)
    for b in range(B):
        for x in range(N):
            i = _first_at_or_after(a["ent_ts"], a["row_ptr"][x],
                                   a["row_ptr"][x + 1], ts[b])
            parent[b, x] = a["ent_parent"][min(i, E - 1)]
    ts_c, te_c = np.asarray(ts)[:, None], np.asarray(te)[:, None]
    active = (stratum & (a["live_from"][None] <= ts_c)
              & (ts_c <= a["live_to"][None]) & (a["node_ct"][None] <= te_c))
    pc = np.clip(parent, 0, N - 1)
    up = (parent >= 0) & active & np.take_along_axis(active, pc, axis=1)
    top = np.where(up, pc, np.arange(N)[None])
    rounds = 0
    while True:
        nxt = np.take_along_axis(top, top, axis=1)
        rounds += 1
        changed = bool((nxt != top).any())
        top = nxt
        if not changed:
            break
    vmask = np.zeros((B, n), bool)
    for b in range(B):
        i = _first_at_or_after(a["vent_ts"], vlo[b], vhi[b], ts[b])
        e0 = a["vent_node"][i] if i < vhi[b] else -1
        if e0 < 0 or a["node_ct"][e0] > te[b]:
            continue
        member = active[b] & (top[b] == top[b, e0])
        vmask[b, a["node_u"][member]] = True
        vmask[b, a["node_v"][member]] = True
    vermask = ((a["ver_ts_from"][None] <= ts_c)
               & (ts_c <= a["ver_ts_to"][None])
               & (a["ver_ct"][None] <= te_c) & vmask[:, a["ver_src"]])
    if kq is not None:
        vermask &= a["ver_k"][None] == np.asarray(kq)[:, None]
    return vmask, vermask, rounds


def _windows(g, qs):
    return ([q[1] for q in qs], [q[2] for q in qs])


@pytest.mark.parametrize("seed", [5, 31])
@pytest.mark.parametrize("program", ["batch_query", "batch_query_full",
                                     "batch_query_full_mixed",
                                     "window_sweep"])
def test_round_counter_matches_numpy_reference(program, seed):
    g = gen_temporal_graph(n=30, m=260, t_max=12, seed=seed)
    qs = random_queries(g, 24, seed=seed)
    ts, te = _windows(g, qs)
    tsd, ted = jnp.asarray(ts, jnp.int32), jnp.asarray(te, jnp.int32)
    sx = build_stratified_index(g)
    rng = np.random.default_rng(seed)
    ks = [int(rng.choice(sx.supported_ks)) for _ in qs]
    kq = None
    if program == "batch_query_full":
        k = sx.supported_ks[0]
        index = build_pecb_index(g, k)
        dix = to_device(index)
        u = np.asarray([q[0] for q in qs], np.int32)
        out = batch_query_full(dix, jnp.asarray(u), tsd, ted)
        slot = u
    elif program == "window_sweep":
        k = ks[0]
        index = sx.slice_k(k)
        u = qs[0][0]
        out = window_sweep(to_device(index), jnp.int32(u), tsd, ted)
        slot = [u] * len(qs)
    else:
        index = sx
        slot = mixed_slots(sx, [(q[0], k) for q, k in zip(qs, ks)])
        dix = to_device(sx)
        if program == "batch_query":
            out = batch_query(dix, jnp.asarray(slot), tsd, ted)
        else:
            kq = ks
            out = batch_query_full_mixed(dix, jnp.asarray(slot), tsd, ted,
                                         jnp.asarray(kq, jnp.int32))
    want_v, want_ver, want_rounds = reference(index, slot, ts, te, kq)
    *masks, rounds = jax.device_get(out)
    assert rounds.dtype == np.int32 and rounds.shape == ()
    assert int(rounds) == want_rounds >= 1
    assert np.array_equal(masks[0], want_v)
    if len(masks) == 2:
        V = index.versions.num_versions if program == "batch_query_full" \
            else index.strata.num_versions
        assert np.array_equal(masks[1][:, :V], want_ver[:, :V])
    # the vertex masks are Algorithm 1's answers
    for i, q in enumerate(qs):
        if program == "window_sweep":
            want = index._component_vertices(u, q[1], q[2])
        elif program == "batch_query_full":
            want = index._component_vertices(*q)
        else:
            want = sx.slice_k(ks[i])._component_vertices(*q)
        assert set(np.nonzero(masks[0][i])[0].tolist()) == set(want)


def test_empty_forest_takes_no_rounds():
    g = gen_temporal_graph(n=12, m=20, t_max=5, seed=1)
    index = build_pecb_index(g, 9)          # above k_max: no forest
    dix = to_device(index)
    q = jnp.zeros(8, jnp.int32)
    mask, rounds = batch_query(dix, q, q + 1, q + 3)
    assert int(rounds) == 0 and not np.asarray(mask).any()


# ----------------------------------------------------------------------
# executor: rounds counted per launch, spans per launch
# ----------------------------------------------------------------------

def test_jump_counters_add_up_across_launches():
    g = gen_temporal_graph(n=30, m=260, t_max=12, seed=5)
    sx = build_stratified_index(g)
    dix = to_device(sx)
    metrics, tracer = EngineMetrics(), Tracer()
    ex = ShardedExecutor(metrics=metrics, tracer=tracer)
    per_launch = []
    for seed in (1, 2, 3):
        qs = random_queries(g, 8, seed=seed)
        slot = mixed_slots(sx, [(q[0], 2) for q in qs])
        ts, te = _windows(g, qs)
        ex.run(dix, slot, ts, te, 8)
        per_launch.append(reference(sx, slot, ts, te)[2])
    sweep_dix = to_device(sx.slice_k(2))
    ex.run_sweep(sweep_dix, 3, [1, 2, 3], [5, 6, 7], 8)
    waits = tracer.spans(name="executor.wait")
    rounds = [s.attrs["jump_rounds"] for s in waits]
    assert rounds[:3] == per_launch
    assert metrics.counter("jump_launches") == 4
    assert metrics.counter("jump_rounds") == sum(rounds)
    # each launch adds its static window once: the fused mirror's widest
    # stratum, and the whole of the one-stratum sweep mirror
    widest = int(np.diff(sx.knode_ptr).max())
    assert dix.max_stratum_nodes == widest < dix.num_nodes
    assert sweep_dix.max_stratum_nodes == sweep_dix.num_nodes
    assert [s.attrs["jump_width"] for s in waits] == [widest] * 3 + [
        sweep_dix.num_nodes]
    assert metrics.counter("jump_width") == 3 * widest + sweep_dix.num_nodes
    # and its longest live list, the width each row propagates over
    lives = [dix.max_live_nodes] * 3 + [sweep_dix.max_live_nodes]
    assert lives[0] < widest and lives[3] < sweep_dix.num_nodes
    assert [s.attrs["live_width"] for s in waits] == lives
    assert metrics.counter("live_width") == sum(lives)
    # three live spans per launch, in order, on this thread
    names = [s.name for s in tracer.spans() if s.name.startswith("executor.")]
    assert names == ["executor.dispatch", "executor.wait",
                     "executor.download"] * 4
    dispatched, waited = ex.last_launch()
    assert dispatched == pytest.approx(
        tracer.spans(name="executor.dispatch")[-1].t_start, abs=1e-3)
    assert waited >= waits[-1].t_end


def _engine(trace=True, **kw):
    return ServingEngine(EngineConfig(flush_ms=0.5, host_threshold=0,
                                      cache_capacity=0, trace=trace, **kw))


def _serve(eng, g, us):
    futs = eng.submit_specs("g", [TCCSQuery(u, 1, g.t_max, 2) for u in us])
    eng.flush()
    return [f.result(timeout=60) for f in futs]


def test_device_route_spans_and_histograms():
    g = gen_temporal_graph(n=40, m=300, t_max=10, seed=7)
    with _engine() as eng:
        eng.register_graph("g", g)
        eng.warmup("g")
        _serve(eng, g, range(8))              # before the reset
        eng.metrics.reset()
        eng.tracer.clear()
        t0 = time.perf_counter()
        _serve(eng, g, range(8, 16))
        _serve(eng, g, range(16, 24))
        elapsed = time.perf_counter() - t0
        m = eng.metrics
        assert m.counter("device_batches") == 2
        assert m.histogram("device_assemble").count == 2
        # the first launch's gap began before the reset: one sample
        gap = m.histogram("launch_gap")
        assert gap.count == 1 and 0 < gap.total < elapsed
        assert m.counter("jump_launches") == 2
        names = [s.name for s in eng.tracer.spans()
                 if s.parent_id is None and s.cat == "serving"
                 and s.name != "query"]
        assert names == ["executor.dispatch", "executor.wait",
                         "executor.download", "planner.assemble",
                         "batcher.resolve"] * 2
        spans = {s.name: s for s in eng.tracer.spans()}
        assert all("batcher" in spans[n].thread_name for n in
                   ("executor.wait", "planner.assemble", "batcher.resolve"))
        (w1, w2) = eng.tracer.spans(name="executor.wait")
        (_, d2) = eng.tracer.spans(name="executor.dispatch")
        assert gap.total == pytest.approx(d2.t_start - w1.t_end, abs=1e-3)
        asm = eng.tracer.spans(name="planner.assemble")
        assert m.histogram("device_assemble").total <= \
            sum(s.duration_s for s in asm) + 1e-6


def test_tracer_off_records_timings_but_no_spans(monkeypatch):
    made = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda name: made.append(name))
    g = gen_temporal_graph(n=40, m=300, t_max=10, seed=7)
    with _engine(trace=False) as eng:
        eng.register_graph("g", g)
        stages = eng.registry.get("g").build_stages
        eng.warmup("g")
        eng.metrics.reset()
        _serve(eng, g, range(8))
        _serve(eng, g, range(8, 16))
        eng.sweep("g", WindowSweep(3, 2, [(d, d + 2) for d in
                                          range(1, g.t_max - 1)]))
        assert len(eng.tracer) == 0 and made == []
        assert set(SUBS) <= set(stages)
        m = eng.metrics
        assert m.counter("jump_launches") == 3      # the sweep counts too
        assert m.counter("jump_rounds") >= 3
        assert m.histogram("device_assemble").count == 2
        assert m.histogram("launch_gap").count == 1


def test_profiler_trace_holds_engine_spans(tmp_path):
    """A CPU profiler trace of one engine launch holds the engine's spans
    as host events, all on the one thread that made the launch."""
    from jax.profiler import ProfileData

    g = gen_temporal_graph(n=40, m=300, t_max=10, seed=7)
    with _engine() as eng:
        eng.register_graph("g", g)
        eng.warmup("g")
        jax.profiler.start_trace(str(tmp_path))
        try:
            _serve(eng, g, range(8))
        finally:
            jax.profiler.stop_trace()
    (path,) = pathlib.Path(tmp_path).rglob("*.xplane.pb")
    found = [[e.name for e in line.events if e.name.startswith("repro.")]
             for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines]
    found = [names for names in found if names]
    assert found == [["repro.executor.dispatch", "repro.executor.wait",
                      "repro.executor.download", "repro.planner.assemble",
                      "repro.batcher.resolve"]]


# ----------------------------------------------------------------------
# core-time build sub-stages
# ----------------------------------------------------------------------

@pytest.mark.parametrize("engine", ["host", "jax"])
def test_core_time_sub_stages(engine):
    g = gen_temporal_graph(n=30, m=240, t_max=12, seed=5)
    timings, tracer = {}, Tracer()
    t0 = time.perf_counter()
    with tracer.span("core_times") as parent:
        tab = stratified_core_times(g, engine=engine, timings=timings,
                                    span=parent)
    total = time.perf_counter() - t0
    assert set(timings) == set(SUBS)
    assert all(v >= 0 for v in timings.values())
    assert sum(timings.values()) <= total
    assert (timings["core_times.dispatch"] > 0) == (engine == "jax")
    assert timings["core_times.sweep"] > 0
    kids = {s.name for s in tracer.spans() if s.parent_id == parent.span_id}
    want = set(SUBS) - ({"core_times.dispatch"} if engine == "host" else set())
    assert kids == want
    # the timed build is the untimed one
    ref = stratified_core_times(g, engine=engine)
    for k in ref.ks:
        a, b = tab.table_for(k), ref.table_for(k)
        assert np.array_equal(a.ct, b.ct) and np.array_equal(a.ts_from,
                                                             b.ts_from)


def test_obs_imports_no_jax():
    code = ("import sys, repro.obs, repro.obs.trace, repro.obs.registry; "
            "print('jax' in sys.modules)")
    src = pathlib.Path(__file__).resolve().parents[1] / "src"
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env={"PYTHONPATH": str(src)}, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"
