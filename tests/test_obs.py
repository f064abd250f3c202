"""Observability-plane tests (DESIGN.md §11): span-context propagation
across the batcher and registry FIFO-refresh thread boundaries, Chrome
trace export schema, unified metrics snapshot round-trip, slow-query log,
compile-event tracking, histogram thread safety, bench artifact schema."""

import json
import threading
import time

import numpy as np
import pytest

from repro.core.query_api import EMPTY_WINDOW, TCCSQuery, WindowSweep
from repro.core.temporal_graph import gen_temporal_graph
from repro.obs import (NULL_SPAN, LatencyHistogram, MetricsRegistry,
                       SlowQueryLog, Tracer, chrome_trace_events,
                       metrics_from_json, metrics_to_json,
                       validate_chrome_trace, write_chrome_trace)
from repro.obs.export import trace_document
from repro.serving import EngineConfig, EngineMetrics, ServingEngine


# ----------------------------------------------------------------------
# LatencyHistogram: thread safety + interpolated percentiles
# ----------------------------------------------------------------------

class TestLatencyHistogram:
    def test_concurrent_adds_lose_nothing(self):
        """The §11.4 audit regression: adds from many threads land under
        the histogram's own lock — exact count/total, no dropped or
        duplicated reservoir slots below the cap."""
        h = LatencyHistogram(cap=100_000)
        n_threads, per_thread = 8, 2_000

        def feed(t):
            for i in range(per_thread):
                h.add((t * per_thread + i) * 1e-6)

        threads = [threading.Thread(target=feed, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        total_n = n_threads * per_thread
        assert h.count == total_n
        assert h.total == pytest.approx(
            sum(i * 1e-6 for i in range(total_n)))
        assert len(h._samples) == total_n     # under cap: every sample kept

    def test_concurrent_adds_respect_reservoir_cap(self):
        h = LatencyHistogram(cap=64)
        threads = [threading.Thread(
            target=lambda: [h.add(0.001) for _ in range(500)])
            for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert h.count == 2_000
        assert len(h._samples) == 64

    def test_linear_interpolation_matches_numpy(self):
        h = LatencyHistogram()
        samples = [0.010, 0.020, 0.030, 0.040]
        for s in samples:
            h.add(s)
        for q in (0, 25, 50, 75, 90, 99, 100):
            assert h.percentile(q) == pytest.approx(
                float(np.percentile(samples, q)))
        # p50 of 4 samples interpolates between the middle two — the
        # nearest-rank convention would snap to one of them
        assert h.percentile(50) == pytest.approx(0.025)

    def test_empty_summary(self):
        s = LatencyHistogram().summary()
        assert s["count"] == 0 and s["p99_ms"] == 0.0


# ----------------------------------------------------------------------
# Tracer: span trees, propagation rules, ring bounds
# ----------------------------------------------------------------------

class TestTracer:
    def test_root_and_explicit_child(self):
        tr = Tracer()
        root = tr.start_span("query", parent=None)
        assert root.trace_id == root.span_id and root.parent_id is None
        child = root.child("queue")
        child.end()
        root.end()
        assert child.trace_id == root.trace_id
        assert child.parent_id == root.span_id
        assert {s.name for s in tr.spans(trace_id=root.trace_id)} == \
            {"query", "queue"}

    def test_implicit_thread_local_parent(self):
        tr = Tracer()
        with tr.span("outer") as outer:
            inner = tr.start_span("inner")
            inner.end()
        assert inner.parent_id == outer.span_id
        # after exit nothing is current: new spans are roots
        after = tr.start_span("after")
        after.end()
        assert after.parent_id is None

    def test_context_does_not_leak_across_threads(self):
        tr = Tracer()
        seen = {}

        def worker():
            s = tr.start_span("w")     # no explicit parent, other thread
            s.end()
            seen["parent"] = s.parent_id

        with tr.span("outer"):
            t = threading.Thread(target=worker)
            t.start()
            t.join()
        assert seen["parent"] is None   # thread identity means nothing

    def test_cross_thread_explicit_ctx(self):
        tr = Tracer()
        root = tr.start_span("root", parent=None)
        out = {}

        def worker(ctx):
            s = tr.start_span("bg", parent=ctx)
            s.end()
            out["ids"] = (s.trace_id, s.parent_id)

        t = threading.Thread(target=worker, args=(root.ctx,))
        t.start()
        t.join()
        assert out["ids"] == (root.trace_id, root.span_id)

    def test_ring_buffer_bounds_and_drop_count(self):
        tr = Tracer(capacity=10)
        for i in range(25):
            tr.start_span(f"s{i}", parent=None).end()
        assert len(tr) == 10
        assert tr.dropped == 15
        assert [s.name for s in tr.spans()] == [f"s{i}" for i in range(15, 25)]

    def test_disabled_tracer_hands_out_null_span(self):
        tr = Tracer(enabled=False)
        s = tr.start_span("x")
        assert s is NULL_SPAN
        assert s.child("y") is NULL_SPAN and s.set("a", 1) is NULL_SPAN
        assert s.ids == (None, None) and s.ctx is None
        s.end()
        assert len(tr) == 0

    def test_end_is_idempotent_and_clamps(self):
        tr = Tracer()
        s = tr.start_span("x", parent=None)
        s.end()
        first = s.t_end
        s.end()
        assert s.t_end == first and len(tr) == 1
        # retrospective span whose end predates its (backdated) start
        t_now = time.perf_counter()
        s2 = tr.start_span("y", parent=None, t0=t_now + 10.0)
        s2.end(t_now)
        assert s2.t_end == s2.t_start

    def test_error_recorded_on_context_exit(self):
        tr = Tracer()
        with pytest.raises(ValueError):
            with tr.span("boom"):
                raise ValueError("nope")
        (s,) = tr.spans()
        assert "nope" in s.attrs["error"]

    def test_annotate_only_live_with_spans(self):
        """A ``with`` span enters ``annotate("repro." + name)`` and exits
        it when it ends, nested in order; a backdated span, a span never
        entered and a disabled tracer annotate nothing."""
        events = []

        class Fake:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                events.append(("enter", self.name))

            def __exit__(self, *exc):
                events.append(("exit", self.name))

        tr = Tracer(annotate=Fake)
        with tr.span("outer") as outer:
            with outer.child("inner"):
                events.append(("work", None))
            with tr.span("queue", t0=time.perf_counter() - 1.0):
                pass                                  # backdated
            tr.start_span("open").end()               # never entered
        assert events == [("enter", "repro.outer"), ("enter", "repro.inner"),
                          ("work", None), ("exit", "repro.inner"),
                          ("exit", "repro.outer")]
        assert len(tr) == 4
        with pytest.raises(ValueError):
            with tr.span("boom"):
                raise ValueError("nope")
        assert events[-2:] == [("enter", "repro.boom"), ("exit", "repro.boom")]
        events.clear()
        off = Tracer(enabled=False, annotate=Fake)
        with off.span("x") as s:
            with s.child("y"):
                pass
        assert events == [] and len(off) == 0


# ----------------------------------------------------------------------
# Chrome trace export
# ----------------------------------------------------------------------

class TestChromeExport:
    def _tracer_with_tree(self):
        tr = Tracer()
        root = tr.start_span("query", parent=None, u=3)
        root.child("queue").end()
        root.child("execute", route="device", bucket=8).end()
        root.end()
        return tr

    def test_export_schema_and_linkage(self, tmp_path):
        tr = self._tracer_with_tree()
        path = tmp_path / "trace.json"
        doc = write_chrome_trace(str(path), tr)
        assert validate_chrome_trace(doc) == len(doc["traceEvents"])
        on_disk = json.loads(path.read_text())
        assert validate_chrome_trace(on_disk) == len(doc["traceEvents"])
        x = [e for e in on_disk["traceEvents"] if e["ph"] == "X"]
        assert {e["name"] for e in x} == {"query", "queue", "execute"}
        root = next(e for e in x if e["name"] == "query")
        for e in x:
            assert e["args"]["trace_id"] == root["args"]["span_id"]
        child = next(e for e in x if e["name"] == "queue")
        assert child["args"]["parent_id"] == root["args"]["span_id"]
        meta = [e for e in on_disk["traceEvents"] if e["ph"] == "M"]
        assert meta and all(e["name"] == "thread_name" for e in meta)
        assert on_disk["otherData"]["dropped_spans"] == 0

    def test_open_spans_are_skipped(self):
        tr = Tracer()
        root = tr.start_span("open", parent=None)
        root.child("done").end()
        events = chrome_trace_events(tr.spans(), t0=tr.t0)
        assert {e["name"] for e in events if e["ph"] == "X"} == {"done"}

    def test_validator_rejects_malformed(self):
        good = trace_document(self._tracer_with_tree())
        with pytest.raises(ValueError):
            validate_chrome_trace({"notTraceEvents": []})
        with pytest.raises(ValueError):
            validate_chrome_trace(42)
        bad = json.loads(json.dumps(good))
        bad["traceEvents"][0]["ph"] = "Z"
        with pytest.raises(ValueError):
            validate_chrome_trace(bad)
        bad = json.loads(json.dumps(good))
        bad["traceEvents"][0]["ts"] = -5
        with pytest.raises(ValueError):
            validate_chrome_trace(bad)
        bad = json.loads(json.dumps(good))
        del bad["traceEvents"][0]["name"]
        with pytest.raises(ValueError):
            validate_chrome_trace(bad)

    def test_nonjson_attrs_flatten(self):
        tr = Tracer()
        s = tr.start_span("x", parent=None, key=("feed", 2),
                          obj=object())
        s.end()
        doc = trace_document(tr)
        validate_chrome_trace(doc)       # round-trips despite exotic attrs


# ----------------------------------------------------------------------
# MetricsRegistry + snapshot export
# ----------------------------------------------------------------------

class TestMetricsRegistry:
    def test_counters_gauges_hists_sources(self):
        m = MetricsRegistry()
        m.count("queries")
        m.count("queries", 4)
        m.gauge("depth", 7)
        m.gauge("lazy", lambda: 42)
        m.observe("e2e", 0.010)
        m.register_source("cache", lambda: {"size": 3})
        snap = m.snapshot()
        assert snap["counters"]["queries"] == 5
        assert snap["gauges"] == {"depth": 7, "lazy": 42}
        assert snap["latency"]["e2e"]["count"] == 1
        assert snap["sources"]["cache"] == {"size": 3}
        assert "sources" not in m.snapshot(include_sources=False)
        m.reset()
        assert m.counter("queries") == 0
        assert m.snapshot()["sources"]["cache"] == {"size": 3}  # sources stay

    def test_engine_metrics_is_registry(self):
        assert issubclass(EngineMetrics, MetricsRegistry)

    def test_json_round_trip(self):
        m = MetricsRegistry()
        m.count("a", 3)
        m.observe("lat", 0.002)
        m.register_source("reg", lambda: {
            "resident": [("feed", 2)], "bytes": np.int64(128)})
        snap = m.snapshot()
        back = metrics_from_json(metrics_to_json(snap))
        assert back["counters"]["a"] == 3
        assert back["latency"]["lat"]["count"] == 1
        assert back["sources"]["reg"]["resident"] == [["feed", 2]]
        assert back["sources"]["reg"]["bytes"] == 128

    def test_non_string_keys_rejected(self):
        with pytest.raises(ValueError):
            metrics_to_json({"sources": {("feed", 2): 1}})


# ----------------------------------------------------------------------
# Engine integration: the full foreground span chain
# ----------------------------------------------------------------------

def _graph(seed=51):
    return gen_temporal_graph(n=40, m=420, t_max=18, seed=seed)


def _names_by_trace(tracer):
    out = {}
    for s in tracer.spans():
        out.setdefault(s.trace_id, set()).add(s.name)
    return out


class TestEngineTracing:
    def test_full_chain_and_provenance_linkage(self):
        g = _graph()
        cfg = EngineConfig(flush_ms=0.5, host_threshold=0, cache_capacity=0)
        with ServingEngine(cfg) as eng:
            eng.register_graph("g", g)
            eng.warmup("g")
            futs = eng.submit_specs(
                "g", [TCCSQuery(u, 1, g.t_max, 2) for u in range(24)])
            eng.flush()
            results = [f.result(timeout=60) for f in futs]
            by_trace = _names_by_trace(eng.tracer)
            for r in results:
                prov = r.provenance
                assert prov.trace_id is not None
                # provenance links the ROOT query span
                roots = [s for s in eng.tracer.spans(trace_id=prov.trace_id)
                         if s.span_id == prov.span_id]
                assert len(roots) == 1 and roots[0].name == "query"
                assert roots[0].attrs["route"] == "device"
                assert by_trace[prov.trace_id] == {"query", "queue",
                                                   "execute"}

    def test_queue_span_crosses_batcher_thread(self):
        """The root span starts on the caller thread; queue/execute
        children are recorded from the batcher worker — same trace, two
        distinct thread ids (explicit ctx propagation, §11.2)."""
        g = _graph()
        cfg = EngineConfig(flush_ms=0.5, host_threshold=0, cache_capacity=0)
        with ServingEngine(cfg) as eng:
            eng.register_graph("g", g)
            eng.warmup("g")
            futs = eng.submit_specs(
                "g", [TCCSQuery(u, 1, g.t_max, 2) for u in range(12)])
            eng.flush()
            res = [f.result(timeout=60) for f in futs]
            tr_id = res[0].provenance.trace_id
            spans = {s.name: s for s in eng.tracer.spans(trace_id=tr_id)}
            root, q = spans["query"], spans["queue"]
            assert q.parent_id == root.span_id
            assert q.tid != root.tid
            assert "batcher" in q.thread_name
            # the retrospective queue span covers the enqueue -> execute gap
            assert q.t_start >= root.t_start
            assert spans["execute"].attrs["route"] == "device"

    def test_cache_hit_and_trivial_routes_are_traced(self):
        g = _graph()
        with ServingEngine(EngineConfig(flush_ms=0.5)) as eng:
            eng.register_graph("g", g)
            eng.warmup("g")
            spec = TCCSQuery(3, 1, g.t_max, 2)
            r1 = eng.answer("g", spec)
            r2 = eng.answer("g", spec)              # cache hit
            assert r2.provenance.route == "cache"
            assert r2.provenance.trace_id != r1.provenance.trace_id
            names = _names_by_trace(eng.tracer)[r2.provenance.trace_id]
            assert names == {"query", "cache"}
            r3 = eng.answer("g", TCCSQuery(3, *EMPTY_WINDOW, 2))
            assert r3.provenance.route == "trivial"
            assert r3.provenance.trace_id is not None
            roots = eng.tracer.spans(trace_id=r3.provenance.trace_id)
            assert roots[0].attrs["route"] == "trivial"

    def test_host_route_chain(self):
        g = _graph()
        cfg = EngineConfig(flush_ms=0.5, host_threshold=512,
                           cache_capacity=0)
        with ServingEngine(cfg) as eng:
            eng.register_graph("g", g)
            r = eng.answer("g", TCCSQuery(5, 1, g.t_max, 2))
            spans = {s.name: s
                     for s in eng.tracer.spans(trace_id=r.provenance.trace_id)}
            assert spans["execute"].attrs["route"] == "host"
            assert spans["query"].span_id == r.provenance.span_id

    def test_sweep_root_span(self):
        g = _graph()
        with ServingEngine(EngineConfig(flush_ms=0.5)) as eng:
            eng.register_graph("g", g)
            eng.warmup("g", sweep=True, sweep_ks=(2,))
            res = eng.sweep("g", WindowSweep(
                u=3, k=2, windows=[(t, min(t + 4, g.t_max))
                                   for t in range(1, 14)]))
            tr_id = next(r.provenance.trace_id for r in res
                         if r.provenance.route == "sweep")
            spans = eng.tracer.spans(trace_id=tr_id)
            root = next(s for s in spans if s.name == "sweep")
            assert root.attrs["windows"] == 13
            ex = [s for s in spans if s.name == "execute"]
            assert ex and all(s.parent_id == root.span_id for s in ex)

    def test_tracing_disabled_serves_identically(self):
        g = _graph()
        cfg = EngineConfig(flush_ms=0.5, trace=False)
        with ServingEngine(cfg) as eng:
            eng.register_graph("g", g)
            r = eng.answer("g", TCCSQuery(5, 1, g.t_max, 2))
            assert r.provenance.trace_id is None
            assert len(eng.tracer) == 0
            assert eng.stats()["trace"]["enabled"] is False

    def test_engine_export_and_unified_snapshot(self, tmp_path):
        g = _graph()
        with ServingEngine(EngineConfig(flush_ms=0.5)) as eng:
            eng.register_graph("g", g)
            eng.answer("g", TCCSQuery(5, 1, g.t_max, 2))
            doc = eng.export_trace(str(tmp_path / "t.json"))
            assert validate_chrome_trace(doc) > 0
            snap = eng.metrics.snapshot()
            assert set(snap["sources"]) == {"cache", "registry"}
            assert snap["sources"]["cache"]["size"] >= 1
            assert snap["sources"]["registry"]["builds"] == 1
            metrics_from_json(metrics_to_json(snap))   # exports clean
            s = eng.stats()
            assert s["trace"]["spans"] == len(eng.tracer)
            assert s["slow_queries"] == 0


# ----------------------------------------------------------------------
# Background planes: builds, ingest refresh, retention
# ----------------------------------------------------------------------

class TestBackgroundTracing:
    def test_index_build_span_from_build_pool(self):
        g = _graph()
        with ServingEngine(EngineConfig(flush_ms=0.5)) as eng:
            eng.register_graph("g", g)
            eng.registry.get("g")
            (b,) = eng.tracer.spans(name="index_build")
            assert b.cat == "index" and b.parent_id is None
            assert "build-pool" in b.thread_name
            kids = [s for s in eng.tracer.spans()
                    if s.parent_id == b.span_id]
            assert {s.name for s in kids} == \
                {"core_times", "forest", "device"}

    def test_ingest_refresh_parented_across_fifo_worker(self):
        """A query racing an ingest: the query's spans pin the old epoch
        while the concurrent index_refresh span — recorded from the FIFO
        refresh worker thread — parents under the caller's ingest span."""
        g = _graph()
        with ServingEngine(EngineConfig(flush_ms=0.5)) as eng:
            eng.register_graph("g", g)
            eng.warmup("g")
            suffix = [(0, 1, g.t_max + 1), (1, 2, g.t_max + 2)]
            futures = eng.ingest("g", suffix)
            r = eng.answer("g", TCCSQuery(3, 1, g.t_max, 2))
            for f in futures.values():
                f.result(timeout=60)
            (ing,) = eng.tracer.spans(name="ingest")
            (ref,) = eng.tracer.spans(name="index_refresh")
            assert ing.cat == "epoch"
            assert ref.trace_id == ing.trace_id
            assert ref.parent_id == ing.span_id
            assert ref.tid != ing.tid
            assert "registry-refresh" in ref.thread_name
            assert ref.attrs["swapped"] is True and ref.attrs["epoch"] == 1
            stage_names = {s.name for s in eng.tracer.spans()
                           if s.parent_id == ref.span_id}
            assert stage_names == {"core_times", "forest", "device"}
            # the concurrent query is a separate trace with a full chain
            q_names = _names_by_trace(eng.tracer)[r.provenance.trace_id]
            assert "query" in q_names and r.provenance.trace_id != ing.trace_id

    def test_retention_span_parented_under_retain(self):
        g = _graph()
        with ServingEngine(EngineConfig(flush_ms=0.5)) as eng:
            eng.register_graph("g", g)
            eng.warmup("g")
            eng.retain("g", 6, wait=True)
            (ret,) = eng.tracer.spans(name="retain")
            (trim,) = eng.tracer.spans(name="index_retention")
            assert trim.trace_id == ret.trace_id
            assert trim.parent_id == ret.span_id
            assert trim.attrs["t_cut"] == 6 and trim.attrs["swapped"] is True


# ----------------------------------------------------------------------
# Slow-query log + compile tracking
# ----------------------------------------------------------------------

class TestSlowQueriesAndCompiles:
    def test_slow_query_log_captures_tree(self):
        g = _graph()
        cfg = EngineConfig(flush_ms=0.5, cache_capacity=0,
                           slow_query_ms=0.0)    # everything is "slow"
        with ServingEngine(cfg) as eng:
            eng.register_graph("g", g)
            eng.answer("g", TCCSQuery(5, 1, g.t_max, 2))
            assert len(eng.slow_queries) == 1
            (entry,) = eng.slow_queries.entries()
            assert "TCCSQuery" in entry["query"]
            assert entry["duration_ms"] >= 0
            names = {s["name"] for s in entry["spans"]}
            assert "query" in names and "execute" in names
            assert "slow query" in eng.slow_queries.format()

    def test_slow_query_log_threshold_filters(self):
        g = _graph()
        cfg = EngineConfig(flush_ms=0.5, slow_query_ms=60_000.0)
        with ServingEngine(cfg) as eng:
            eng.register_graph("g", g)
            eng.answer("g", TCCSQuery(5, 1, g.t_max, 2))
            assert len(eng.slow_queries) == 0

    def test_disabled_by_default(self):
        log = SlowQueryLog()
        assert not log.enabled
        assert log.observe(NULL_SPAN) is False

    def test_compile_events_recorded(self):
        """A fresh graph shape forces an XLA compile; the executor records
        it as a counter + a "compile"-category span (cache-size delta)."""
        # unusual n/t_max => shapes no earlier test compiled
        g = gen_temporal_graph(n=53, m=300, t_max=17, seed=97)
        with ServingEngine(EngineConfig(flush_ms=0.5,
                                        host_threshold=0)) as eng:
            eng.register_graph("g", g)
            eng.warmup("g")
            assert eng.metrics.counter("jit_compiles") > 0
            assert eng.metrics.counter("jit_compile_batch_query") > 0
            comp = eng.tracer.spans(name="jit_compile")
            assert comp and all(s.cat == "compile" for s in comp)
            assert comp[0].attrs["program"] == "batch_query"
            before = eng.metrics.counter("jit_compiles")
            eng.warmup("g")     # warm: no cache growth, no new events
            assert eng.metrics.counter("jit_compiles") == before


# ----------------------------------------------------------------------
# Bench artifact schema
# ----------------------------------------------------------------------

class TestBenchArtifacts:
    def test_artifact_round_trip(self, tmp_path):
        import sys
        sys.path.insert(0, str(__import__("pathlib").Path(__file__).parent.parent))
        from benchmarks.artifacts import (load_bench_json,
                                          validate_bench_artifact,
                                          write_bench_json)
        machine = {"platform": "test", "cpu_count": 1, "python": "3",
                   "jax": "0", "numpy": "0", "calib_s": 0.1}
        path = write_bench_json(
            str(tmp_path), "engine",
            {"open_loop_qps": (1000.0, "qps"), "p99": (2.5, "ms"),
             "coverage": (0.99, "frac")},
            {"load": (["a", "b"], [[1, 2], [3, 4]])}, machine)
        doc = load_bench_json(path)
        assert doc["metrics"]["open_loop_qps"]["normalized"] == \
            pytest.approx(100.0)
        assert doc["metrics"]["p99"]["normalized"] == \
            pytest.approx(0.0025 / 0.1)
        assert doc["metrics"]["coverage"]["normalized"] is None
        bad = json.loads(json.dumps(doc))
        bad["schema_version"] = 99
        with pytest.raises(ValueError):
            validate_bench_artifact(bad)
        bad = json.loads(json.dumps(doc))
        bad["tables"]["load"]["rows"][0] = [1]      # width mismatch
        with pytest.raises(ValueError):
            validate_bench_artifact(bad)

    def test_committed_artifacts_validate(self):
        """The BENCH_<area>.json files committed at the repo root must
        parse against the schema (the perf trajectory stays readable)."""
        import os
        import sys
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        sys.path.insert(0, repo)
        from benchmarks.artifacts import AREAS, validate_bench_files
        docs = validate_bench_files(repo, require=AREAS)
        assert set(docs) == set(AREAS)
        assert "span_chain_coverage" in docs["engine"]["metrics"]
        assert docs["engine"]["metrics"]["span_chain_coverage"]["value"] \
            >= 0.95
