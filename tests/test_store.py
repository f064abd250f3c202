"""Persistent index store (DESIGN.md §13): blob I/O, the segment/manifest
commit format with its delta classes, IndexStore roundtrips, and the
registry disk tier (write-through, promote, demote, warm restart)."""

import json
import os

import numpy as np
import pytest

from repro.core.temporal_graph import gen_temporal_graph
from repro.serving import EngineConfig, ServingEngine
from repro.serving.metrics import EngineMetrics
from repro.serving.registry import IndexRegistry
from repro.core.query_api import TCCSQuery
from repro.store import IndexStore, StoreCorruption
from repro.store import blobio
from repro.store import segment as seg
from repro.store.index_store import key_dirname

from test_streaming import assert_pecb_identical, split_epoch

TAB_FIELDS = ("kptr", "edge_id", "ts_from", "ts_to", "ct",
              "vptr", "v_ts_from", "v_ts_to", "v_ct")


def small_graph(seed=3):
    return gen_temporal_graph(n=40, m=320, t_max=20, seed=seed)


def build_handle(g, k=2, name="g"):
    """One cold-built IndexHandle via a throwaway registry (no store)."""
    reg = IndexRegistry()
    reg.register_graph(name, g)
    try:
        return reg.get(name)
    finally:
        reg.close()


def assert_handles_identical(a, b):
    assert_pecb_identical(a.pecb, b.pecb)
    assert a.epoch == b.epoch
    for f in TAB_FIELDS:
        assert np.array_equal(getattr(a.tab, f), getattr(b.tab, f)), f
    for f in ("src", "dst", "t"):
        assert np.array_equal(getattr(a.graph, f), getattr(b.graph, f)), f


# ----------------------------------------------------------------------
# blobio (the checkpoint manager shares these helpers — satellite 1)
# ----------------------------------------------------------------------

class TestBlobio:
    def test_atomic_write_roundtrip_no_tmp_left(self, tmp_path):
        p = str(tmp_path / "x.bin")
        blobio.atomic_write(p, b"hello-store")
        with open(p, "rb") as f:
            assert f.read() == b"hello-store"
        assert [n for n in os.listdir(tmp_path) if "tmp" in n] == []

    def test_array_blob_roundtrip(self):
        for a in (np.arange(17, dtype=np.int32),
                  np.linspace(0, 1, 9).reshape(3, 3),
                  np.zeros(0, dtype=np.int64)):
            b = blobio.blob_array(blobio.array_blob(a))
            assert b.dtype == a.dtype and b.shape == a.shape
            assert np.array_equal(b, a)

    def test_blob_crc_failure_detected(self):
        blob = blobio.array_blob(np.arange(8, dtype=np.int32))
        raw = bytearray(blob["raw"])
        raw[3] ^= 0xFF
        blob["raw"] = bytes(raw)
        with pytest.raises(IOError, match="crc32"):
            blobio.blob_array(blob)


# ----------------------------------------------------------------------
# segment/manifest format
# ----------------------------------------------------------------------

class TestSegmentFormat:
    def _commit(self, d, epoch, arrays, prev=None, **kw):
        return seg.write_commit(str(d), {"epoch": epoch}, arrays, prev, **kw)

    def test_full_commit_roundtrip(self, tmp_path):
        arrays = {"a": np.arange(100, dtype=np.int32),
                  "b": np.linspace(0, 1, 33),
                  "c": np.arange(12, dtype=np.int64).reshape(3, 4)}
        res = self._commit(tmp_path, 0, arrays)
        assert res["mode"] == "full" and res["epoch"] == 0
        man, loaded, recovered = seg.open_latest(str(tmp_path))
        assert recovered == 0 and man["epoch"] == 0
        for name, a in arrays.items():
            got = loaded[name]
            assert got.dtype == a.dtype and got.shape == a.shape
            assert np.array_equal(got, a)

    def test_parts_are_aligned(self, tmp_path):
        arrays = {"a": np.arange(7, dtype=np.int32),
                  "b": np.arange(5, dtype=np.int64)}
        self._commit(tmp_path, 0, arrays)
        man, _, _ = seg.open_latest(str(tmp_path))
        for ent in man["arrays"].values():
            for p in ent["parts"]:
                assert p["offset"] % seg.ALIGN == 0

    def test_delta_reuse_suffix_prefix(self, tmp_path):
        a0 = {"keep": np.arange(200, dtype=np.int32),
              "grow": np.arange(300, dtype=np.int32),
              "front": np.arange(100, 300, dtype=np.int32)}
        self._commit(tmp_path, 0, a0)
        man0, arr0, _ = seg.open_latest(str(tmp_path))
        a1 = {"keep": a0["keep"],
              "grow": np.concatenate([a0["grow"],
                                      np.arange(300, 340, dtype=np.int32)]),
              "front": np.arange(100, 300, dtype=np.int32)}
        a1["front"] = np.concatenate([np.arange(50, 100, dtype=np.int32),
                                      a0["front"]])
        res = self._commit(tmp_path, 1, a1, prev=(man0, arr0))
        assert res["mode"] == "delta"
        man1, arr1, _ = seg.open_latest(str(tmp_path))
        assert man1["epoch"] == 1
        # reuse: single part still living in the epoch-0 segment
        keep_parts = man1["arrays"]["keep"]["parts"]
        assert len(keep_parts) == 1
        assert keep_parts[0]["segment"] == man0["arrays"]["keep"]["parts"][0]["segment"]
        # suffix: old part first, tail appended in the new segment
        grow_parts = man1["arrays"]["grow"]["parts"]
        assert len(grow_parts) == 2
        assert grow_parts[1]["segment"] != grow_parts[0]["segment"]
        # prefix: new head first, old bytes second
        front_parts = man1["arrays"]["front"]["parts"]
        assert len(front_parts) == 2
        assert front_parts[0]["segment"] != front_parts[1]["segment"]
        for name, a in a1.items():
            assert np.array_equal(arr1[name], a), name
        # the delta wrote strictly less than a full rewrite would
        full = sum(a.nbytes for a in a1.values())
        assert res["bytes_written"] < full

    def test_full_change_falls_back_to_full_commit(self, tmp_path):
        a0 = {"x": np.arange(64, dtype=np.int32)}
        self._commit(tmp_path, 0, a0)
        man0, arr0, _ = seg.open_latest(str(tmp_path))
        a1 = {"x": a0["x"][::-1].copy()}   # same size, reordered: no delta
        res = self._commit(tmp_path, 1, a1, prev=(man0, arr0))
        assert res["mode"] == "full"
        _, arr1, _ = seg.open_latest(str(tmp_path))
        assert np.array_equal(arr1["x"], a1["x"])

    def test_chain_bound_forces_compaction(self, tmp_path):
        arrays = {"grow": np.arange(512, dtype=np.int32),
                  "pad": np.arange(4096, dtype=np.int32)}
        self._commit(tmp_path, 0, arrays)
        modes = []
        for e in range(1, 6):
            prev = seg.open_latest(str(tmp_path))
            arrays = {"grow": np.concatenate(
                          [arrays["grow"],
                           np.arange(8, dtype=np.int32)]),
                      "pad": arrays["pad"]}
            res = self._commit(tmp_path, e, arrays,
                               prev=(prev[0], prev[1]),
                               max_chain=3, keep_manifests=10)
            modes.append(res["mode"])
        # deltas until the referenced chain would exceed max_chain, then a
        # fresh full commit re-bases the chain and deltas resume
        assert "full" in modes and modes[0] == "delta"
        first_full = modes.index("full")
        assert all(m == "delta" for m in modes[:first_full])
        man, loaded, _ = seg.open_latest(str(tmp_path))
        assert np.array_equal(loaded["grow"], arrays["grow"])
        assert len(man["segments"]) <= 4

    def test_gc_drops_old_manifests_and_orphans(self, tmp_path):
        for e in range(4):
            self._commit(tmp_path, e,
                         {"x": np.arange(32 + e, dtype=np.int32)},
                         keep_manifests=2)
        names = os.listdir(tmp_path)
        assert len([n for n in names if n.startswith("manifest_")]) == 2
        # only the kept manifests' segments survive
        kept_segs = {n for n in names if n.startswith("seg_")}
        man, _, _ = seg.open_latest(str(tmp_path))
        assert set(man["segments"]) <= kept_segs
        assert len(kept_segs) == 2

    def test_next_seq_never_reuses_orphans(self, tmp_path):
        self._commit(tmp_path, 0, {"x": np.arange(8, dtype=np.int32)})
        (tmp_path / "seg_00000007.bin").write_bytes(b"orphan")
        assert seg.next_seq(str(tmp_path)) == 8


class TestSegmentRecovery:
    def _two_commits(self, d):
        a0 = {"x": np.arange(256, dtype=np.int32)}
        seg.write_commit(str(d), {"epoch": 0}, a0)
        a1 = {"x": np.arange(256, 512, dtype=np.int32)}
        seg.write_commit(str(d), {"epoch": 1}, a1)
        return a0, a1

    def test_corrupt_newest_segment_recovers_previous(self, tmp_path):
        a0, _ = self._two_commits(tmp_path)
        man, _, _ = seg.open_latest(str(tmp_path))
        target = tmp_path / man["arrays"]["x"]["parts"][0]["segment"]
        raw = bytearray(target.read_bytes())
        raw[5] ^= 0xFF
        target.write_bytes(bytes(raw))
        man2, loaded, recovered = seg.open_latest(str(tmp_path))
        assert man2["epoch"] == 0 and recovered == 1
        assert np.array_equal(loaded["x"], a0["x"])

    def test_truncated_manifest_recovers_previous(self, tmp_path):
        a0, _ = self._two_commits(tmp_path)
        newest = seg.list_manifests(str(tmp_path))[0][1]
        p = tmp_path / newest
        p.write_bytes(p.read_bytes()[:20])
        man, loaded, recovered = seg.open_latest(str(tmp_path))
        assert man["epoch"] == 0 and recovered == 1
        assert np.array_equal(loaded["x"], a0["x"])

    def test_missing_segment_recovers_previous(self, tmp_path):
        a0, _ = self._two_commits(tmp_path)
        man, _, _ = seg.open_latest(str(tmp_path))
        os.remove(tmp_path / man["arrays"]["x"]["parts"][0]["segment"])
        man2, loaded, recovered = seg.open_latest(str(tmp_path))
        assert man2["epoch"] == 0 and recovered == 1
        assert np.array_equal(loaded["x"], a0["x"])

    def test_stray_tmp_files_ignored(self, tmp_path):
        _, a1 = self._two_commits(tmp_path)
        (tmp_path / "seg_00000009.bin.tmp-123").write_bytes(b"partial")
        (tmp_path / "manifest_00000009.json.tmp-123").write_bytes(b"{")
        man, loaded, recovered = seg.open_latest(str(tmp_path))
        assert man["epoch"] == 1 and recovered == 0
        assert np.array_equal(loaded["x"], a1["x"])

    def test_empty_dir_is_a_miss(self, tmp_path):
        assert seg.open_latest(str(tmp_path)) is None
        assert seg.open_latest(str(tmp_path / "absent")) is None


# ----------------------------------------------------------------------
# IndexStore: handle <-> segment roundtrip
# ----------------------------------------------------------------------

class TestIndexStore:
    def test_put_load_roundtrip(self, tmp_path):
        g = small_graph()
        h = build_handle(g, k=2)
        store = IndexStore(str(tmp_path))
        res = store.put_handle("g", h)
        assert res["mode"] == "full" and res["epoch"] == 0
        assert store.current_epoch("g") == 0
        assert store.keys() == ["g"]
        stored = store.load("g")
        assert stored is not None and stored.recovered == 0
        assert_pecb_identical(stored.pecb, h.pecb)
        for f in TAB_FIELDS:
            assert np.array_equal(getattr(stored.tab, f), getattr(h.tab, f))
        for f in ("src", "dst", "t"):
            assert np.array_equal(getattr(stored.graph, f), getattr(g, f))
        st = store.stats()
        assert st["commits"] == 1 and st["commits_full"] == 1
        assert st["loads"] == 1 and st["load_bytes"] > 0

    def test_put_same_epoch_is_noop(self, tmp_path):
        h = build_handle(small_graph(), k=2)
        store = IndexStore(str(tmp_path))
        store.put_handle("g", h)
        res = store.put_handle("g", h)
        assert res["mode"] == "current" and res["bytes_written"] == 0
        assert store.stats()["commits_noop"] == 1

    def test_load_miss_returns_none(self, tmp_path):
        store = IndexStore(str(tmp_path))
        assert store.load("nope") is None
        assert store.current_epoch("nope") is None

    def test_key_dirname_sanitized_and_collision_proof(self):
        d1 = key_dirname("feed@2026/08")
        d2 = key_dirname("feed@2026_08")
        assert "/" not in d1 and d1 != d2

    def test_stored_answers_match_live_index(self, tmp_path):
        g = small_graph(seed=9)
        h = build_handle(g, k=2)
        store = IndexStore(str(tmp_path))
        store.put_handle("g", h)
        stored = store.load("g")
        rng = np.random.default_rng(0)
        for _ in range(25):
            u = int(rng.integers(0, g.n))
            ts = int(rng.integers(1, g.t_max))
            te = int(rng.integers(ts, g.t_max + 1))
            q = TCCSQuery(u, ts, te, 2)
            assert stored.pecb.answer(q).vertices == h.pecb.answer(q).vertices


# ----------------------------------------------------------------------
# registry disk tier: write-through, promote, demote, warm restart
# ----------------------------------------------------------------------

class TestRegistryDiskTier:
    def test_build_writes_through_then_promotes_on_restart(self, tmp_path):
        g = small_graph(seed=5)
        store_a = IndexStore(str(tmp_path))
        reg_a = IndexRegistry(store=store_a)
        reg_a.register_graph("w", g)
        h_a = reg_a.get("w")
        reg_a.close()
        assert h_a.source == "build"
        assert store_a.stats()["commits"] == 1   # write-through, no demote

        # "restart": fresh registry + fresh store object over the same root
        reg_b = IndexRegistry(store=IndexStore(str(tmp_path)))
        reg_b.register_graph("w", g)
        h_b = reg_b.get("w")
        reg_b.close()
        assert h_b.source == "disk"
        assert reg_b.builds == 0 and reg_b.promotions == 1
        assert_handles_identical(h_b, h_a)

    def test_stale_store_falls_back_to_cold_build(self, tmp_path):
        store = IndexStore(str(tmp_path))
        reg_a = IndexRegistry(store=store)
        reg_a.register_graph("w", small_graph(seed=5))
        reg_a.get("w")
        reg_a.close()
        # same name, different graph: promotion must refuse the stored epoch
        reg_b = IndexRegistry(store=IndexStore(str(tmp_path)))
        reg_b.register_graph("w", small_graph(seed=6))
        h = reg_b.get("w")
        reg_b.close()
        assert h.source == "build"
        assert reg_b.promotions == 0 and reg_b.builds == 1

    def test_evict_demotes_and_promote_counts_metrics(self, tmp_path):
        metrics = EngineMetrics()
        store = IndexStore(str(tmp_path), metrics=metrics)
        reg = IndexRegistry(capacity=1, metrics=metrics, store=store)
        reg.register_graph("a", small_graph(seed=1))
        reg.register_graph("b", small_graph(seed=2))
        h_a = reg.get("a")
        reg.get("b")              # evicts ("a", 2) -> demote
        assert "a" not in reg
        assert reg.stats()["demotions"] == 1
        h_a2 = reg.get("a")       # promoted back, evicting+demoting b
        reg.close()
        assert h_a2.source == "disk"
        assert reg.promotions == 1 and reg.builds == 2
        assert_handles_identical(h_a2, h_a)
        snap = metrics.snapshot(include_sources=False)["counters"]
        assert snap["evictions_demoted"] == 2
        assert snap["promotions"] == 1
        # write-through made both demotions cheap manifest probes
        assert snap.get("demote_bytes", 0) == 0
        assert snap["store_commits"] == 2 and snap["store_loads"] >= 1

    def test_epoch_lifecycle_deltas_and_warm_reopen(self, tmp_path):
        g = small_graph(seed=7)
        g0, suffix = split_epoch(g, 0.7)
        store = IndexStore(str(tmp_path))
        reg = IndexRegistry(store=store)
        reg.register_graph("feed", g0)
        reg.get("feed")
        for fut in reg.extend_graph("feed", suffix).values():
            fut.result(timeout=60)
        t_cut = max(2, g.t_max // 4)
        for fut in reg.retain("feed", t_cut).values():
            fut.result(timeout=60)
        h_live = reg.get("feed")
        g_final = reg.resolve_graph("feed")
        reg.close()
        assert h_live.epoch == 2
        st = store.stats()
        assert st["commits"] == 3
        assert st["commits_delta"] >= 1    # the suffix ingest deltas

        # warm reopen WITHOUT register_graph: resolve_graph adopts the
        # stored graph + epoch, the build promotes the stored index
        reg2 = IndexRegistry(store=IndexStore(str(tmp_path)))
        h2 = reg2.get("feed")
        assert h2.source == "disk" and h2.epoch == 2
        assert_handles_identical(h2, h_live)
        g2 = reg2.resolve_graph("feed")
        assert np.array_equal(g2.t, g_final.t)
        # the adopted graph keeps ingesting from the stored epoch
        nxt = g2.t_max + 1
        futs = reg2.extend_graph(
            "feed", [(int(g2.src[0]), int(g2.dst[0]), nxt)])
        h3 = futs["feed"].result(timeout=60)
        reg2.close()
        assert h3.epoch == 3 and h3.pecb.t_max == nxt

        # and the delta-chained commits replay to a cold-build-identical
        # index on a third open
        fresh = IndexStore(str(tmp_path)).load("feed")
        assert fresh.epoch == 3
        h_cold = build_handle(reg2.resolve_graph("feed"), k=2)
        assert_pecb_identical(fresh.pecb, h_cold.pecb)

    def test_promoted_handle_stamps_disk_provenance(self, tmp_path):
        g = small_graph(seed=11)
        with ServingEngine(EngineConfig(store_dir=str(tmp_path),
                                        flush_ms=1.0)) as eng:
            eng.register_graph("w", g)
            eng.warmup("w")
            res = eng.answer("w", TCCSQuery(0, 1, g.t_max, 2))
            assert res.provenance.route != "disk"
        with ServingEngine(EngineConfig(store_dir=str(tmp_path),
                                        flush_ms=1.0)) as eng:
            eng.register_graph("w", g)
            eng.warmup("w")
            res = eng.answer("w", TCCSQuery(0, 1, g.t_max, 2))
            assert res.provenance.route == "disk"
            stats = eng.stats()
            assert stats["registry"]["promotions"] == 1
            assert stats["store"]["loads"] >= 1
            snap = eng.metrics.snapshot()
            assert snap["sources"]["store"]["commits_noop"] >= 0
            assert "index_promote" in snap["latency"]

    def test_store_failure_degrades_to_build(self, tmp_path):
        class BrokenStore(IndexStore):
            def load(self, key):
                raise OSError("disk on fire")

            def put_handle(self, key, handle, prev=None):
                raise OSError("disk on fire")

        metrics = EngineMetrics()
        reg = IndexRegistry(store=BrokenStore(str(tmp_path)),
                            metrics=metrics)
        reg.register_graph("w", small_graph(seed=4))
        h = reg.get("w")
        reg.close()
        assert h.source == "build" and reg.builds == 1
        snap = metrics.snapshot(include_sources=False)["counters"]
        assert snap["store_load_failures"] == 1
        assert snap["store_commit_failures"] == 1

    def test_device_upload_failure_in_promote_propagates(self, tmp_path,
                                                         monkeypatch):
        from repro.serving import registry as registry_mod
        g = small_graph(seed=5)
        reg = IndexRegistry(store=IndexStore(str(tmp_path)))
        reg.register_graph("w", g)
        reg.get("w")
        reg.close()

        def broken_upload(index):
            raise RuntimeError("device lost")

        monkeypatch.setattr(registry_mod, "to_device", broken_upload)
        metrics = EngineMetrics()
        reg = IndexRegistry(store=IndexStore(str(tmp_path)), metrics=metrics)
        reg.register_graph("w", g)
        with pytest.raises(RuntimeError, match="device lost"):
            reg.get("w")
        reg.close()
        assert reg.builds == 0 and reg.promotions == 0
        snap = metrics.snapshot(include_sources=False)["counters"]
        assert "store_load_failures" not in snap
