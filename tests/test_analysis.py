"""Tests for the static-analysis suite + dynamic lock witness
(DESIGN.md §12).

Fixture files under ``tests/fixtures/analysis/`` are *parsed*, never
imported: each seeded violation pins its rule (and the clean twins pin
zero findings), so a pass that stops firing — or starts over-firing —
fails here before it lies in CI.
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading

import pytest

from repro.analysis import PASSES, AnalysisConfig, Baseline, run_analysis
from repro.analysis.core import Module
from repro.obs.locks import (LOCK_HIERARCHY, LockWitness, WitnessCondition,
                             WitnessLock, named_condition, named_lock,
                             witness_enabled)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = "tests/fixtures/analysis"


def analyze(rel_file: str, **overrides) -> list:
    """Run every pass over one fixture file with the repo config, include
    overridden to just that file."""
    config = AnalysisConfig.from_pyproject(REPO)
    config.include = (f"{FIXTURES}/{rel_file}",)
    for k, v in overrides.items():
        setattr(config, k, v)
    return run_analysis(REPO, config, PASSES)


def rules(findings) -> set[str]:
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# lock passes
# ---------------------------------------------------------------------------

class TestLockPassFixtures:
    def test_seeded_violations_all_detected(self):
        fs = analyze("lock_violations.py")
        by_rule: dict[str, list] = {}
        for f in fs:
            by_rule.setdefault(f.rule, []).append(f)
        # rank inversion, unnamed-under-named, unknown level, receiver map
        assert len(by_rule["lock-order"]) == 4
        # Future.result, block_until_ready, open()
        assert len(by_rule["lock-blocking-call"]) == 3

    def test_inversion_message_names_both_levels_and_ranks(self):
        fs = [f for f in analyze("lock_violations.py")
              if f.rule == "lock-order" and "cache" in f.message
              and "metrics" in f.message]
        assert fs, "cache-under-metrics inversion not detected"
        assert "strictly increasing" in fs[0].message

    def test_clean_fixture_has_zero_findings(self):
        assert analyze("lock_clean.py") == []

    def test_findings_carry_location_and_symbol(self):
        fs = analyze("lock_violations.py")
        f = next(f for f in fs if f.rule == "lock-blocking-call"
                 and "Future.result" in f.message)
        assert f.path.endswith("lock_violations.py")
        assert f.symbol == "BadBlocking.waits_under_lock"
        assert f.line > 0 and f.fingerprint


# ---------------------------------------------------------------------------
# jax passes
# ---------------------------------------------------------------------------

class TestJaxPassFixtures:
    def test_seeded_violations_all_detected(self):
        fs = analyze("jax_violations.py")
        assert rules(fs) >= {"jit-assert", "jit-python-branch",
                             "jit-host-sync", "jit-mutable-closure",
                             "jit-unhashable-static"}

    def test_clean_fixture_has_zero_jax_findings(self):
        fs = analyze("jax_clean.py")
        # static-metadata branches (dix.num_nodes), lax.cond, host wrappers
        # and module constants must all stay silent
        assert not rules(fs) & {"jit-assert", "jit-python-branch",
                                "jit-host-sync", "jit-mutable-closure",
                                "jit-unhashable-static"}

    def test_hot_path_transfer_fires_only_on_listed_modules(self):
        mod = "tests.fixtures.analysis.lock_violations"
        hot = analyze("lock_violations.py", hot_path_modules=(mod,))
        cold = analyze("lock_violations.py")
        assert "hot-path-transfer" in rules(hot)      # block_until_ready
        assert "hot-path-transfer" not in rules(cold)

    def test_repo_batch_query_static_branches_stay_clean(self):
        """The real jitted programs branch on DeviceIndex aux_data
        (num_nodes etc.) — static at trace time, must not be flagged."""
        config = AnalysisConfig.from_pyproject(REPO)
        config.include = ("src/repro/core/batch_query.py",)
        fs = run_analysis(REPO, config, PASSES)
        assert "jit-python-branch" not in rules(fs)


# ---------------------------------------------------------------------------
# api passes
# ---------------------------------------------------------------------------

class TestApiPassFixtures:
    def test_seeded_violations_all_detected(self):
        mod = "tests.fixtures.analysis"
        # assert-exempt covers tests/ in the repo config; disable it so
        # the seeded bare-assert stays a true positive here
        fs = analyze("api_violations.py", wallclock_modules=(mod,),
                     assert_exempt=())
        by_rule: dict[str, list] = {}
        for f in fs:
            by_rule.setdefault(f.rule, []).append(f)
        assert len(by_rule["deprecated-shim"]) == 3
        assert len(by_rule["metrics-direct"]) == 2
        assert len(by_rule["wallclock-in-traced"]) == 1
        assert len(by_rule["bare-assert"]) == 1
        assert len(by_rule["per-k-key"]) == 6

    def test_clean_fixture_has_zero_findings(self):
        mod = "tests.fixtures.analysis"
        fs = analyze("api_clean.py", wallclock_modules=(mod,))
        assert fs == []

    def test_wallclock_rule_scoped_to_module_list(self):
        fs = analyze("api_violations.py")   # repo list: repro.serving/.obs
        assert "wallclock-in-traced" not in rules(fs)


# ---------------------------------------------------------------------------
# suppressions, baseline, CLI
# ---------------------------------------------------------------------------

class TestSuppressionAndBaseline:
    def test_inline_suppression_drops_the_finding(self, tmp_path):
        src = ("def f(x):\n"
               "    assert x > 0  # repro: ignore[bare-assert]\n"
               "    return x\n")
        mod = Module(str(tmp_path / "m.py"), "m.py", src)
        assert mod.suppressed(2, "bare-assert")
        assert not mod.suppressed(2, "lock-order")

    def test_line_above_suppression(self, tmp_path):
        src = ("def f(x):\n"
               "    # repro: ignore[bare-assert]\n"
               "    assert x > 0\n")
        mod = Module(str(tmp_path / "m.py"), "m.py", src)
        assert mod.suppressed(3, "bare-assert")

    def test_bare_ignore_suppresses_every_rule(self, tmp_path):
        src = "x = 1  # repro: ignore\n"
        mod = Module(str(tmp_path / "m.py"), "m.py", src)
        assert mod.suppressed(1, "anything")

    def test_suppression_respected_end_to_end(self):
        fs = analyze("api_clean.py")
        assert "bare-assert" not in rules(fs)   # fixture suppresses inline

    def test_baseline_round_trip(self, tmp_path):
        fs = analyze("api_violations.py")
        assert fs
        path = str(tmp_path / "baseline.json")
        Baseline.from_findings(fs, comment="fixture").save(path)
        loaded = Baseline.load(path)
        assert all(f.fingerprint in loaded for f in fs)
        # a fresh finding (different fingerprint) is not baselined
        assert "0" * 16 not in loaded

    def test_fingerprints_stable_across_unrelated_line_shifts(self):
        """Fingerprints hash line *text*, not line numbers."""
        fs1 = analyze("api_violations.py")
        fp = {f.fingerprint for f in fs1}
        fs2 = analyze("api_violations.py")
        assert fp == {f.fingerprint for f in fs2}

    def test_missing_baseline_file_is_empty(self, tmp_path):
        b = Baseline.load(str(tmp_path / "nope.json"))
        assert "anything" not in b


class TestCli:
    def test_strict_on_repo_tree_is_clean(self):
        """The acceptance gate: the shipped tree has zero non-baselined
        findings."""
        from repro.analysis.__main__ import main
        assert main(["--root", REPO, "--strict"]) == 0

    def test_json_artifact_shape(self, tmp_path, capsys):
        from repro.analysis.__main__ import main
        out = str(tmp_path / "findings.json")
        assert main(["--root", REPO, "--json", out]) == 0
        with open(out) as f:
            payload = json.load(f)
        assert set(payload) >= {"findings", "baselined", "fresh", "passes"}
        assert payload["fresh"] == 0

    def test_unknown_pass_is_usage_error(self):
        from repro.analysis.__main__ import main
        assert main(["--root", REPO, "--passes", "nonsense"]) == 2

    def test_pass_subset_runs(self, capsys):
        from repro.analysis.__main__ import main
        assert main(["--root", REPO, "--passes", "api"]) == 0

    def test_write_baseline_then_strict_passes(self, tmp_path):
        """Seeded violations + --write-baseline -> strict exits 0; the
        same findings without the baseline fail strict."""
        from repro.analysis.__main__ import main
        root = tmp_path
        (root / "pyproject.toml").write_text(
            '[tool.repro-analysis]\ninclude = ["bad.py"]\n'
            'baseline = "b.json"\n')
        (root / "bad.py").write_text("def f(x):\n    assert x\n    return x\n")
        assert main(["--root", str(root), "--strict"]) == 1
        assert main(["--root", str(root), "--write-baseline"]) == 0
        assert main(["--root", str(root), "--strict"]) == 0


# ---------------------------------------------------------------------------
# dynamic lock witness
# ---------------------------------------------------------------------------

class TestLockWitness:
    def test_ordered_acquisition_is_clean(self):
        w = LockWitness()
        reg = WitnessLock("registry", w)
        met = WitnessLock("metrics", w)
        with reg:
            with met:
                pass
        assert w.check() == []
        assert w.acquisitions == 2
        (edge,) = w.edges()
        assert (edge["outer"], edge["inner"]) == ("registry", "metrics")

    def test_deliberate_inversion_detected(self):
        """The acceptance-criteria case: acquire out of declared order."""
        w = LockWitness()
        met = WitnessLock("metrics", w)
        reg = WitnessLock("registry", w)
        with met:
            with reg:          # registry ranks ABOVE metrics: inversion
                pass
        problems = w.check()
        kinds = {p["kind"] for p in problems}
        assert "lock-order" in kinds
        inv = next(p for p in problems if p["kind"] == "lock-order")
        assert (inv["outer"], inv["inner"]) == ("metrics", "registry")
        assert inv["threads"]   # owning thread recorded for the report

    def test_undeclared_lock_detected(self):
        w = LockWitness()
        reg = WitnessLock("registry", w)
        rogue = WitnessLock("rogue", w)
        with reg:
            with rogue:
                pass
        assert any(p["kind"] == "undeclared-lock" for p in w.check())

    def test_cross_thread_cycle_detected(self):
        """Thread A takes registry->cache in declared order; thread B
        takes cache->registry. No single thread inverts twice the same
        way, but the union of edges cycles — a real deadlock shape."""
        w = LockWitness(hierarchy=("a", "b"))
        la = WitnessLock("a", w)
        lb = WitnessLock("b", w)
        with la:
            with lb:
                pass

        def other():
            with lb:
                with la:
                    pass

        t = threading.Thread(target=other, name="inverter")
        t.start()
        t.join()
        problems = w.check()
        assert any(p["kind"] == "lock-cycle" for p in problems)
        cyc = next(p for p in problems if p["kind"] == "lock-cycle")
        assert set(cyc["cycle"]) >= {"a", "b"}

    def test_per_thread_hold_stacks_do_not_interleave(self):
        """Two threads each holding one lock concurrently must not create
        a cross-thread 'nesting' edge."""
        w = LockWitness()
        reg = WitnessLock("registry", w)
        met = WitnessLock("metrics", w)
        barrier = threading.Barrier(2)

        def hold(lock):
            with lock:
                barrier.wait(timeout=10)
                barrier.wait(timeout=10)

        t1 = threading.Thread(target=hold, args=(reg,))
        t2 = threading.Thread(target=hold, args=(met,))
        t1.start(); t2.start()
        t1.join(); t2.join()
        assert w.edges() == []          # concurrent != nested
        assert w.check() == []

    def test_condition_wrapper_reports_monitor_sections(self):
        w = LockWitness()
        cond = WitnessCondition("batcher", w)
        met = WitnessLock("metrics", w)
        with cond:
            with met:                   # batcher -> metrics: declared edge
                pass
        assert w.check() == []
        (edge,) = w.edges()
        assert (edge["outer"], edge["inner"]) == ("batcher", "metrics")

    def test_condition_wait_notify_roundtrip(self):
        w = LockWitness()
        cond = WitnessCondition("batcher", w)
        state = {"go": False}

        def producer():
            with cond:
                state["go"] = True
                cond.notify_all()

        t = threading.Thread(target=producer)
        with cond:
            t.start()
            assert cond.wait_for(lambda: state["go"], timeout=10)
        t.join()
        assert w.check() == []

    def test_report_is_json_serializable(self):
        w = LockWitness()
        with WitnessLock("metrics", w):
            with WitnessLock("registry", w):
                pass
        json.dumps(w.report())          # must not raise

    def test_reset_clears_observations(self):
        w = LockWitness()
        with WitnessLock("metrics", w):
            with WitnessLock("registry", w):
                pass
        assert w.check()
        w.reset()
        assert w.check() == [] and w.edges() == []
        assert w.acquisitions == 0


class TestNamedFactories:
    def test_plain_primitives_when_witness_off(self, monkeypatch):
        monkeypatch.delenv("REPRO_LOCK_WITNESS", raising=False)
        assert not witness_enabled()
        lk = named_lock("registry")
        assert isinstance(lk, type(threading.Lock()))
        cd = named_condition("batcher")
        assert isinstance(cd, threading.Condition)

    def test_wrappers_when_witness_passed_explicitly(self):
        w = LockWitness()
        lk = named_lock("registry", witness=w)
        cd = named_condition("batcher", witness=w)
        assert isinstance(lk, WitnessLock)
        assert isinstance(cd, WitnessCondition)
        with lk:
            pass
        assert w.acquisitions == 1

    def test_env_arms_global_witness(self, monkeypatch):
        monkeypatch.setenv("REPRO_LOCK_WITNESS", "1")
        assert witness_enabled()
        lk = named_lock("registry")
        assert isinstance(lk, WitnessLock)

    def test_hierarchy_covers_every_subsystem(self):
        assert LOCK_HIERARCHY == (
            "engine", "registry", "batcher", "cache", "store", "metrics",
            "histogram", "slowlog", "tracer", "checkpoint")


class TestWitnessedServingPath:
    """End-to-end: a real engine built with the witness armed respects
    the declared hierarchy while serving queries + background builds."""

    def test_engine_serving_respects_hierarchy(self, monkeypatch):
        monkeypatch.setenv("REPRO_LOCK_WITNESS", "1")
        w = LockWitness()
        # route the factories at this process's global witness aside: use
        # a local witness by monkeypatching the module singleton so the
        # session-level gate never sees these deliberate test edges
        import repro.obs.locks as locks_mod
        monkeypatch.setattr(locks_mod, "WITNESS", w)

        from repro.core.query_api import TCCSQuery
        from repro.core.temporal_graph import TemporalGraph
        from repro.serving.engine import EngineConfig, ServingEngine
        import numpy as np

        src = np.array([0, 1, 2, 0, 1, 2, 3], np.int32)
        dst = np.array([1, 2, 0, 2, 3, 3, 0], np.int32)
        t = np.array([1, 2, 3, 4, 5, 6, 7], np.int32)
        g = TemporalGraph(n=4, src=src, dst=dst, t=t)
        with ServingEngine(EngineConfig(flush_ms=0.5)) as eng:
            eng.register_graph("g", g)
            eng.warmup("g")
            r = eng.answer("g", TCCSQuery(0, 1, 7, 2))
            assert r is not None
        assert w.acquisitions > 0
        assert w.check() == [], w.report()


# ---------------------------------------------------------------------------
# kernels passes (static half)
# ---------------------------------------------------------------------------

class TestKernelPassFixtures:
    def test_seeded_violations_all_detected(self):
        fs = analyze("kernel_violations.py")
        by_rule: dict[str, list] = {}
        for f in fs:
            by_rule.setdefault(f.rule, []).append(f)
        # unpadded ep // SLOT_BLOCK
        assert len(by_rule["pallas-grid-divisibility"]) == 1
        # index_map closing over the wrapper-local `start`
        assert len(by_rule["pallas-indexmap-closure"]) == 1
        # the (4096, 4096) f32 tile, in + out
        assert len(by_rule["pallas-vmem-budget"]) == 1
        # k_index*n + u product; int64 cumsum row_ptr wrapped back
        assert len(by_rule["int32-narrowing"]) == 2
        # float64 node_u, unprovable node_v, undeclared bogus_plane,
        # the aggregated missing-arrays finding (node_ct stays clean)
        assert len(by_rule["layout-contract"]) == 4

    def test_vmem_finding_reports_bytes_and_platform(self):
        f = next(f for f in analyze("kernel_violations.py")
                 if f.rule == "pallas-vmem-budget")
        assert "tpu" in f.message and " B " in f.message

    def test_clean_fixture_has_zero_kernel_findings(self):
        fs = analyze("kernel_clean.py")
        assert not rules(fs) & {"pallas-grid-divisibility",
                                "pallas-indexmap-closure",
                                "pallas-vmem-budget", "int32-narrowing",
                                "layout-contract"}

    def test_real_kernel_modules_stay_clean(self):
        """The shipped Pallas wrappers all pad before dividing, use pure
        index_maps and stay inside the VMEM budget (flash's conservative
        static estimate is suppressed inline with its reason)."""
        config = AnalysisConfig.from_pyproject(REPO)
        config.include = ("src/repro/kernels",)
        fs = run_analysis(REPO, config, PASSES)
        assert not [f for f in fs if f.rule.startswith("pallas-")]

    def test_batch_query_packed_math_routed_through_checked_caster(self):
        """Satellite: the PR-9 slot/row-pointer widening — no unguarded
        int32 narrowing anywhere in the device-layout builder."""
        config = AnalysisConfig.from_pyproject(REPO)
        config.include = ("src/repro/core/batch_query.py",)
        fs = run_analysis(REPO, config, PASSES)
        assert "int32-narrowing" not in rules(fs)
        assert "layout-contract" not in rules(fs)


class TestShapeflow:
    def _env(self, src: str):
        import ast
        from repro.analysis import shapeflow as sf
        tree = ast.parse(src)
        fn = next(n for n in ast.walk(tree)
                  if isinstance(n, ast.FunctionDef))
        return sf, fn, sf.function_env(fn, sf.module_int_consts(tree))

    def test_padding_idiom_proves_divisibility(self):
        sf, fn, env = self._env(
            "def f(w, block=256):\n"
            "    e = w.shape[0]\n"
            "    ep = int(np.ceil(max(e, 1) / block)) * block\n"
            "    g = ep // block\n")
        import ast
        ep = env.lin(ast.parse("ep", mode="eval").body)
        blk = env.lin(ast.parse("block", mode="eval").body)
        assert sf.divides(ep, blk)

    def test_unpadded_extent_does_not_divide(self):
        sf, fn, env = self._env(
            "def f(w, block=256):\n"
            "    e = w.shape[0]\n")
        import ast
        e = env.lin(ast.parse("e", mode="eval").body)
        blk = env.lin(ast.parse("block", mode="eval").body)
        assert not sf.divides(e, blk)

    def test_tuple_assignment_stays_arithmetic(self):
        """Mp, Kp = (ceil(M/bm)*bm, ceil(K/bk)*bk) binds element-wise —
        the matmul wrapper's idiom must not degrade to opaque atoms."""
        sf, fn, env = self._env(
            "def f(a, bm=128, bk=64):\n"
            "    M, K = a.shape\n"
            "    Mp, Kp = (int(np.ceil(M / bm)) * bm,\n"
            "              int(np.ceil(K / bk)) * bk)\n")
        import ast
        assert sf.divides(env.lin(ast.parse("Mp", mode="eval").body),
                          env.lin(ast.parse("bm", mode="eval").body))
        assert sf.divides(env.lin(ast.parse("Kp", mode="eval").body),
                          env.lin(ast.parse("bk", mode="eval").body))

    def test_sequence_repetition_is_not_a_product(self):
        import ast
        from repro.analysis import shapeflow as sf
        assert not sf.int_expr_has_product(
            ast.parse("[u] * w", mode="eval").body)
        assert sf.int_expr_has_product(
            ast.parse("k_index * n + u", mode="eval").body)

    def test_dtype_flow_through_preserving_ops(self):
        import ast
        from repro.analysis import shapeflow as sf
        sf_, fn, env = self._env(
            "def f(counts):\n"
            "    r = np.cumsum(counts.astype(np.int64))\n")
        assert env.dtype_of(ast.parse("r", mode="eval").body) == "int64"


# ---------------------------------------------------------------------------
# kernel witness (runtime half)
# ---------------------------------------------------------------------------

class TestKernelWitness:
    @pytest.fixture()
    def armed(self, monkeypatch):
        """Local witness wired into the decorators; the session gate never
        sees these deliberate test violations."""
        import repro.kernels.contracts as kc
        w = kc.KernelWitness()
        monkeypatch.setenv("REPRO_KERNEL_WITNESS", "1")
        monkeypatch.setattr(kc, "WITNESS", w)
        return w

    def test_disarmed_is_passthrough(self, monkeypatch):
        import numpy as np
        import repro.kernels.contracts as kc
        from repro.kernels.segmented_select import segmented_count_le
        monkeypatch.delenv("REPRO_KERNEL_WITNESS", raising=False)
        before = kc.WITNESS.calls
        w = np.array([1, 2, 3, 4], np.int32)
        seg = np.array([0, 0, 1, 1], np.int32)
        thr = np.array([2, 3], np.int32)
        segmented_count_le(w, seg, thr, 2)
        assert kc.WITNESS.calls == before

    def test_armed_clean_call_recorded(self, armed):
        import numpy as np
        from repro.kernels.segmented_select import segmented_count_le
        w = np.array([1, 2, 3, 4], np.int32)
        seg = np.array([0, 0, 1, 1], np.int32)
        thr = np.array([2, 3], np.int32)
        out = segmented_count_le(w, seg, thr, 2)
        assert list(np.asarray(out)) == [2, 1]
        assert armed.calls == 1
        assert armed.problems() == []
        assert armed.report()["kernels"]["segmented_count_le"]["calls"] == 1

    def test_arm_disarm_roundtrip(self, armed, monkeypatch):
        import numpy as np
        from repro.kernels.kcore_peel import degree_count
        src = np.array([0, 1], np.int32)
        dst = np.array([1, 2], np.int32)
        alive = np.ones(2, bool)
        degree_count(src, dst, alive, 3)
        assert armed.calls == 1
        monkeypatch.delenv("REPRO_KERNEL_WITNESS")
        degree_count(src, dst, alive, 3)
        assert armed.calls == 1          # disarmed call not recorded

    def test_symbol_conflict_detected(self, armed):
        import numpy as np
        from repro.kernels.kcore_peel import degree_count
        # src and dst declare the shared symbolic dim E; mismatched
        # lengths must surface as a shape-contract problem
        src = np.array([0, 1, 2], np.int32)
        dst = np.array([1, 2], np.int32)
        alive = np.ones(3, bool)
        try:
            degree_count(src, dst, alive, 3)
        except Exception:
            pass                          # the kernel itself may reject
        kinds = {p["kind"] for p in armed.problems()}
        assert "shape-contract" in kinds

    def test_dtype_violation_detected(self, armed):
        import numpy as np
        from repro.kernels.segmented_select import segmented_count_le
        w = np.array([1.5, 2.5], np.float64)   # ANY_INT expected
        seg = np.array([0, 0], np.int32)
        thr = np.array([2], np.int32)
        try:
            segmented_count_le(w, seg, thr, 1)
        except Exception:
            pass
        kinds = {p["kind"] for p in armed.problems()}
        assert "dtype-contract" in kinds

    def test_vmem_violation_detected(self, armed):
        import numpy as np
        from repro.kernels.segmented_select import segmented_count_le
        armed.vmem_budget = 16            # absurdly small budget
        w = np.array([1, 2], np.int32)
        seg = np.array([0, 0], np.int32)
        thr = np.array([2], np.int32)
        segmented_count_le(w, seg, thr, 1)
        kinds = {p["kind"] for p in armed.problems()}
        assert "vmem-budget" in kinds

    def test_violations_deduplicate(self, armed):
        import numpy as np
        from repro.kernels.segmented_select import segmented_count_le
        armed.vmem_budget = 16
        w = np.array([1, 2], np.int32)
        seg = np.array([0, 0], np.int32)
        thr = np.array([2], np.int32)
        for _ in range(3):
            segmented_count_le(w, seg, thr, 1)
        vmem = [p for p in armed.problems() if p["kind"] == "vmem-budget"]
        assert len(vmem) == 1 and vmem[0]["count"] == 3

    def test_report_is_json_serializable(self, armed):
        json.dumps(armed.report())

    def test_every_pallas_wrapper_carries_a_contract(self):
        """Coverage is assertable unarmed: each module-level Pallas
        wrapper registered its contract at import."""
        import repro.kernels.contracts as kc
        import repro.kernels.flash_attention  # noqa: F401
        import repro.kernels.kcore_peel  # noqa: F401
        import repro.kernels.label_prop  # noqa: F401
        import repro.kernels.segment_matmul  # noqa: F401
        import repro.kernels.segmented_select  # noqa: F401
        assert set(kc.CONTRACTS) >= {
            "segmented_count_le", "kth_smallest_pallas", "degree_count",
            "peel_round", "label_prop_round", "matmul", "segment_sum",
            "flash_attention"}
        from repro.kernels.segmented_select import segmented_count_le
        assert segmented_count_le.__kernel_contract__.name == \
            "segmented_count_le"

    def test_check_layout_roundtrip(self):
        import numpy as np
        import repro.kernels.contracts as kc
        z = np.zeros(4, np.int32)
        good = {name: z for name in kc.LAYOUT_CONTRACTS}
        assert kc.check_layout(good) == []
        bad = dict(good)
        bad["node_u"] = z.astype(np.int64)      # wrong dtype
        bad["bogus_plane"] = z                  # undeclared
        del bad["ver_k"]                        # missing
        w = kc.KernelWitness()
        problems = kc.check_layout(bad, witness=w)
        assert any("int64" in p for p in problems)
        assert any("bogus_plane" in p for p in problems)
        assert any("ver_k" in p for p in problems)
        assert {p["kind"] for p in w.problems()} == {"layout-contract"}


class TestWitnessedDeviceQuery:
    def test_armed_end_to_end_device_query(self, monkeypatch):
        """A real index upload + device query with the witness armed:
        the layout passes check_layout and every kernel call validates
        clean."""
        import numpy as np
        import jax.numpy as jnp
        import repro.kernels.contracts as kc
        from repro.core.batch_query import to_device, window_sweep
        from repro.core.core_time import edge_core_times
        from repro.core.pecb_index import build_pecb_index
        from repro.core.temporal_graph import gen_temporal_graph
        from repro.kernels.kcore_peel import degree_count

        w = kc.KernelWitness()
        monkeypatch.setenv("REPRO_KERNEL_WITNESS", "1")
        monkeypatch.setattr(kc, "WITNESS", w)

        g = gen_temporal_graph(n=20, m=90, t_max=8, seed=3)
        pecb = build_pecb_index(g, 2, edge_core_times(g, 2))
        dix = to_device(pecb)                 # layout checked on upload
        ts = jnp.asarray([1, 2], jnp.int32)
        te = jnp.asarray([5, 6], jnp.int32)
        mask = np.asarray(window_sweep(dix, jnp.int32(0), ts, te)[0])
        assert mask.shape == (2, g.n)

        deg = degree_count(g.src, g.dst, np.ones(g.m, bool), g.n)
        assert int(np.asarray(deg).sum()) == 2 * g.m
        assert w.calls >= 1
        assert w.problems() == []
