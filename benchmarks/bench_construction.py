"""Construction-plane benchmark: PR-1 baseline vs the batched engines.

The paper's headline claim is construction cost (ECB builds up to 100x
faster than EF); this bench tracks *our own* construction trajectory across
PRs. Both planes are measured cold in the same run so the speedup column is
self-contained:

* ``pr1`` — the seed path: per-start-time projection + lexsort fixpoint
  (``edge_core_times(engine="legacy")``) and the per-version Python insert
  loop (``IncrementalBuilder(prefilter=False)``).
* ``batched`` — the PR-2 plane: precomputed pair-CSR/t_uv sweep engine
  (the host engine, ``engine="auto"``), MSF-prefiltered builder, and the
  lexsort ``pack_index``.

The two planes are asserted to produce identical ``CoreTimeTable``s (all
five arrays) and identical packed indexes before any number is reported —
a benchmark of a wrong answer is worthless.

CSV: ``construction_plane.csv``.
"""

from __future__ import annotations

import numpy as np

from repro.core.core_time import edge_core_times, stratified_core_times
from repro.core.ecb_forest import IncrementalBuilder
from repro.core.pecb_index import (build_pecb_index, build_stratified_index,
                                   pack_index)

from .common import default_k, timed, workload, write_csv

WORKLOADS = ["fb_like", "cm_like", "em_like", "mo_like", "wk_like"]

_TABLE_FIELDS = ("edge_id", "ts_from", "ts_to", "ct", "vertex_ct")

#: acceptance floors for the |K|-stratified scenario on em_like (the
#: ISSUE's target workload): one stratified build must beat |K| per-k
#: builds by >= 3x cold and hold registry+store bytes >= 2x smaller
MIN_STRATIFIED_SPEEDUP = 3.0
MIN_STRATIFIED_BYTES_RATIO = 2.0

_VERSION_ARRAYS = ("edge_id", "ts_from", "ts_to", "ct", "src", "dst", "t")


def _assert_identical(name, tab_old, tab_new, idx_old, idx_new):
    for f in _TABLE_FIELDS:
        if not np.array_equal(getattr(tab_old, f), getattr(tab_new, f)):
            raise AssertionError(f"{name}: CoreTimeTable.{f} differs between "
                                 "the legacy and batched construction planes")
    import dataclasses
    for f in dataclasses.fields(idx_old):
        va, vb = getattr(idx_old, f.name), getattr(idx_new, f.name)
        same = np.array_equal(va, vb) if isinstance(va, np.ndarray) else va == vb
        if not same:
            raise AssertionError(f"{name}: PECBIndex.{f.name} differs between "
                                 "the two construction planes")


def bench_construction_plane(workloads=WORKLOADS):
    rows = []
    for name in workloads:
        k = default_k(name)
        g = workload(name)
        # -- PR-1 baseline (cold, measured first) -----------------------
        tab_old, t_core_old = timed(edge_core_times, g, k, engine="legacy")
        b_old, t_forest_old = timed(
            lambda: IncrementalBuilder(g, tab_old, prefilter=False).run())
        idx_old, t_pack_old = timed(pack_index, g, k, b_old)
        old_s = t_core_old + t_forest_old + t_pack_old
        # -- batched plane (cold: includes any jit compile) -------------
        tab_new, t_core_new = timed(edge_core_times, g, k)
        b_new, t_forest_new = timed(
            lambda: IncrementalBuilder(g, tab_new).run())
        idx_new, t_pack_new = timed(pack_index, g, k, b_new)
        new_s = t_core_new + t_forest_new + t_pack_new
        _assert_identical(name, tab_old, tab_new, idx_old, idx_new)
        rows.append([
            name, k,
            round(t_core_old, 4), round(t_forest_old + t_pack_old, 4),
            round(old_s, 4),
            round(t_core_new, 4), round(t_forest_new + t_pack_new, 4),
            round(new_s, 4),
            round(old_s / new_s, 2),
        ])
    write_csv("construction_plane.csv",
              ["workload", "k", "pr1_core_s", "pr1_forest_s", "pr1_total_s",
               "batched_core_s", "batched_forest_s", "batched_total_s",
               "speedup"], rows)
    return rows


def _per_k_plane_bytes(g, tabs, idxs):
    """Registry + store footprint of the pre-PR-9 per-k plane, measured
    on real per-k builds. Registry: each handle kept its packed index,
    its core-time records, the dense ``(t_max+1, n)`` vertex matrix and
    an eagerly-built version store. Store: the PR-8 layout wrote all of
    those arrays — graph included — once per ``(workload, k)`` key."""
    graph_b = int(g.src.nbytes + g.dst.nbytes + g.t.nbytes)
    reg = store = 0
    for tab, idx in zip(tabs, idxs):
        ver_b = sum(int(getattr(idx.versions, f).nbytes)
                    for f in _VERSION_ARRAYS)
        handle_b = (idx.nbytes() + tab.nbytes()
                    + int(tab.vertex_ct.nbytes) + ver_b)
        reg += handle_b
        store += handle_b + graph_b
    return reg, store


def _stratified_plane_bytes(g, stab, sx):
    """Registry + store footprint of the one-build plane: what the
    registry's ``resident_bytes``/``resident_tab_bytes`` stats report for
    the single handle (version arrays are derived lazily, not retained),
    plus the actual bytes a fresh :class:`IndexStore` commit writes."""
    import shutil
    import tempfile

    from repro.core.batch_query import to_device
    from repro.serving.registry import IndexHandle
    from repro.store import IndexStore

    reg = sx.nbytes() + stab.nbytes()
    root = tempfile.mkdtemp(prefix="bench-strat-")
    try:
        h = IndexHandle("strat", g, sx, to_device(sx), 0.0, tab=stab)
        store = IndexStore(root).put_handle("strat", h)["bytes_written"]
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return reg, int(store)


def bench_stratified_construction(name: str = "em_like", n_ks: int = 8,
                                  assert_floors: bool = True):
    """|K|-stratified scenario (PR-9 tentpole): ONE k-stratified build vs
    |K| separate per-k builds of the same strata.

    Every stratum of the stratified index is asserted bit-identical to
    its per-k build before any number is reported. Floors (em_like only):
    cold build >= 3x faster, registry+store bytes >= 2x smaller.

    CSV row: workload, |K|, ks, per-k build s, stratified build s,
    speedup, per-k registry+store MB, stratified registry+store MB,
    bytes ratio.
    """
    from repro.core.kcore import k_max

    g = workload(name)
    km = k_max(g)
    ks = tuple(range(2, 2 + min(n_ks, km - 1)))

    per_k_s = 0.0
    tabs, idxs = [], []
    for k in ks:
        tab, t_tab = timed(edge_core_times, g, k)
        idx, t_idx = timed(build_pecb_index, g, k, tab)
        per_k_s += t_tab + t_idx
        tabs.append(tab)
        idxs.append(idx)

    stab, t_stab = timed(stratified_core_times, g, ks)
    sx, t_sx = timed(build_stratified_index, g, ks, strata=stab)
    strat_s = t_stab + t_sx

    # exactness first, numbers second: every stratum bit-identical
    import dataclasses
    for k, idx in zip(ks, idxs):
        sl = sx.slice_k(k)
        for f in dataclasses.fields(idx):
            va, vb = getattr(idx, f.name), getattr(sl, f.name)
            if isinstance(va, np.ndarray):
                assert np.array_equal(va, vb), (
                    f"{name}: stratum k={k} field {f.name} diverged from "
                    "the per-k build")

    perk_reg, perk_store = _per_k_plane_bytes(g, tabs, idxs)
    strat_reg, strat_store = _stratified_plane_bytes(g, stab, sx)
    perk_b = perk_reg + perk_store
    strat_b = strat_reg + strat_store

    speedup = per_k_s / strat_s
    bytes_ratio = perk_b / strat_b
    if assert_floors and name == "em_like":
        assert speedup >= MIN_STRATIFIED_SPEEDUP, (
            f"em_like |K|={len(ks)} stratified build speedup "
            f"{speedup:.2f}x fell below the {MIN_STRATIFIED_SPEEDUP}x "
            "acceptance floor")
        assert bytes_ratio >= MIN_STRATIFIED_BYTES_RATIO, (
            f"em_like |K|={len(ks)} registry+store bytes ratio "
            f"{bytes_ratio:.2f}x fell below the "
            f"{MIN_STRATIFIED_BYTES_RATIO}x acceptance floor")

    rows = [[name, len(ks), f"{ks[0]}-{ks[-1]}",
             round(per_k_s, 4), round(strat_s, 4), round(speedup, 2),
             round(perk_b / 1e6, 2), round(strat_b / 1e6, 2),
             round(bytes_ratio, 2)]]
    write_csv("construction_stratified.csv",
              ["workload", "n_ks", "ks", "perk_build_s", "strat_build_s",
               "build_speedup", "perk_mb", "strat_mb", "bytes_ratio"],
              rows)
    return rows
