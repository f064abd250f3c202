"""Chip smoke: the TCCS serving path, once, end to end, on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the 4-device executor phase only

One process drives ``ServingEngine`` the way a user does: ``register_graph``,
``warmup(full=True)`` (cold k-stratified build + bucket compiles),
``submit_specs`` and ``sweep``, on a seeded graph at SNAP CollegeMsg's
published size (1,899 vertices, 59,835 temporal edges over 193 days). The
result cache is off, so every answer comes from the device programs, and
every VERTICES/COUNT answer is checked against Algorithm 1, a sample of
EDGES answers against the brute-force oracle, and the window sweep against
per-window Algorithm 1.

``--chips 4`` runs only the multi-device phase: the same graph and
queries through one engine whose executor spans 4 devices and one on a
single device, which must answer identically, with the batch placed
across all 4 devices.

Any mismatch, off-device answer or failed phase exits non-zero. Without a
TPU it exits non-zero and names the platform JAX found. The last line of
stdout is one JSON object: ``{"ok": true, "device": {"platform": "tpu",
"kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

WORKLOAD = "collegemsg"
#: SNAP CollegeMsg: users, temporal edges (messages), span in days
COLLEGEMSG = dict(n=1899, m=59835, t_max=193)
#: generator shape parameters SNAP does not publish (gen_temporal_graph's
#: defaults)
ASSUMED = dict(power=1.2, burstiness=0.35)
#: strata the mixed-k traffic draws from (those the graph supports)
SERVE_KS = (2, 4, 8, 16)
BATCH = 256
N_VERTEX_BATCHES = 4          # 1024 VERTICES queries
COUNT_BATCH = 64              # one smaller COUNT batch: the bucket-64 program
N_EDGE_CHECKS = 32            # EDGES answers checked against the oracle
SWEEP_WINDOWS = 64
SWEEP_WIDTH = 14              # days per sweep window


def log(msg: str) -> None:
    print(msg, flush=True)


def make_graph(shape: dict, seed: int):
    from repro.core.temporal_graph import gen_temporal_graph
    t0 = time.perf_counter()
    g = gen_temporal_graph(shape["n"], shape["m"], shape["t_max"],
                           seed=seed, **ASSUMED)
    log(f"[graph] n={g.n} m={g.m} t_max={g.t_max} seed={seed} "
        f"generated in {time.perf_counter() - t0:.2f}s "
        f"(published counts: {shape})")
    log(f"[graph] assumed: {json.dumps(ASSUMED)} (Zipf vertex popularity "
        "exponent and burst share; SNAP publishes neither)")
    return g


def serve_ks(supported: tuple) -> tuple:
    ks = tuple(k for k in SERVE_KS if k in supported)
    require(len(ks) >= 3, f"mixed-k traffic needs 3 of {SERVE_KS}, the "
            f"index serves {supported}")
    return ks


def make_specs(g, ks, n_q: int, mode, seed: int) -> list:
    """Mixed-k queries around real activity: each picks a random message,
    asks about its sender, over a window of up to 30 days either side."""
    from repro.core.query_api import TCCSQuery
    rng = np.random.default_rng(seed)
    e = rng.integers(0, g.m, n_q)
    ts = np.maximum(1, g.t[e] - rng.integers(0, 31, n_q))
    te = np.minimum(g.t_max, g.t[e] + rng.integers(0, 31, n_q))
    return [TCCSQuery(int(g.src[e[i]]), int(ts[i]), int(te[i]),
                      int(ks[i % len(ks)]), mode) for i in range(n_q)]


def sweep_spec(g, k: int):
    from repro.core.query_api import WindowSweep
    deg = np.bincount(np.concatenate([g.src, g.dst]), minlength=g.n)
    last = max(1, g.t_max - SWEEP_WIDTH + 1)      # latest window start
    stride = max(1, (last - 1) // (SWEEP_WINDOWS - 1))
    starts = [min(1 + i * stride, last) for i in range(SWEEP_WINDOWS)]
    windows = [(ts, min(g.t_max, ts + SWEEP_WIDTH - 1)) for ts in starts]
    return WindowSweep(int(np.argmax(deg)), k, windows)


def submit_batches(eng, specs: list) -> tuple[list, float]:
    """Submit in batches of BATCH, one batch in flight at a time; return
    the results and the wall seconds."""
    results = []
    t0 = time.perf_counter()
    for i in range(0, len(specs), BATCH):
        futs = eng.submit_specs(WORKLOAD, specs[i:i + BATCH])
        eng.flush()
        results += [f.result(timeout=900) for f in futs]
    return results, time.perf_counter() - t0


def check_vertices(pecb, results: list, route: str) -> tuple[int, int, int]:
    """(mismatches vs Algorithm 1, answers off ``route``, non-empty)."""
    from repro.core.query_api import ResultMode
    bad = off = nonempty = 0
    for r in results:
        q = r.query
        want = pecb.slice_k(q.k)._component_vertices(q.u, q.ts, q.te)
        if q.mode is ResultMode.COUNT:
            bad += r.num_vertices != len(want)
        else:
            bad += r.vertices != frozenset(want)
        off += r.provenance.route != route
        nonempty += bool(want)
    return bad, off, nonempty


def check_edges(g, results: list) -> tuple[int, int]:
    """(mismatches vs tccs_oracle_edges, non-empty) on the first
    N_EDGE_CHECKS answers."""
    from repro.core.kcore import tccs_oracle_edges
    bad = nonempty = 0
    for r in results[:N_EDGE_CHECKS]:
        q = r.query
        want = tccs_oracle_edges(g, q.k, q.u, q.ts, q.te)
        bad += r.edges.edge_ids() != frozenset(want)
        nonempty += bool(want)
    return bad, nonempty


def tables_equal(a, b) -> bool:
    """Field-for-field equality of two StratifiedCoreTables."""
    import dataclasses
    return all(np.array_equal(getattr(a, f.name), getattr(b, f.name))
               for f in dataclasses.fields(a))


def require(cond: bool, msg: str) -> None:
    if not cond:
        print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
        raise SystemExit(1)


def one_chip(shape: dict, seed: int) -> None:
    import jax

    from repro.core import ecb_native
    from repro.core.core_time import (_sweep_block, resolve_engine,
                                      stratified_core_times)
    from repro.core.query_api import ResultMode
    from repro.serving import EngineConfig, ServingEngine

    g = make_graph(shape, seed)
    cfg = EngineConfig(cache_capacity=0)
    with ServingEngine(cfg) as eng:
        eng.register_graph(WORKLOAD, g)
        t0 = time.perf_counter()
        handle = eng.warmup(WORKLOAD, full=True)
        warm_s = time.perf_counter() - t0
        pecb = handle.pecb
        stages = " ".join(f"{k}={v:.2f}s"
                          for k, v in handle.build_stages.items())
        log(f"[build] cold build {handle.build_seconds:.2f}s ({stages})")
        log(f"[build] core-time engine={resolve_engine('auto')} "
            f"sweep_programs_compiled={_sweep_block._cache_size()} "
            f"forest_builder="
            f"{'native' if ecb_native.available() else 'python'}")
        log(f"[build] strata={len(pecb.supported_ks)} "
            f"ks={pecb.supported_ks[0]}..{pecb.supported_ks[-1]} "
            f"forest_nodes={pecb.num_nodes} "
            f"versions={handle.device.num_versions} "
            f"index_bytes={handle.nbytes}")
        log(f"[warmup] compile seconds (warmup minus build) "
            f"{warm_s - handle.build_seconds:.2f}s "
            f"jit_compiles={eng.metrics.counter('jit_compiles')}")

        # Algorithm 1 reads the same index, so check the served core
        # times against the jitted sweep, built on the chip on its own
        t0 = time.perf_counter()
        same_tab = tables_equal(handle.tab, stratified_core_times(
            g, pecb.supported_ks, engine="jax"))
        log(f"[check] core-time table from the {resolve_engine('auto')} "
            f"engine ({handle.build_stages['core_times']:.2f}s) equals the "
            f"jax engine's: {same_tab} ({time.perf_counter() - t0:.2f}s, "
            f"sweep_programs_compiled={_sweep_block._cache_size()})")

        ks = serve_ks(pecb.supported_ks)
        vspecs = make_specs(g, ks, N_VERTEX_BATCHES * BATCH,
                            ResultMode.VERTICES, seed + 1)
        vres, dt = submit_batches(eng, vspecs)
        log(f"[serve] {len(vres)} VERTICES queries, k in {ks}, batches of "
            f"{BATCH}: {dt:.3f}s -> {len(vres) / dt:.1f} q/s "
            "(smoke figure, not a benchmark)")
        cspecs = make_specs(g, ks, COUNT_BATCH, ResultMode.COUNT, seed + 2)
        cres, _ = submit_batches(eng, cspecs)
        bad_v, off_v, ne_v = check_vertices(pecb, vres + cres, "device")
        log(f"[check] VERTICES+COUNT vs Algorithm 1: {len(vres) + len(cres)} "
            f"checked, mismatches={bad_v}, off_device={off_v}, "
            f"non_empty={ne_v}")

        especs = make_specs(g, ks, BATCH, ResultMode.EDGES, seed + 3)
        eres, dt = submit_batches(eng, especs)
        bad_ev, off_e, _ = check_vertices(pecb, eres, "device")
        bad_e, ne_e = check_edges(g, eres)
        log(f"[check] EDGES batch of {len(eres)} in {dt:.3f}s: vertices vs "
            f"Algorithm 1 mismatches={bad_ev}, off_device={off_e}; "
            f"edges vs oracle: {N_EDGE_CHECKS} checked, mismatches={bad_e}, "
            f"non_empty={ne_e}")

        ws = sweep_spec(g, ks[1])
        t0 = time.perf_counter()
        sres = eng.sweep(WORKLOAD, ws, timeout=900)
        dt = time.perf_counter() - t0
        bad_s, off_s, ne_s = check_vertices(pecb, sres, "sweep")
        log(f"[check] sweep u={ws.u} k={ws.k} W={len(sres)} in {dt:.3f}s: "
            f"vs per-window Algorithm 1 mismatches={bad_s}, "
            f"off_device={off_s}, non_empty={ne_s}")

        counters = eng.metrics.snapshot(include_sources=False)["counters"]
        log(f"[engine] device_batches={counters.get('device_batches', 0)} "
            f"device_queries={counters.get('device_queries', 0)} "
            f"host_batches={counters.get('host_batches', 0)} "
            f"sweep_launches={counters.get('sweep_launches', 0)} "
            f"cache_hits={counters.get('cache_hits', 0)} "
            f"jit_compiles={counters.get('jit_compiles', 0)}")
        mem = jax.devices()[0].memory_stats() or {}
        log(f"[memory] peak_bytes_in_use={mem.get('peak_bytes_in_use')} "
            f"bytes_limit={mem.get('bytes_limit')}")

    require(same_tab, "served core-time table differs from the jax "
            "engine's")
    require(bad_v == bad_ev == bad_e == bad_s == 0,
            f"mismatches: vertices={bad_v} edges_vertices={bad_ev} "
            f"edges={bad_e} sweep={bad_s}")
    require(off_v == off_e == off_s == 0,
            f"answers off the device: vertices={off_v} edges={off_e} "
            f"sweep={off_s}")
    require(counters.get("device_batches", 0) > 0, "no device batch ran")
    require(counters.get("sweep_launches", 0) > 0, "no device sweep ran")


def four_chips(shape: dict, seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.batch_query import batch_query, mixed_slots
    from repro.core.query_api import ResultMode
    from repro.serving import EngineConfig, ServingEngine
    from repro.serving.executor import pad_queries

    devs = jax.devices()
    require(len(devs) >= 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    g = make_graph(shape, seed)
    cfg = EngineConfig(cache_capacity=0)
    with ServingEngine(cfg, devices=devs[:4]) as eng4, \
            ServingEngine(cfg, registry=eng4.registry,
                          devices=devs[:1]) as eng1:
        eng4.register_graph(WORKLOAD, g)
        handle = eng4.registry.get(WORKLOAD)
        log(f"[build] cold build {handle.build_seconds:.2f}s "
            f"strata={len(handle.supported_ks)} "
            f"forest_nodes={handle.pecb.num_nodes}")
        require(eng4.executor.num_devices == 4
                and eng1.executor.num_devices == 1,
                "executors do not span 4 and 1 devices")
        ks = serve_ks(handle.supported_ks)
        specs = (make_specs(g, ks, N_VERTEX_BATCHES * BATCH,
                            ResultMode.VERTICES, seed + 1)
                 + make_specs(g, ks, BATCH, ResultMode.EDGES, seed + 3))
        ws = sweep_spec(g, ks[1])
        out = {}
        for name, eng in (("4-device", eng4), ("1-device", eng1)):
            res, dt = submit_batches(eng, specs)
            t0 = time.perf_counter()
            sres = eng.sweep(WORKLOAD, ws, timeout=900)
            log(f"[{name}] {len(res)} queries in {dt:.3f}s, sweep W="
                f"{len(sres)} in {time.perf_counter() - t0:.3f}s")
            out[name] = (res, sres)

        (r4, s4), (r1, s1) = out["4-device"], out["1-device"]
        diff = sum(a.vertices != b.vertices or a.num_vertices != b.num_vertices
                   or (a.edges is not None
                       and a.edges.edge_ids() != b.edges.edge_ids())
                   for a, b in zip(r4 + s4, r1 + s1))
        bad_v, off_v, ne_v = check_vertices(handle.pecb, r4, "device")
        bad_s, off_s, _ = check_vertices(handle.pecb, s4, "sweep")
        bad_e, _ = check_edges(g, r4[N_VERTEX_BATCHES * BATCH:])
        log(f"[check] 4-device vs 1-device: {len(r4) + len(s4)} answers "
            f"compared, differing={diff}")
        log(f"[check] 4-device vs Algorithm 1/oracle: mismatches vertices="
            f"{bad_v} sweep={bad_s} edges={bad_e}, off_device={off_v + off_s}"
            f", non_empty={ne_v}")

        # the executor's batch sharding splits a 256-query batch into four
        # 64-query shards, one per device, and the launch runs on all four
        ex = eng4.executor
        q = pad_queries(mixed_slots(handle.pecb,
                                    [(s.u, s.k) for s in specs[:BATCH]]),
                        [s.ts for s in specs[:BATCH]],
                        [s.te for s in specs[:BATCH]], BATCH)
        placed = [jax.device_put(jnp.asarray(a), ex.batch_sharding)
                  for a in q]
        mask, _ = batch_query(handle.device, *placed)
        rows = sorted(sh.data.shape[0] for sh in placed[0].addressable_shards)
        in_devs = len(placed[0].sharding.device_set)
        out_devs = len(mask.sharding.device_set)
        log(f"[placement] query shards: devices={in_devs} rows={rows}; "
            f"output on {out_devs} devices as {mask.sharding.spec}")
        want = np.stack([np.isin(np.arange(g.n), sorted(r.vertices))
                         for r in r4[:BATCH]])
        same = bool(np.array_equal(np.asarray(mask), want))
        log(f"[placement] sharded launch equals served answers: {same}")
        c4 = eng4.metrics.counter("device_batches")
        c1 = eng1.metrics.counter("device_batches")
        log(f"[engine] device_batches 4-device={c4} 1-device={c1}")

    require(diff == 0, f"{diff} answers differ between 4 and 1 devices")
    require(bad_v == bad_s == bad_e == 0, "4-device answers disagree with "
            "Algorithm 1 / the oracle")
    require(off_v + off_s == 0, "answers off the device")
    require(in_devs == out_devs == 4 and rows == [BATCH // 4] * 4,
            "batch not placed across 4 devices")
    require(same, "sharded launch disagrees with the served answers")
    require(c4 > 0 and c1 > 0, "no device batch ran")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 4-device executor phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform {platform!r} "
              f"({len(devices)} device(s))", file=sys.stderr)
        return 2
    log(f"[device] platform={platform} device_kind={devices[0].device_kind} "
        f"count={len(devices)} jax={jax.__version__}")

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.runtime.compile_cache import enable_compile_cache
    log(f"[device] compile cache: {enable_compile_cache()}")
    if args.chips == 4:
        four_chips(COLLEGEMSG, args.seed)
    else:
        one_chip(COLLEGEMSG, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
