"""Run one benchmark cell once, on the served path, on the chip.

    python3 -m tccsbench --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``configs/<name>.json``: the deployment's published counts, what was
assumed, its guarantees and the engine's settings) and a traffic mix
(``traffic/<name>.json``, read by ``loadgen``). Each metric is read by
``metrics/<name>.py``. A new cell, mix or metric is new files and new
entries; nothing here changes.

One run: make the configuration's graph; start a ``ServingEngine`` with the
configuration's settings; register the graph and build its index through
the registry; send one round of the mix's queries to warm the programs it
reaches; then drive the mix, drawn from ``--seed``, through
``submit_specs`` for ``--seconds``, drain, free the engine, and compare
every answer with the plain reference (``reference.py``). With ``--trace 1`` the window runs under the JAX
profiler and the result carries the per-layer metrics, the device's busy
time and a breakdown; with ``--trace 0`` it carries the end-to-end ones.

The last line of stdout is one JSON object; the last lines of stderr are
the numbers compared, each beside its limit. Without a TPU, or with fewer
chips than the cell asks for, the run prints no result and exits 2.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from concurrent.futures import wait
from contextlib import nullcontext
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
GRACE_S = 60.0     # how long past the window's close an answer may come

#: numbers compared to decide ``correct``, each with its limit
LIMITS = {"wrong": 0, "missing": 0}


class NoChip(RuntimeError):
    pass


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    metrics: dict           # name -> BENCHMARK.json entry, in report order
    root: Path


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    mix = json.loads((root / "tccsbench" / "traffic"
                      / f"{w['traffic']}.json").read_text())
    metrics = {}
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if name in m.get("workloads", [name]):
                metrics[m["name"]] = dict(m, kind=kind)
    return Cell(name, int(w["chips"]), config, mix, metrics, root)


def reader(root: Path, name: str):
    """``metrics/<name>.py``'s ``read``."""
    path = root / "tccsbench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "tccsbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def enable_cache() -> str:
    """JAX's persistent compilation cache in ``<checkout>/.jax_cache/`` (or
    where ``JAX_COMPILATION_CACHE_DIR`` says), holding every program,
    however quick to compile, so that only a cell's first run compiles."""
    import jax

    from repro.runtime.compile_cache import enable_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return enable_compile_cache()


def check_platform(chips: int) -> list:
    """The devices the cell runs on; ``NoChip`` off a TPU or short of
    chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found platform {devs[0].platform!r} "
                     f"({len(devs)} device(s))")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX found "
                     f"{len(devs)}")
    return devs[:chips]


@dataclasses.dataclass
class Run:
    """What the metric readers see of one run."""

    records: list            # loadgen.Sent, one per query sent
    t_first: float           # first send
    t_gave_up: float         # the close plus the grace for late answers
    setup_s: float
    counters: dict           # engine counters over the window and drain
    hists: dict              # engine histograms over the window and drain
    stages: dict             # this run's index build stages (seconds)
    trace: dict | None       # trace.summarize() of the window, if traced


def log(msg: str) -> None:
    print(f"[tccsbench] {msg}", file=sys.stderr, flush=True)


def _specs(queries):
    from repro.core.query_api import ResultMode, TCCSQuery
    return [TCCSQuery(q.u, q.ts, q.te, q.k, ResultMode[q.mode])
            for q in queries]


@dataclasses.dataclass
class Served:
    """A cell's deployment after set-up: the engine with the index built
    and the mix's programs warm, and the graph it serves."""

    cell: Cell
    eng: object
    g: object
    stages: dict             # the index build's stages (seconds)
    setup_s: float

    @property
    def workload(self) -> str:
        return self.cell.config["name"]

    def submit(self, qs):
        return self.eng.submit_specs(self.workload, _specs(qs))


def bring_up(cell: Cell, seed: int, devices, t_start: float) -> Served:
    """Set-up: the graph, the engine, the index built through the registry,
    one round of the mix (stream 2 of ``seed``) to warm its programs."""
    from repro.core.temporal_graph import TemporalGraph
    from repro.serving import EngineConfig, ServingEngine

    from tccsbench import graphgen, loadgen

    cfg, mix = cell.config, cell.mix
    g = graphgen.make_graph(cfg)
    log(f"graph {cfg['name']}: n={g.n} m={g.m} t_max={g.t_max} seed={seed}")
    eng = ServingEngine(EngineConfig(**cfg["engine"]), devices=devices)
    try:
        # the engine gets its own copy: the reference reads ``g``
        eng.register_graph(cfg["name"], TemporalGraph(
            g.n, g.src.copy(), g.dst.copy(), g.t.copy()))
        handle = eng.registry.get(cfg["name"])
        stages = dict(handle.build_stages)
        log(f"index: {len(handle.supported_ks)} strata, "
            f"{handle.pecb.num_nodes} forest nodes, "
            f"{handle.device.num_versions} versions, build "
            + " ".join(f"{k}={v:.3f}s" for k, v in stages.items()))
        del handle
        served = Served(cell, eng, g, stages, 0.0)
        warm = loadgen.queries(g, mix, seed, stream=2)
        warmed = wait(served.submit([next(warm)
                                     for _ in range(int(mix["clients"]))]),
                      timeout=600)
        log(f"warm-up: {len(warmed.done)} answered, "
            f"{sum(f.exception() is not None for f in warmed.done)} failed, "
            f"{len(warmed.not_done)} unanswered")
    except BaseException:
        eng.close()
        raise
    served.setup_s = time.perf_counter() - t_start
    log(f"set-up {served.setup_s:.3f}s")
    return served


def window(served: Served, seed: int, seconds: float, traced: bool) -> Run:
    """Drive the mix, drawn from ``seed``, for ``seconds`` and drain; the
    engine's metrics cover this window and its drain alone."""
    import jax

    from tccsbench import loadgen
    from tccsbench import trace as tracemod

    eng = served.eng
    eng.metrics.reset()
    logdir = tempfile.mkdtemp(prefix="tccsbench-trace-") if traced else None
    annotate = (jax.profiler.TraceAnnotation if traced
                else (lambda name: nullcontext()))
    if traced:
        jax.profiler.start_trace(logdir)
    try:
        with annotate(tracemod.WINDOW):
            records, t_first = loadgen.closed_loop(
                served.submit, loadgen.queries(served.g, served.cell.mix, seed),
                int(served.cell.mix["clients"]), seconds, grace_s=GRACE_S,
                annotate=annotate)
    finally:
        if traced:
            jax.profiler.stop_trace()
    loadgen.settle(records)
    snap = eng.metrics.snapshot(include_sources=False)
    hists = {k: eng.metrics.histogram(k) for k in snap["latency"]}
    summary = None
    if traced:
        t0 = time.perf_counter()
        summary = tracemod.summarize(tracemod.load(logdir))
        shutil.rmtree(logdir, ignore_errors=True)
        log(f"trace read in {time.perf_counter() - t0:.3f}s")
    log(f"window: sent {len(records)}, counters {snap['counters']}")
    return Run(records, t_first, t_first + seconds + GRACE_S, served.setup_s,
               snap["counters"], hists, served.stages, summary)


def drive(cell: Cell, seed: int, seconds: float, traced: bool, devices,
          t_start: float) -> tuple[Run, object, int]:
    """Set-up, warm-up and the window; returns the run, the graph and the
    device's memory peak."""
    served = bring_up(cell, seed, devices, t_start)
    try:
        run = window(served, seed, seconds, traced)
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
    finally:
        served.eng.close()
    return run, served.g, peak


def check(run: Run, g) -> dict:
    """Compare every answer of the window with the plain reference."""
    from tccsbench import reference
    wrong = missing = 0
    for r in run.records:
        if r.t_done is None or r.error is not None:
            missing += 1
            continue
        q, res = r.query, r.result
        want = reference.component(g, q.u, q.ts, q.te, q.k)
        got = res.query
        same_query = (got.u, got.ts, got.te, got.k, got.mode.name) == (
            q.u, q.ts, q.te, q.k, q.mode)
        if q.mode == "COUNT":
            ok = res.num_vertices == len(want)
        else:
            ok = res.vertices == want and res.num_vertices == len(want)
        wrong += not (same_query and ok)
    return {"wrong": wrong, "missing": missing}


def report(cell: Cell, run: Run, traced: bool) -> dict:
    kind = "per_layer" if traced else "end_to_end"
    out = {}
    for name, m in cell.metrics.items():
        if m["kind"] != kind:
            continue
        value = reader(cell.root, name)(run)
        if value is not None:
            out[name] = {"value": float(value), "unit": m["unit"]}
    return out


def prepare(cell: Cell) -> list:
    """The cell's devices, once the chip, its peak table and the compile
    cache are in order; the program under test importable."""
    devices = check_platform(cell.chips)
    sys.path.insert(0, str(cell.root / "src"))
    from tccsbench import trace as tracemod
    tracemod.peaks(devices[0].device_kind)
    log(f"device {devices[0].platform} {devices[0].device_kind} "
        f"x{len(devices)}; compile cache {enable_cache()}")
    return devices


def is_correct(checks: dict) -> bool:
    return all(checks[k] <= LIMITS[k] for k in LIMITS)


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             t_start: float) -> dict:
    devices = prepare(cell)
    kind = devices[0].device_kind
    run, g, peak = drive(cell, seed, seconds, traced, devices, t_start)
    checks = check(run, g)
    correct = is_correct(checks)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": correct, "attempted": len(run.records),
              "failed": checks["missing"],
              "metrics": report(cell, run, traced), "device": device}
    if traced:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": LIMITS[k]}
                        for k, v in checks.items()}
    return result


def main(argv=None, root: Path = ROOT) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(prog="tccsbench",
                                 description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload, root)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          t_start)
    except NoChip as exc:
        print(f"tccsbench: {exc}", file=sys.stderr, flush=True)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
