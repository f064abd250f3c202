"""From a JAX profiler trace to device numbers.

``load`` keeps what the reduction reads from an ``.xplane.pb``: the
device planes' op and module lines (an op named by its HLO instruction,
``fusion.80``), and the benchmark's own host annotations
(``tccsbench.*``), as plain lists of ``[name, start_ns, duration_ns]``.
Host and device events share one clock. ``summarize`` reduces that to the
window's length, the device's busy time, the device time of each program,
the operations that took most time and the longest idle gaps, each gap
named by the benchmark annotation that covers most of it.

Busy time is the union of the intervals in which an operation ran on a
device, clipped to the window (the ``tccsbench.window`` annotation), and
averaged over the devices that ran anything. Ops nest (a ``while`` holds
its body's ops), so an op's time in the breakdown is its self time: its
interval less the ops inside it.
"""

from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path

#: published peaks of one chip, keyed by JAX's ``device_kind``
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANNOTATION = "tccsbench."
WINDOW = "tccsbench.window"


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def load(logdir) -> dict:
    """The reduced trace of the newest ``.xplane.pb`` under ``logdir``."""
    from jax.profiler import ProfileData

    paths = sorted(Path(logdir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    planes = []
    for plane in ProfileData.from_file(str(paths[-1])).planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [[_short(e.name) if line.name == OPS_LINE else e.name,
                       e.start_ns, e.duration_ns] for e in line.events
                      if device or e.name.startswith(ANNOTATION)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "device": device,
                           "lines": lines})
    return {"planes": planes}


def _short(op: str) -> str:
    """``%fusion.80 = s32[...] fusion(...), ...`` -> ``fusion.80``."""
    return op.split(" = ", 1)[0].lstrip("%")


def _events(plane: dict, line_name: str) -> list:
    return [e for ln in plane["lines"] if ln["name"] == line_name
            for e in ln["events"]]


def _clip(events, t0: float, t1: float) -> list[tuple[float, float, str]]:
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((a, b, name))
    return out


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted (start, end) pairs of ``intervals``."""
    merged: list[list[float]] = []
    for a, b in sorted((a, b) for a, b, *_ in intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def program(name: str) -> str:
    """A module event's program name: ``jit_batch_query(123)`` ->
    ``batch_query``."""
    name = re.sub(r"\(\d+\)$", "", name)
    return name[4:] if name.startswith("jit_") else name


def window(tr: dict) -> tuple[float, float]:
    for plane in tr["planes"]:
        if not plane["device"]:
            for ln in plane["lines"]:
                for name, s, d in ln["events"]:
                    if name == WINDOW:
                        return s, s + d
    raise ValueError(f"trace holds no {WINDOW!r} annotation")


def summarize(tr: dict, top: int = 10) -> dict:
    t0, t1 = window(tr)
    devices = [p for p in tr["planes"] if p["device"]
               and _clip(_events(p, OPS_LINE), t0, t1)]
    if not devices:
        raise ValueError("no device operation ran inside the traced window")
    busy_ns, op_ns, prog_ns = 0.0, defaultdict(float), defaultdict(float)
    gaps = []
    host = [e for p in tr["planes"] if not p["device"]
            for ln in p["lines"] for e in ln["events"] if e[0] != WINDOW]
    for p in devices:
        ops = _clip(_events(p, OPS_LINE), t0, t1)
        spans = union(ops)
        busy_ns += sum(b - a for a, b in spans)
        for name, ns in _self_times(ops):
            op_ns[name] += ns
        for a, b, name in _clip(_events(p, MODULES_LINE), t0, t1):
            prog_ns[program(name)] += b - a
        edges = [t0] + [x for ab in spans for x in ab] + [t1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, _doing(host, a, b)))
    n = len(devices)
    by_op = sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "devices": n,
        "program_s": {k: v / n / 1e9 for k, v in prog_ns.items()},
        "device_ops": [[k, v / n / 1e9] for k, v in by_op],
        "idle_gaps": [[name, d / 1e9] for d, name in
                      sorted(gaps, key=lambda g: -g[0])[:top]],
    }


def _self_times(ops) -> list[tuple[str, float]]:
    """Each op's interval less the parts that ops nested in it cover."""
    out, stack = [], []          # stack: [end, index into out]
    for a, b, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= a:
            stack.pop()
        if stack:
            parent = out[stack[-1][1]]
            parent[1] -= min(b, stack[-1][0]) - a
        out.append([name, b - a])
        stack.append((b, len(out) - 1))
    return [(name, ns) for name, ns in out]


def _doing(host: list, a: float, b: float) -> str:
    """The annotation that covers most of ``[a, b]``."""
    best, name = 0.0, "unannotated"
    for n, s, d in host:
        cover = min(s + d, b) - max(s, a)
        if cover > best:
            best, name = cover, n
    return name
