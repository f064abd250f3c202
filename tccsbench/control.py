"""The control, and the program's readings beside it, in one process.

    python3 -m tccsbench.control --workload <cell> --seconds <s> \\
        --seeds <s> ... --control-seeds <s> ...

One set-up of the cell, as a run makes it, then one window of the cell's
own load per seed, each compared by ``run.check`` against ``run.LIMITS``
as a run is. The ``--seeds`` windows serve the program's answers: their
readings are the lower ones. The ``--control-seeds`` windows serve the
control's: the plain reference with one guarantee broken, a degree that
counts parallel temporal edges (the shortcut that skips collapsing them),
put in the program's place where a device answer is assembled. The device
launches still run; their masks are replaced by the control's. Each must
read not correct. The result cache is emptied before every window, so
that no window is served answers of another.

Each window prints one JSON line; the last line sums them up. The exit
code is 0 when every program window is correct and every control window
is not.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from contextlib import contextmanager, nullcontext

import numpy as np

from tccsbench import reference, run


@contextmanager
def in_programs_place(g):
    """Device answers assembled from the control's vertex sets."""
    from repro.serving import planner
    real = planner.assemble_device_results

    def control(store, specs, vmask, vermask, prov):
        vmask = np.array(vmask)
        for row, s in zip(vmask, specs):
            row[:] = False
            row[list(reference.component(g, s.u, s.ts, s.te, s.k,
                                         distinct_neighbours=False))] = True
        return real(store, specs, vmask, vermask, prov)

    planner.assemble_device_results = control
    try:
        yield
    finally:
        planner.assemble_device_results = real


def windows(cell, seeds, control_seeds, seconds: float,
            t_start: float) -> dict:
    """Set up once; one window per seed, the program's then the
    control's; the checks of each."""
    devices = run.prepare(cell)
    first = (list(seeds) + list(control_seeds))[0]
    served = run.bring_up(cell, first, devices, t_start)
    out = {"program": {}, "control": {}}
    try:
        for arm, arm_seeds in (("program", seeds), ("control", control_seeds)):
            place = (in_programs_place(served.g) if arm == "control"
                     else nullcontext())
            with place:
                for seed in arm_seeds:
                    served.eng.cache.purge_index(served.workload)
                    r = run.window(served, seed, seconds, traced=False)
                    checks = run.check(r, served.g)
                    line = {"arm": arm, "seed": seed,
                            "correct": run.is_correct(checks),
                            "attempted": len(r.records), "checks": checks}
                    print(json.dumps(line), flush=True)
                    out[arm][seed] = line
    finally:
        served.eng.close()
    return out


def main(argv=None, root=run.ROOT) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(prog="tccsbench.control",
                                 description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload, root)
    try:
        out = windows(cell, args.seeds, args.control_seeds, args.seconds,
                      t_start)
    except run.NoChip as exc:
        print(f"tccsbench.control: {exc}", file=sys.stderr, flush=True)
        return 2
    sound = (all(x["correct"] for x in out["program"].values())
             and not any(x["correct"] for x in out["control"].values()))
    for arm in ("program", "control"):
        for seed, x in out[arm].items():
            print(f"{arm} seed {seed}: " + " ".join(
                f"{k} {v} limit {run.LIMITS[k]}"
                for k, v in x["checks"].items()), file=sys.stderr, flush=True)
    print(json.dumps({"workload": cell.name, "sound": sound,
                      "program": {s: x["checks"] for s, x in out["program"].items()},
                      "control": {s: x["checks"] for s, x in out["control"].items()},
                      "limits": run.LIMITS}), flush=True)
    return 0 if sound else 1


if __name__ == "__main__":
    sys.exit(main())
