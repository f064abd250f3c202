"""Planner milliseconds per device query spent turning a launch's masks
into typed results (the ``planner.assemble`` span, histogram
``device_assemble``) over the window, divided by the queries launched."""


def read(run):
    h = run.hists.get("device_assemble")
    n = run.counters.get("device_queries", 0)
    return 1e3 * h.total / n if h is not None and h.count and n else None
