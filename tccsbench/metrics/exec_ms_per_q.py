"""Executor milliseconds per device query: the planner's ``device_exec``
clock (padding, dispatch, the run and the download of the masks) over the
window, divided by the queries it launched."""


def read(run):
    h, n = run.hists.get("device_exec"), run.counters.get("device_queries", 0)
    return 1e3 * h.total / n if h is not None and n else None
