"""Share of the queries executed in the window (cache hits and empty
windows excluded) that the planner sent to the device."""


def read(run):
    dev = run.counters.get("device_queries", 0)
    total = dev + run.counters.get("host_queries", 0)
    return 100.0 * dev / total if total else None
