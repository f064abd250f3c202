"""Device milliseconds of the query programs (``batch_query*``) per query
launched, from the profiler trace of the window."""


def read(run):
    if run.trace is None:
        return None
    s = sum(v for k, v in run.trace["program_s"].items()
            if k.startswith("batch_query"))
    n = run.counters.get("device_queries", 0)
    return 1e3 * s / n if s > 0 and n else None
