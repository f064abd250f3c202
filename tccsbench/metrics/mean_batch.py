"""Queries executed per flushed batch in the window, both routes."""


def read(run):
    q = run.counters.get("device_queries", 0) + run.counters.get(
        "host_queries", 0)
    b = run.counters.get("device_batches", 0) + run.counters.get(
        "host_batches", 0)
    return q / b if b else None
