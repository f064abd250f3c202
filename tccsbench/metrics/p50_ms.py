"""Median send-to-answer latency of every query sent in the window."""

from tccsbench.stats import latencies_s, percentile


def read(run):
    return 1e3 * percentile(latencies_s(run), 50) if run.records else None
