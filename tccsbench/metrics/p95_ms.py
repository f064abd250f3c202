"""95th percentile of the send-to-answer latency of every query sent in
the window; a query never answered counts as past every limit."""

from tccsbench.stats import latencies_s, percentile


def read(run):
    return 1e3 * percentile(latencies_s(run), 95) if run.records else None
