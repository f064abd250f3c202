"""95th percentile of the batcher's queue wait (enqueue to dispatch) over
the samples taken in the window."""


def read(run):
    h = run.hists.get("queue_wait")
    return 1e3 * h.percentile(95) if h is not None and h.count else None
