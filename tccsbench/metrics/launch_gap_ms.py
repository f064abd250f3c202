"""Mean milliseconds a batcher worker left the device without work of the
engine's between launches in the window: histogram ``launch_gap``, from
the end of one launch's ``executor.wait`` to the start of the next
launch's ``executor.dispatch`` on the same worker."""


def read(run):
    h = run.hists.get("launch_gap")
    return 1e3 * h.total / h.count if h is not None and h.count else None
