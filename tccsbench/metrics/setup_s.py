"""Seconds from the start of the run to the first send: the graph, the
engine, the index build (core times, forests, upload, and any compile they
need) and the warm-up of the programs the cell's traffic reaches."""


def read(run):
    return run.setup_s
