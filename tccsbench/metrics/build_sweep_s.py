"""Seconds of the core-time sweep itself in this run's index build,
summed over strata (registry stage ``core_times.sweep``): blocking on and
downloading the jitted sweep's result, or the numpy sweep on the host."""


def read(run):
    return run.stages.get("core_times.sweep")
