"""Seconds spent handing the core-time sweep to the device in this run's
index build, summed over strata (registry stage ``core_times.dispatch``):
each stratum's ``_sweep_block`` call, a compile or a persistent-cache
load and then the enqueue; 0 on the host engine."""


def read(run):
    return run.stages.get("core_times.dispatch")
