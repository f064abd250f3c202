"""Seconds of the registry's ``core_times`` stage in this run's build."""


def read(run):
    return run.stages.get("core_times")
