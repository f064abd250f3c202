"""Seconds of the registry's ``forest`` stage in this run's build."""


def read(run):
    return run.stages.get("forest")
