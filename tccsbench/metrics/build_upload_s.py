"""Seconds of the registry's ``device`` stage in this run's build: the
index arrays' upload to the device, until every array is ready."""


def read(run):
    return run.stages.get("device")
