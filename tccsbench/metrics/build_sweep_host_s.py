"""Seconds of host work around the core-time sweep in this run's index
build, summed over strata: registry stages ``core_times.prepare`` (pair
CSR, t_uv rows, operand uploads) and ``core_times.compress``."""


def read(run):
    prep = run.stages.get("core_times.prepare")
    comp = run.stages.get("core_times.compress")
    return prep + comp if prep is not None and comp is not None else None
