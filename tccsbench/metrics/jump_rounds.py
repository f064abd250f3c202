"""Pointer-jump rounds per device launch in the window: the executor's
``jump_rounds`` counter (each launch's rounds to fixpoint, the last round,
which finds no change, included) over its ``jump_launches``."""


def read(run):
    n = run.counters.get("jump_launches", 0)
    return run.counters.get("jump_rounds", 0) / n if n else None
