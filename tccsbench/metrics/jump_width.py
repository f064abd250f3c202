"""Pointer-jump width per device launch in the window: the executor's
``jump_width`` counter (each launch's static node window, the widest
stratum of the mirror it ran on) over its ``jump_launches``; nothing where
the program counts no width."""


def read(run):
    n = run.counters.get("jump_launches", 0)
    width = run.counters.get("jump_width")
    return width / n if n and width is not None else None
