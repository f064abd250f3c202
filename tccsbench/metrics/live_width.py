"""Live-list width per device launch in the window: the executor's
``live_width`` counter (each launch's longest list of the nodes of one
stratum live at one start time, the width every row propagates over) over
its ``jump_launches``; nothing where the program counts no live width."""


def read(run):
    n = run.counters.get("jump_launches", 0)
    width = run.counters.get("live_width")
    return width / n if n and width is not None else None
