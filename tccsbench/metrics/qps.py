"""Answers per second: every answer to a query sent in the window, over the
time from the first send to the last answer. Sending stops when the window
closes and the drain is counted, so no launch is cut in half. A query that
failed or was never answered is no answer; the last answer of either kind
still ends the time."""


def read(run):
    done = [r.t_done for r in run.records if r.t_done is not None]
    answered = sum(r.t_done is not None and r.error is None
                   for r in run.records)
    return answered / (max(done) - run.t_first) if answered else None
