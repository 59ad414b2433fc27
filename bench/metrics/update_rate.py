"""Ops committed in the window over the time from its start to its last
commit (closed-loop cells)."""

import stats


def read(run):
    if run.arrivals != "closed" or not run.batches:
        return None
    return stats.update_rate(run.window_start,
                             [(b["commit"], b["ops"]) for b in run.batches])
