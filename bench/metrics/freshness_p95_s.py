"""95th percentile of each window op's time from its due time to the
sink event of the batch that commits it (open-loop cells)."""

import stats


def read(run):
    if run.op_due is None:
        return None
    return stats.percentile(run.op_commit - run.op_due, 95)
