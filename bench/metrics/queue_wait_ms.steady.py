"""Median over the window's ops of the time from the op's due time to
the start of the batch that takes it: journal wait plus batch formation."""

import numpy as np


def read(run):
    if run.op_due is None:
        return None
    return 1e3 * float(np.median(run.op_start - run.op_due))
