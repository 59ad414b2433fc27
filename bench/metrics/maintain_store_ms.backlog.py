"""Mean device time per window batch of the megastep's store upkeep: the
operations under the named scopes ``maintain/<pattern>/store`` (delete
filter, merge of the patch into the store, count), from the trace."""

import progtrace


def read(run):
    return progtrace.scope_ms_per_batch(run, "maintain/*/store")
