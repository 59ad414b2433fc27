"""Mean device time per window batch of the megastep's unit-carry
refresh: the operations under the named scopes ``maintain/<pattern>/refresh``
(the join-tree executor's ``lax.cond``), from the trace."""

import progtrace


def read(run):
    return progtrace.scope_ms_per_batch(run, "maintain/*/refresh")
