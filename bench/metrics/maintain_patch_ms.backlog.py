"""Mean device time per window batch of the megastep's patches: the
operations under the named scopes ``maintain/<pattern>/patch`` (Nav-join
chains, or the delta-seeded generic join and its merge), from the trace."""

import progtrace


def read(run):
    return progtrace.scope_ms_per_batch(run, "maintain/*/patch")
