"""Mean device time per window batch of the storage step's in-place patch
of the partitions (``patch_partition`` and its ``member_probe`` calls):
the operations under the named scope ``storage/patch``, from the trace."""

import progtrace


def read(run):
    return progtrace.scope_ms_per_batch(run, "storage/patch")
