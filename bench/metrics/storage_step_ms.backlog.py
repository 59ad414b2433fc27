"""Mean execute time of the storage step (``storage_update``, by
``obs.jaxprof``) over the window's batches."""


def read(run):
    if run.arrivals != "closed" or not run.batches:
        return None
    return 1e3 * sum(b["storage_s"] for b in run.batches) / len(run.batches)
