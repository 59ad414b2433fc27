"""Mean execute time of the maintain megastep (``maintain_mega``, by
``obs.jaxprof``) over the window's batches."""


def read(run):
    if run.arrivals != "closed" or not run.batches:
        return None
    return 1e3 * sum(b["maintain_s"] for b in run.batches) / len(run.batches)
