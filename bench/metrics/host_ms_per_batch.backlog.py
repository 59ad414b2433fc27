"""Mean over the window's batches of the batch's wall time less its two
device steps (``obs.jaxprof`` execute times): the service's host path."""


def read(run):
    if run.arrivals != "closed" or not run.batches:
        return None
    host = [(b["end"] - b["start"]) - b["storage_s"] - b["maintain_s"] for b in run.batches]
    return 1e3 * sum(host) / len(host)
