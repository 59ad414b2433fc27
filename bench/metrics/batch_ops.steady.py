"""Mean journal ops per batch in the window, as the scheduler sized them."""


def read(run):
    if run.arrivals == "closed" or not run.batches:
        return None
    return sum(b["ops"] for b in run.batches) / len(run.batches)
