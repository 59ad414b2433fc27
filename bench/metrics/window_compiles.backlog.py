"""Executables compiled or loaded from the cache inside the window (JAX's
backend-compile events); a warm run has none."""


def read(run):
    if run.arrivals != "closed":
        return None
    return float(run.window_compiles)
