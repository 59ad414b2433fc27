"""Wall time of ``register()`` until the initial listing is on the
device, less the compile (lowering, compiling or loading) it contained."""


def read(run):
    return run.register["wall_s"] - run.register["compile_s"]
