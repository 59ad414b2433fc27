"""Execute time of the steps ``register()`` ran (``list:*``,
``init_store:*``, ``unit_refresh:*``), by ``obs.jaxprof``."""


def read(run):
    return run.register["device_s"]
