"""XLA compiles plus persistent-cache reads inside the measured window
(``jax.monitoring``); 0 when set-up warmed every shape.  Reads
``window_compiles.tick`` and ``window_compiles.replay``."""


def read(inputs):
    return float(inputs["window_compiles"])
