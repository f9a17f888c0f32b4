"""Device idle share (``device_idle_share``) in a cell whose model is the
granite-4.0-h hybrid: the same reading, under a name of its own so that the
dense cell's entry keeps its list."""
from harness import load_reader


def read(ctx):
    return load_reader("device_idle_share")(ctx)
