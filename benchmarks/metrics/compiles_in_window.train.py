"""Programs built inside the window (``jax.monitoring`` backend-compile
events, which a persistent-cache load fires too).  Should read 0."""


def read(run):
    return run["work"]["compiles_in_window"]
