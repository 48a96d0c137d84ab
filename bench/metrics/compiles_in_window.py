"""Backend compilations inside the window (``jax.monitoring``'s
``backend_compile_duration`` events; a persistent-cache hit is none).
Every shape is warmed up in set-up, so a count above 0 is the program
recompiling while it trains.  Moves ``epoch_s``.
"""

UNIT = "count"


def read(run):
    return float(run.compiles_in_window)
