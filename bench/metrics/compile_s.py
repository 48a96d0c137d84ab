"""Seconds of backend compilation during set-up (the same events as
``compiles_in_window``): about 0 once the persistent cache holds every
program.  Moves ``setup_s``.
"""

UNIT = "s"


def read(run):
    return run.compile_s
