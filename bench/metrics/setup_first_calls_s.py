"""Seconds of the first train-step and evaluation calls: the first
``step`` span plus the first ``eval`` span of the program's span table
(trace, lower, cache load or compile, first dispatch; ``compile_s`` is
part of it).  Moves ``setup_s``.
"""

import spans

UNIT = "s"


def read(run):
    table = spans.program_table()
    if "step" not in table:
        return None
    return table["step"]["first_s"] + table.get("eval", {}).get("first_s",
                                                                  0.0)
