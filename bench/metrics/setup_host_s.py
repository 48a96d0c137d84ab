"""Seconds of ``train_gnn``'s host set-up: the sum of the program's
``setup/*`` spans (partition, device arrays, p2p halo arrays, parameters
and metadata, step builders) from its span table.  Moves ``setup_s``.
"""

import spans

UNIT = "s"


def read(run):
    setup = [row["total_s"] for name, row in spans.program_table().items()
             if name.startswith("setup/")]
    return sum(setup) if setup else None
