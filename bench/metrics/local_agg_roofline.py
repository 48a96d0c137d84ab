"""Local aggregation's share of its roofline: the least time the chip
needs for the step's local aggregations over ``local_agg_ms``, both per
epoch.

The least time counts each aggregation of each partition on its own, at
the feature widths of the model's family (``local_agg_widths``): the
larger of ``2 E_local F`` operations at the chip's peak and
``4 F (N_src + N_dst) + 8 E_local`` bytes at its memory bandwidth
(``work.ell_cost``), from the partition's real rows and local edges.  At
these sizes the memory bound applies.  Moves ``epoch_s``.
"""

import harness
import work

UNIT = "%"


def read(run):
    ms = harness.load_module("metrics", "local_agg_ms").read(run)
    if not ms:
        return None
    widths = run.family.local_agg_widths(run.model, run.in_dim, run.out_dim)
    least = work.local_agg_least(run.counts, widths, run.peak)
    # partitions spread over chips: each chip's share of the least time
    return 100.0 * 1e3 * least / run.chips / ms
