"""The whole training epoch's share of the chips' peak: the operations
an epoch needs (the model family's ``epoch_flops``: forward and backward
over the real nodes and edges, and the periodic evaluation's forward at
one in ``eval_every``) over ``epoch_s``, over chips times the bf16 peak.
Moves ``epoch_s``.
"""

UNIT = "%"


def read(run):
    c = run.counts
    flops = run.family.epoch_flops(run.model, c["nodes"], c["edges"],
                                   run.in_dim, run.out_dim,
                                   run.traffic["eval_every"])
    return 100.0 * flops / run.epoch_s / (run.chips *
                                          run.peak["flops_per_s"])
