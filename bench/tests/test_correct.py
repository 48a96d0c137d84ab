"""``correct`` on the CPU at a small size: the whole run path with the
chip look skipped, the reference against the program, the control, and
the timed path broken underneath in each way a training cell can break.
"""

import jax.numpy as jnp
import numpy as np
import pytest

import check
import harness
import reference

SMALL = {"graph": {"num_nodes": 1500}}
SEED = 2**31 + 11
CELLS = ["sage3-arxiv.random.full", "sage3-products.random.full"]


def run(workload):
    return harness.run(workload, SEED, 0.0, False, require_chip=False,
                       config_overrides=SMALL)


@pytest.mark.parametrize("workload", CELLS)
def test_program_matches_reference(workload):
    r = run(workload)
    assert r["correct"], r["checks"]
    assert r["metrics"] == {}               # no device metric off the chip
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails(workload):
    """The reference at the next precision down fails a limit."""
    kept = {}
    harness.run(workload, SEED, 0.0, False, require_chip=False,
                config_overrides=SMALL, keep=kept)
    ctl = reference.train_readings(kept["family"], kept["arrays"],
                                   kept["model"], SEED,
                                   harness.CHECKED_STEPS, "high")
    ok, shown = check.judge(check.numbers(ctl, kept["ref"]),
                            harness.load("workloads", workload)["limits"])
    assert not ok, shown


def _unchanged(make_train_step):
    def build(*a, **k):
        step = make_train_step(*a, **k)

        def frozen(params, opt_state, *rest):
            _, _, m = step(params, opt_state, *rest)
            return params, opt_state, m
        return frozen
    return build


def _loss_altered(make_train_step):
    def build(*a, **k):
        step = make_train_step(*a, **k)

        def altered(*args):
            p, s, m = step(*args)
            return p, s, {**m, "loss": m["loss"] * 1.01}
        return altered
    return build


def _half_batch(loss_and_correct):
    def half(logits, labels, mask):
        keep = mask & (jnp.cumsum(mask, axis=-1) % 2 == 0)
        loss, correct = loss_and_correct(logits, labels, keep)
        return 2.0 * loss, correct
    return half


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "no_exchange", "loss_altered"])
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    from repro.dist import gnn_parallel
    from repro.train import trainer
    if fault == "state_unchanged":
        monkeypatch.setattr(trainer, "make_train_step",
                            _unchanged(trainer.make_train_step))
    elif fault == "loss_altered":
        monkeypatch.setattr(trainer, "make_train_step",
                            _loss_altered(trainer.make_train_step))
    elif fault == "half_batch":
        monkeypatch.setattr(gnn_parallel, "masked_loss_and_correct",
                            _half_batch(gnn_parallel.masked_loss_and_correct))
    else:
        from repro.core.varco import CommPolicy
        monkeypatch.setattr(CommPolicy, "parse",
                            staticmethod(lambda *a, **k: CommPolicy("none")))
    r = run(CELLS[0])
    assert not r["correct"], r["checks"]


def test_flat_leaves_leave_the_change_out():
    ref = {"loss": np.ones(3), "grad1": {"a": np.ones(4), "b": np.ones(4),
                                         "c": np.zeros(4)},
           "p0": {k: np.zeros(4) for k in "abc"},
           "pn": {"a": np.ones(4), "b": np.ones(4), "c": np.full(4, 1e-9)}}
    prog = {**ref, "pn": {**ref["pn"], "c": np.full(4, 0.5)}}
    assert check.numbers(prog, ref)["change_gap"] == 0.0
