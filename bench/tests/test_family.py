"""A model family enters through new files only: a configuration, a
workload and ``models/poly.py`` under ``data/``, run by the unchanged
harness on the CPU with the chip look skipped."""

from pathlib import Path

import pytest

import check
import harness
import reference

DATA = Path(__file__).parent / "data"
WORKLOAD = "poly2-small.random.full"
SEED = 2**31 + 23


@pytest.fixture(scope="module")
def kept():
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "ROOTS", [DATA, *harness.ROOTS])
        kept = {}
        result = harness.run(WORKLOAD, SEED, 0.0, False, require_chip=False,
                             keep=kept)
        limits = harness.load("workloads", WORKLOAD)["limits"]
    return {**kept, "result": result, "limits": limits}


def test_program_matches_reference(kept):
    assert kept["family"].__name__ == "bench_models_poly"
    assert any("taps" in k for k in kept["prog"]["grad1"])
    assert kept["result"]["correct"], kept["result"]["checks"]


def test_control_fails(kept):
    """The reference at the next precision down fails a limit."""
    ctl = reference.train_readings(kept["family"], kept["arrays"],
                                   kept["model"], SEED,
                                   harness.CHECKED_STEPS, "high")
    ok, shown = check.judge(check.numbers(ctl, kept["ref"]), kept["limits"])
    assert not ok, shown


def test_the_residual_reaches_the_program(kept):
    """Without its residual the reference no longer matches the program:
    the model's fields beyond ``conv``, ``hidden`` and ``layers`` reach
    the program's model."""
    model = {**kept["model"], "residual": False}
    plain = reference.train_readings(kept["family"], kept["arrays"], model,
                                     SEED, harness.CHECKED_STEPS,
                                     model["matmul_precision"])
    ok, shown = check.judge(check.numbers(kept["prog"], plain),
                            kept["limits"])
    assert not ok, shown


def test_work_counts_by_hand(kept):
    """K = 2 taps, 128 -> 256 -> 256 -> 40, at the arxiv cell's sizes."""
    poly, model = kept["family"], kept["model"]
    n, e = 169_343, 2_339_410
    assert poly.local_agg_widths(model, 128, 40) == [128, 256, 256, 256, 256]
    fwd = (4 * n * 128 * 256 + 2 * e * 128
           + 4 * n * 256 * 256 + 2 * e * 256
           + 4 * n * 256 * 40 + 2 * e * 256)
    weight_grads = 4 * n * (128 * 256 + 256 * 256 + 256 * 40)
    input_grads = 4 * n * (256 * 256 + 256 * 40) + 2 * e * (256 + 256)
    assert poly.epoch_flops(model, n, e, 128, 40, 5) == \
        fwd + weight_grads + input_grads + fwd / 5
    three = {**model, "k_taps": 3}
    assert poly.local_agg_widths(three, 128, 40) == [128, 128] + [256] * 8
