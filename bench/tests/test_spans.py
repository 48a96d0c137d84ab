"""The scope reduction (``spans.py``) on a small trace recorded on a TPU
v5e with the scoped program (``data/scoped_trace``): the
``sage3-arxiv.random.full`` cell cut to 1,500 nodes, seed 2900000010, a
window of five epochs, recorded on the chip by

    harness.run("sage3-arxiv.random.full", 2900000010, 0.0, True,
                config_overrides={"graph": {"num_nodes": 1500}})

and the readers of the per-layer metrics built on it.
"""

import shutil
import types
from pathlib import Path

import jax
import numpy as np
import pytest

import harness
import spans
import work
import xplane

DATA = Path(__file__).parent / "data"
TRACE = DATA / "scoped_trace"
EPOCHS = 5
NEW_METRICS = ("halo_ms", "local_agg_ms", "remote_agg_ms", "eval_ms",
               "unscoped_ms", "loop_host_ms", "syncs_per_epoch")


@pytest.fixture(scope="module")
def red():
    return spans.reduce(TRACE, 1)


def metric(name):
    return harness.load_module("metrics", name)


def _xla_ops(path):
    """``(name, start ns, duration ns)`` of the ``XLA Ops`` line as
    ``ProfileData`` reads it, and as the decoder does."""
    pd = jax.profiler.ProfileData.from_file(str(path))
    want = [(e.name, e.start_ns, e.duration_ns)
            for p in pd.planes if p.name == "/device:TPU:0"
            for line in p.lines if line.name == "XLA Ops"
            for e in line.events]
    plane = next(p for p in spans.planes(path) if p.name == "/device:TPU:0")
    events = dict(plane.lines)["XLA Ops"]
    got = [(plane.metadata[mid][0], s / 1e3, d / 1e3) for mid, s, d in events]
    return want, got


@pytest.mark.parametrize("trace", ["scoped_trace", "small_trace"])
def test_decoder_reads_what_profiledata_reads(trace):
    path = next((DATA / trace).glob("**/*.xplane.pb"))
    want, got = _xla_ops(path)
    assert len(got) == len(want) > 0
    for (wn, ws, wd), (gn, gs, gd) in zip(want, got):
        assert gn == wn
        assert abs(gs - ws) < 1 and abs(gd - wd) < 1


def xop(op):
    return xplane.Op(op.chip, op.text, op.start_ps // 1000,
                     op.dur_ps // 1000)


def test_every_op_in_one_bucket(red):
    assert not [op for op in red.ops
                if xop(op).opcode in xplane.CONTAINERS]
    assert sum(red.bucket_ops.values()) == len(red.ops)
    assert sum(red.bucket_s.values()) == pytest.approx(red.work_s, rel=1e-12)
    # work ops run one at a time, inside the busy time
    assert 0 < red.work_s <= red.busy_s


def test_ell_launches_are_local_aggregation(red, is_ell_kernel):
    # five aggregations a step (three forward, two transposed), one launch
    # per partition, four partitions, every one under local_agg
    ell = [op for op in red.ops if is_ell_kernel(xop(op))]
    assert len(ell) == EPOCHS * 5 * 4
    assert {op.bucket for op in ell} == {"local_agg"}
    assert sum("transpose(" in op.op_name for op in ell) == EPOCHS * 2 * 4


def test_evaluation_stays_out_of_the_train_buckets(red):
    ev = [op for op in red.ops if "jit(evaluate)" in op.op_name]
    assert ev and {op.bucket for op in ev} == {"eval"}
    for b in ("halo", "local_agg", "remote_agg"):
        assert all("evaluate" not in op.op_name
                   for op in red.ops if op.bucket == b)


def test_host_spans_of_the_window(red):
    # the window opened in epoch 0's log and closed in epoch 5's: epochs
    # 1-4 closed in it, five steps, two ledger reads a step and five at
    # epoch 5's evaluation
    assert red.count("epoch") == EPOCHS - 1
    assert red.count("step") == EPOCHS
    assert red.count("eval") == 1
    assert red.count("sync") == 2 * EPOCHS + 5
    assert red.count("log") == 0
    assert all(label != "no span" for _, label in red.gaps)


def test_pinned_readings(red):
    """Read from this trace: 3,808 work ops, 10.39 ms of work."""
    assert len(red.ops) == 3808
    want_ms = {"eval": 0.935674764, "halo": 0.720875704,
               "local_agg": 1.985501718, "remote_agg": 5.281106954,
               "dense": 0.371621564, "loss": 0.09781539,
               "optimizer": 0.004189844, "layer": 0.090095002,
               "unscoped": 0.90449617}
    assert {b: pytest.approx(1e3 * t, rel=1e-9)
            for b, t in red.bucket_s.items()} == want_ms
    assert 1e3 * red.host_loop_s() == pytest.approx(20.12333, rel=1e-9)


def _fake_run(tmp_path, trace, monkeypatch):
    out = tmp_path / "out"
    shutil.copytree(DATA / trace, out / "trace" / "w")
    monkeypatch.setattr(harness, "OUT", out)
    return types.SimpleNamespace(workload="w", chips=1, epochs=EPOCHS,
                                 trace=object())


def test_readers_on_the_scoped_trace(tmp_path, monkeypatch, red):
    run = _fake_run(tmp_path, "scoped_trace", monkeypatch)
    got = {m: metric(m).read(run) for m in NEW_METRICS}
    assert got["syncs_per_epoch"] == 3.0
    assert got["loop_host_ms"] == pytest.approx(
        1e3 * red.host_loop_s() / EPOCHS)
    for b in ("halo", "local_agg", "remote_agg", "eval", "unscoped"):
        assert got[f"{b}_ms"] == pytest.approx(
            1e3 * red.bucket_s[b] / EPOCHS), b
    # the ELL launches are a part of local_agg
    ell = sum(op.dur_ps for op in red.ops if op.bucket == "local_agg"
              and "pallas_call" in op.op_name) / 1e9 / EPOCHS
    assert got["local_agg_ms"] >= ell > 0


def test_local_agg_roofline_on_the_scoped_trace(tmp_path, monkeypatch, red):
    """The least time of the step's five local aggregations, from the
    recorded cell's own rows and local edges, over ``local_agg_ms``."""
    cfg = harness.load("configs", "sage3-arxiv")
    cfg["graph"]["num_nodes"] = 1500
    arrays = harness.graph_arrays(cfg, 2900000010)
    n = len(arrays["indptr"]) - 1
    owner = np.empty(n, np.int64)
    owner[arrays["perm"]] = np.arange(n) % 4
    run = _fake_run(tmp_path, "scoped_trace", monkeypatch)
    run.family = harness.load_module("models", "sage")
    run.model, run.in_dim, run.out_dim = cfg["model"], 128, 40
    run.peak = work.peak("TPU v5 lite")
    run.counts = work.partition_counts(arrays["indptr"], arrays["indices"],
                                       owner, 4)
    got = metric("local_agg_roofline").read(run)
    least = sum(work.ell_cost(f, int(r), int(e))[1] / 819e9
                for f in (128, 256, 256, 256, 256)
                for r, e in zip(run.counts["rows"],
                                run.counts["local_edges"]))
    ms = metric("local_agg_ms").read(run)
    assert got == pytest.approx(100 * 1e3 * least / ms)
    assert 0 < got < 100


def test_readers_on_an_unscoped_program(tmp_path, monkeypatch):
    """PR 13's trace, of a program that names nothing: every new reader
    finds nothing to read, as on the parent commit."""
    run = _fake_run(tmp_path, "small_trace", monkeypatch)
    assert {m: metric(m).read(run) for m in NEW_METRICS} == dict.fromkeys(
        NEW_METRICS)
    assert metric("local_agg_roofline").read(run) is None


def test_set_up_readers_read_the_span_table():
    from repro import tracing
    tracing.reset()
    for name in ("setup/partition", "setup/meta", "step", "step", "eval"):
        with tracing.span(name):
            pass
    table = tracing.table()
    assert metric("setup_host_s").read(None) == pytest.approx(
        table["setup/partition"]["total_s"] + table["setup/meta"]["total_s"])
    assert metric("setup_first_calls_s").read(None) == pytest.approx(
        table["step"]["first_s"] + table["eval"]["first_s"])
    tracing.reset()
    assert metric("setup_host_s").read(None) is None
    assert metric("setup_first_calls_s").read(None) is None


@pytest.mark.parametrize("op_name,want", [
    ("jit(evaluate)/eval/layer0/vmap(halo)/gather", "eval"),
    ("jit(_jit_step)/transpose(jvp(layer1))/while/body/closed_call/"
     "local_agg/pallas_call", "local_agg"),
    ("jit(_jit_step)/jvp(layer2)/vmap(remote_agg)/scatter-add", "remote_agg"),
    ("jit(step)/jit(sm)/shard_map/grad_sync/psum", "optimizer"),
    ("jit(_jit_step)/jvp(layer2)/add", "layer"),
    ("jit(_jit_step)/jvp()/while/body/closed_call/pallas_call", "unscoped"),
    ("jit(f)/relayer0/evaluate/halos", "unscoped"),
    ("", "unscoped"),
])
def test_bucket_order(op_name, want):
    assert spans.bucket(op_name) == want


def test_host_loop_leaves_out_waits():
    s = spans.Span
    red = spans.Scoped(
        chips=1, ops=[], busy_s=0.0, work_s=0.0, bucket_s={}, bucket_ops={},
        scoped=True, gaps=[],
        spans=[s("epoch", 100, 50), s("step", 101, 5), s("sync", 110, 30),
               s("epoch", 150, 60), s("sync", 160, 20), s("log", 185, 20),
               s("step", 210, 4), s("sync", 215, 10)])
    # from 100 to 225, less 30 + 20 + 10 of syncs and 20 of log
    assert red.host_loop_s() == pytest.approx((125 - 80) / 1e12)
    assert red.count("sync") == 3
