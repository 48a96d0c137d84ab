"""The trace reduction on a small trace recorded on a TPU v5e: the
``sage3-arxiv.random.full`` cell cut to 1,500 nodes, five traced epochs
(``data/small_trace``)."""

from pathlib import Path

import pytest

import harness
import xplane

TRACE = Path(__file__).parent / "data" / "small_trace"
EPOCHS = 5


@pytest.fixture(scope="module")
def red():
    return xplane.reduce(TRACE, 1)


def metric(name):
    return harness.load_module("metrics", name)


def test_busy_within_window(red):
    assert 0 < red.busy_s <= red.window_s


def test_every_local_aggregation_is_found(red, is_ell_kernel):
    # five aggregations a step (three forward, two transposed), one
    # launch per partition, four partitions
    ell = [op for op in red.ops if is_ell_kernel(op)]
    assert len(ell) == EPOCHS * 5 * 4
    assert all("s32[" in op.operands.split(",")[0] for op in ell)


def test_scatters_and_products_are_found(red):
    scat = [op for op in red.ops if metric("scatter_ms").is_scatter(op)]
    mm = [op for op in red.ops if metric("matmul_ms").is_matmul(op)]
    assert scat and mm
    assert not set(scat) & set(mm)
    assert red.op_seconds(lambda op: False) is None


def test_containers_are_not_work(red):
    assert not [op for op in red.ops if op.opcode in xplane.CONTAINERS]


def test_breakdown_shape(red):
    b = red.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    # the longest gaps fall where the host's Python thread waits
    assert all(not label.startswith("host: no span")
               for label, _ in b["idle_gaps"])


def test_pinned_readings(red, is_ell_kernel):
    """Read by hand from this trace: 3,808 work ops, 10.40 ms busy."""
    assert len(red.ops) == 3808
    assert red.busy_s == pytest.approx(0.010399775)
    assert red.op_seconds(is_ell_kernel) == pytest.approx(
        5 * 0.1456072e-3)


def test_op_text_parsing(is_ell_kernel):
    op = xplane.Op(0, '%closed_call.54 = f32[42368,256]{1,0:T(8,128)} '
                   'custom-call(s32[42368,15]{1,0} %pad.1, f32[42368,15]'
                   '{1,0} %pad.2, f32[42496,256]{1,0} %pad.3), '
                   'custom_call_target="tpu_custom_call"', 0, 10)
    assert op.name == "closed_call.54"
    assert op.shape == "f32[42368,256]{1,0:T(8,128)}"
    assert op.opcode == "custom-call"
    assert op.operands.startswith("s32[42368,15]")
    assert is_ell_kernel(op)
    tup = xplane.Op(0, "%while.2 = (s32[], f32[4,8]{1,0}) while((s32[], "
                    "f32[4,8]{1,0}) %tuple.1), condition=%c, body=%b", 0, 1)
    assert tup.opcode == "while" and tup.shape == "(s32[], f32[4,8]{1,0})"


def test_union_and_gaps():
    total, gaps = xplane._union_ns([(0, 10), (5, 12), (20, 25)])
    assert total == 17 and gaps == [(12, 20)]
