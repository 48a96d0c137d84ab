"""Compile-only checks of the Pallas kernels, and of the local
aggregation that replaced one, for a TPU v5e chip.

Nothing runs here: each test lowers and compiles a kernel for one chip of
a ``v5e:2x2`` topology that is described, not attached, so what Mosaic
refuses — a block shape off the (8, 128) tiling, an op it cannot lower,
VMEM over budget — fails on a machine without a chip.  Interpret mode
cannot show any of that; the ``ref.py`` oracle tests pin the values.

Shapes are those of ``chip_smoke.py``: the 169,343-node citation graph
split into Q = 4 partitions of 42,336 rows, its local ELL lists 16 wide
(the partitions' max local in-degree), feature widths 128 and 256.

The wire codecs pick their Pallas branch by asking
``jax.default_backend()``, which sees the CPU here; the tests steer it to
``"tpu"`` with ``monkeypatch`` so the compiled program is the one the
chip would run.
All of these tests live in this one file, so one test worker describes
the topology (and holds the TPU library) for all of them.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.varco_pack import LANE

Q = 4
PART = 42_336             # ceil(169,343 / 4) rows per partition
ELL_K = 16                # max local in-degree of the smoke's partitions
HOP_ROWS = 41_061         # largest per-pair p2p hop of the smoke's graph


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2 topology, with the persistent
    compilation cache off: entries compiled for a described chip cannot
    be read back without one."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch):
    """Steer the runtime's backend checks onto the Pallas branch."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_count(fn, *args) -> int:
    text = jax.jit(fn).lower(*args).compile().as_text()
    return text.count('custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("f", [128, 256])
def test_ell_aggregate_compiles_at_smoke_shapes(one_chip, on_tpu, f):
    """The p2p wire's local aggregation as the emulated backend runs it:
    one partition at a time over Q (``lax.map``), forward and the
    custom-VJP transpose, as XLA row gathers with no Pallas kernel."""
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731

    def loss(*stacks):
        out = jax.lax.map(lambda a: ops.ell_aggregate(*a), stacks)
        return jnp.sum(out ** 2)

    n = _kernel_count(jax.value_and_grad(loss),
                      s((Q, PART, f), jnp.float32),
                      s((Q, PART, ELL_K), jnp.int32),
                      s((Q, PART, ELL_K), jnp.float32),
                      s((Q, PART, ELL_K), jnp.int32),
                      s((Q, PART, ELL_K), jnp.int32))
    assert n == 0, n                       # gathers, not the ELL kernel


@pytest.mark.parametrize("k", [1, 2])
def test_wire_pack_unpack_compile_vmapped(one_chip, on_tpu, k):
    """``varco_pack`` / ``varco_unpack`` with batched indices, the way the
    emulated p2p wire calls them: pack each worker's boundary block, then
    unpack every hop of every worker (``vmap(vmap(...))``)."""
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    nb = 256 // LANE

    def wire(publish, hops, kept, inv):
        packed = jax.vmap(ops.wire_pack)(publish, kept, inv)
        sent = jax.vmap(lambda hp, kk, iv: jax.vmap(
            lambda h_: ops.wire_unpack(h_, kk, iv))(hp))(hops, kept, inv)
        return packed, sent

    n = _kernel_count(wire, s((Q, PART, 256), jnp.float32),
                      s((Q, Q - 1, HOP_ROWS, k * LANE), jnp.float32),
                      s((Q, k), jnp.int32), s((Q, nb), jnp.int32))
    assert n >= 2, n


@pytest.mark.parametrize("k", [1, 2])
@pytest.mark.parametrize("width", [2, 4, 8])
def test_pack_quant_compiles(one_chip, on_tpu, width, k):
    """The fused gather + quantise + bit-pack kernel behind
    ``ops.pack_quant`` (rows padded to its tile there)."""
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    n = _kernel_count(
        lambda x, kept: ops.pack_quant(x, kept, width=width),
        s((PART, 256), jnp.float32), s((k,), jnp.int32))
    assert n == 1, n


@pytest.mark.parametrize("k", [1, 2])
@pytest.mark.parametrize("width", [2, 4, 8])
def test_unpack_quant_compiles(one_chip, on_tpu, width, k):
    """The fused bit-unpack + dequantise + scatter kernel behind
    ``ops.unpack_quant``."""
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    bpb = LANE * width // 8
    n = _kernel_count(
        lambda p, sc, inv: ops.unpack_quant(p, sc, inv, width=width),
        s((PART, k * bpb), jnp.uint8), s((PART, k), jnp.float32),
        s((256 // LANE,), jnp.int32))
    assert n == 1, n
