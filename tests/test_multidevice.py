"""Multi-device semantics via subprocess (8 virtual CPU devices).

The main test process must keep the single real device (smoke tests &
benches), so anything needing a mesh runs in a fresh interpreter with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""

import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, timeout: int = 600) -> str:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


SHARD_MAP_EQUIV = """
import jax, jax.numpy as jnp
from repro.graph import tiny_graph, partition_graph
from repro.nn import GNNConfig, init_gnn
from repro.dist.gnn_parallel import (DistMeta, make_train_step,
                                     make_worker_mesh, shard_graph,
                                     make_eval_step)
from repro.core import varco
from repro.train.optim import adamw

g = tiny_graph(n=256)
cfg = GNNConfig(conv='sage', in_dim=g.feat_dim, hidden=32,
                out_dim=g.num_classes, layers=3)
params = init_gnn(jax.random.key(0), cfg)
pg = partition_graph(g, 8, scheme='random')
graph = pg.device_arrays()
meta = DistMeta.build(pg, params)
opt = adamw(1e-2); opt_state = opt.init(params)
pol = varco(total_steps=20, slope=5)

p_e, s_e = params, opt_state
step_e = make_train_step(cfg, pol, opt, meta)
for i in range(6):
    p_e, s_e, m_e = step_e(p_e, s_e, graph, jnp.asarray(i), jax.random.key(i))

mesh = make_worker_mesh(8)
gs = shard_graph(graph, mesh)
step_s = make_train_step(cfg, pol, opt, meta, mesh=mesh)
p_s, s_s = params, opt_state
for i in range(6):
    p_s, s_s, m_s = step_s(p_s, s_s, gs, jnp.asarray(i), jax.random.key(i))

d = max(float(jnp.abs(a - b).max())
        for a, b in zip(jax.tree.leaves(p_e), jax.tree.leaves(p_s)))
assert d < 1e-5, d
assert abs(float(m_e['loss']) - float(m_s['loss'])) < 1e-5
ev = make_eval_step(cfg, meta, mesh=mesh)(p_s, gs)
assert 0 <= float(ev['test']) <= 1
print('SHARD_MAP_OK', d)
"""


FEDAVG_MODE = """
import jax, jax.numpy as jnp
from repro.graph import tiny_graph, partition_graph
from repro.nn import GNNConfig, init_gnn
from repro.dist.gnn_parallel import (DistMeta, make_train_step,
                                     make_worker_mesh, shard_graph)
from repro.core import FULL_COMM
from repro.train.optim import sgd

g = tiny_graph(n=256)
cfg = GNNConfig(conv='sage', in_dim=g.feat_dim, hidden=16,
                out_dim=g.num_classes, layers=2)
params = init_gnn(jax.random.key(0), cfg)
pg = partition_graph(g, 4, scheme='random')
graph = pg.device_arrays()
meta = DistMeta.build(pg, params)
opt = sgd(1e-2)

mesh = make_worker_mesh(4)
gs = shard_graph(graph, mesh)
# with plain SGD, fedavg (avg of local steps) == grad-psum (avg gradient)
pa, sa = params, opt.init(params)
step_a = make_train_step(cfg, FULL_COMM, opt, meta, mesh=mesh, sync='grad')
pb, sb = params, opt.init(params)
step_b = make_train_step(cfg, FULL_COMM, opt, meta, mesh=mesh, sync='fedavg')
for i in range(3):
    pa, sa, _ = step_a(pa, sa, gs, jnp.asarray(i), jax.random.key(i))
    pb, sb, _ = step_b(pb, sb, gs, jnp.asarray(i), jax.random.key(i))
# grad mode sums grads (then opt applies lr once); fedavg averages local
# SGD steps — identical iff update is linear in grad and grads are summed
# with the same normalisation. Our local loss divides by GLOBAL train count,
# so psum(grad) == sum of local grads == full gradient, while fedavg's
# parameter mean applies lr to each local grad then averages:
#   mean_q(p - lr g_q) = p - lr mean_q(g_q) = p - lr/Q * full_grad.
# So fedavg == grad mode with lr/Q. Verify that relationship instead.
import numpy as np
da = jax.tree.map(lambda a, b: np.asarray(a - b), pa, params)
db = jax.tree.map(lambda a, b: np.asarray(a - b), pb, params)
la = jax.tree.leaves(da); lb = jax.tree.leaves(db)
# after 1 step relationship is exact; after 3 it's approximate — test 1 step
pa1, _, _ = step_a(params, opt.init(params), gs, jnp.asarray(0),
                   jax.random.key(0))
pb1, _, _ = step_b(params, opt.init(params), gs, jnp.asarray(0),
                   jax.random.key(0))
for a, b in zip(jax.tree.leaves(jax.tree.map(lambda x, y: x - y, pa1, params)),
                jax.tree.leaves(jax.tree.map(lambda x, y: x - y, pb1, params))):
    a = np.asarray(a); b = np.asarray(b)
    scale = np.abs(a).max() + 1e-12
    np.testing.assert_allclose(a / scale, 4.0 * b / scale,
                               rtol=0, atol=2e-3)
print('FEDAVG_OK')
"""


COLLECTIVES = """
import functools
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from repro.core.collectives import (compressed_all_gather, compressed_psum,
                                    compressed_all_to_all, uncompressed_bits)
from repro.core.compression import get_compressor

mesh = Mesh(np.array(jax.devices()[:4]), ('w',))
c = get_compressor('randmask')
x = jax.random.normal(jax.random.key(0), (4, 8, 16))

@functools.partial(shard_map, mesh=mesh, in_specs=P('w'), out_specs=(P('w'), P()),
                   check_vma=False)
def gather_rate1(xs):
    g, bits = compressed_all_gather(xs[0], 'w', compressor=c,
                                    rate=jnp.float32(1.0),
                                    key=jax.random.key(1))
    return g[None], bits

g, bits = gather_rate1(x)
np.testing.assert_allclose(np.asarray(g[0]), np.asarray(x), rtol=1e-6)
assert float(bits) == 3 * x.size / 4 * 32 * 4 / 4 * 4 / 4 or True
# exact: per-device bits = 8*16*32 ; psum -> 4x ; *(Q-1)=3
assert float(bits) == 4 * 8 * 16 * 32 * 3, float(bits)

@functools.partial(shard_map, mesh=mesh, in_specs=P('w'), out_specs=(P('w'), P()),
                   check_vma=False)
def psum_rate1(xs):
    s, bits = compressed_psum(xs[0], 'w', compressor=c,
                              rate=jnp.float32(1.0), key=jax.random.key(1))
    return s[None], bits

s, bits = psum_rate1(x)
np.testing.assert_allclose(np.asarray(s[0]), np.asarray(x.sum(0)), rtol=1e-5)

@functools.partial(shard_map, mesh=mesh, in_specs=P('w'), out_specs=(P('w'), P()),
                   check_vma=False)
def a2a_rate1(xs):
    o, bits = compressed_all_to_all(xs[0], 'w', compressor=c,
                                    rate=jnp.float32(1.0),
                                    key=jax.random.key(1))
    return o[None], bits

xa = jax.random.normal(jax.random.key(2), (4, 4, 16))
o, _ = a2a_rate1(xa)
np.testing.assert_allclose(np.asarray(o), np.asarray(xa.transpose(1, 0, 2)),
                           rtol=1e-6)
print('COLLECTIVES_OK')
"""


SMALL_DRYRUN = """
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.dist.sharding import activation_sharding, param_shardings
from repro.launch.mesh import make_small_mesh
from repro.launch.steps import make_train_step, make_optimizer
from repro.models.transformer import init_lm

cfg = get_config('granite-3-2b', smoke=True)
mesh = make_small_mesh(2, 4)
params_s = jax.eval_shape(lambda: init_lm(jax.random.key(0), cfg))
p_sh = param_shardings(params_s, mesh)
opt = make_optimizer(cfg)
opt_s = jax.eval_shape(opt.init, params_s)
o_sh = param_shardings(opt_s, mesh)
batch = {'tokens': jax.ShapeDtypeStruct((8, 128), jnp.int32,
         sharding=NamedSharding(mesh, P('data')))}
step = make_train_step(cfg, opt)

def wrapped(p, o, b):
    with activation_sharding(mesh):
        return step(p, o, b)

params_in = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                         sharding=sh), params_s, p_sh)
opt_in = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                      sharding=sh), opt_s, o_sh)
fn = jax.jit(wrapped, in_shardings=(p_sh, o_sh, None),
             out_shardings=(p_sh, o_sh, None))
compiled = fn.lower(params_in, opt_in, batch).compile()
mem = compiled.memory_analysis()
assert mem is not None
print('SMALL_DRYRUN_OK')
"""


@pytest.mark.slow
def test_shard_map_matches_emulated():
    assert "SHARD_MAP_OK" in run_py(SHARD_MAP_EQUIV)


@pytest.mark.slow
def test_fedavg_mode_relationship():
    assert "FEDAVG_OK" in run_py(FEDAVG_MODE)


@pytest.mark.slow
def test_compressed_collectives_rate1_exact():
    assert "COLLECTIVES_OK" in run_py(COLLECTIVES)


@pytest.mark.slow
def test_small_mesh_dryrun_compiles():
    assert "SMALL_DRYRUN_OK" in run_py(SMALL_DRYRUN)
