"""The program names its layers (``repro.tracing``).

Device scopes: the compiled train and eval programs carry each layer's
``jax.named_scope`` in the HLO ``op_name`` metadata, on the emulated
backend and under ``shard_map`` on 4 virtual CPU devices (subprocess: the
main test process keeps its single device).  Host spans: ``train_gnn``
fills the span table, returns it as ``TrainResult.spans`` and closes every
span when ``log_fn`` raises.  The persistent compilation cache keys on the
names, so a cached executable profiles under the names of the program that
asks for it.
"""

import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from parity import build_setup

from repro import tracing
from repro.core import FULL_COMM
from repro.core.varco import CommPolicy
from repro.dist.gnn_parallel import DistMeta, make_eval_step, make_train_step
from repro.graph import tiny_graph
from repro.train.optim import adamw
from repro.train.trainer import train_gnn

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_INSTR = re.compile(r"^\s*(?:ROOT )?%\S+ = .*? ([\w\-]+)\(.*"
                    r'op_name="([^"]*)"')


def scoped_ops(hlo_text: str) -> list[tuple[str, set]]:
    """``(opcode, scope names)`` of every instruction that carries an
    ``op_name``; transforms wrap scopes (``transpose(jvp(layer0))``), so
    the names are the identifiers of the ``op_name``."""
    out = []
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            out.append((m.group(1), set(re.findall(r"[A-Za-z_]\w*",
                                                   m.group(2)))))
    return out


def _p2p_program(q: int = 4):
    g, cfg, params, pg, graph = build_setup(q, f=128, layers=3, n=256,
                                            hidden=128)
    meta = DistMeta.build(pg, params, wire="p2p")
    opt = adamw(1e-2)
    return cfg, params, opt, opt.init(params), meta, graph


@pytest.fixture(scope="module")
def emulated():
    cfg, params, opt, opt_state, meta, graph = _p2p_program()
    step = make_train_step(cfg, FULL_COMM, opt, meta)
    train = step.lower(params, opt_state, graph, jnp.asarray(0),
                       jax.random.key(0)).compile().as_text()
    evaluate = make_eval_step(cfg, meta)
    ev = evaluate.lower(params, graph).compile().as_text()
    return scoped_ops(train), scoped_ops(ev)


def _check_train_scopes(ops, backend: str) -> None:
    names = set().union(*(s for _, s in ops))
    for scope in ("halo", "local_agg", "remote_agg", "dense", "loss",
                  "optimizer", "layer0", "layer1", "layer2"):
        assert scope in names, (backend, scope)
    # the custom-VJP local aggregation is named in both directions
    assert any("local_agg" in s and "transpose" in s for _, s in ops)
    assert any("local_agg" in s and "transpose" not in s for _, s in ops)
    # the remote scatter, forward and transposed, under remote_agg
    assert any(op == "scatter" and "remote_agg" in s for op, s in ops)
    # Adam's square roots are the optimizer's and nobody else's
    sqrt = [s for op, s in ops if op == "sqrt"]
    assert sqrt and all("optimizer" in s for s in sqrt)
    # nothing of the train step is named as evaluation
    assert "eval" not in names


def test_train_step_scopes_emulated(emulated):
    train, _ = emulated
    _check_train_scopes(train, "emulated")
    # every product of the step is a dense layer's
    dots = [s for op, s in train if op == "dot"]
    assert dots and all("dense" in s for s in dots)


def test_eval_program_is_under_eval(emulated):
    _, ev = emulated
    named = [s for _, s in ev if "jit" in s]
    assert named and all("eval" in s for s in named)
    assert not [s for _, s in ev if "optimizer" in s]


SHARD_SCOPES = """
import json, sys
sys.path.insert(0, {tests!r})
import jax, jax.numpy as jnp
from test_tracing import _p2p_program, scoped_ops
from repro.core import FULL_COMM
from repro.dist.gnn_parallel import (make_eval_step, make_train_step,
                                     make_worker_mesh, shard_graph)
cfg, params, opt, opt_state, meta, graph = _p2p_program()
mesh = make_worker_mesh(4)
gs = shard_graph(graph, mesh)
step = make_train_step(cfg, FULL_COMM, opt, meta, mesh=mesh)
train = step.lower(params, opt_state, gs, jnp.asarray(0),
                   jax.random.key(0)).compile().as_text()
ev = make_eval_step(cfg, meta, mesh=mesh).lower(params, gs).compile().as_text()
print(json.dumps([[[op, sorted(s)] for op, s in scoped_ops(t)]
                  for t in (train, ev)]))
"""


def test_train_step_scopes_shard_map():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=SRC,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = SHARD_SCOPES.format(tests=os.path.dirname(__file__))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr
    train, ev = ([(op, set(s)) for op, s in prog]
                 for prog in json.loads(out.stdout.splitlines()[-1]))
    _check_train_scopes(train, "shard_map")
    # the ring's hops are the halo's; the step's all-reduces the sync's
    permutes = [s for op, s in train if op == "collective-permute"]
    assert permutes and all("halo" in s for s in permutes)
    reduces = [s for op, s in train if op == "all-reduce"]
    assert reduces and all("grad_sync" in s for s in reduces)
    named = [s for _, s in ev if "jit" in s]
    assert named and all("eval" in s for s in named)


def _run(epochs=3, eval_every=2, log_fn=None):
    g = tiny_graph(n=256)
    return train_gnn(g, q=4, policy=CommPolicy.parse("full", epochs),
                     epochs=epochs, hidden=16, layers=2, eval_every=eval_every,
                     wire="p2p", log_fn=log_fn)


SETUP_SPANS = ("setup/partition", "setup/params", "setup/device_arrays",
               "setup/p2p", "setup/meta", "setup/build")


def test_train_gnn_records_spans():
    rows = []
    res = _run(log_fn=rows.append)
    spans = res.spans
    assert spans == tracing.table()
    for name in SETUP_SPANS:
        assert spans[name]["count"] == 1, name
    assert spans["epoch"]["count"] == spans["step"]["count"] == 3
    # evaluations at epochs 0 and 2 (the last), each logged once
    assert spans["eval"]["count"] == spans["log"]["count"] == 2
    # two ledger floats a step; loss, rate and three accuracies an eval
    assert spans["sync"]["count"] == 2 * 3 + 5 * 2
    for row in spans.values():
        assert 0 <= row["first_s"] <= row["total_s"]
    # the epoch spans hold their steps; the first step traces and compiles
    assert spans["epoch"]["total_s"] >= spans["step"]["total_s"]
    assert spans["step"]["first_s"] > spans["step"]["total_s"] / 3
    wall = res.history.wall_s
    assert len(wall) == 2 and 0 <= wall[0] <= wall[1]
    assert [r["epoch"] for r in rows] == [0, 2]


class _Stop(Exception):
    pass


def test_spans_close_when_log_fn_raises():
    def log_fn(row):
        if row["epoch"] > 0:
            raise _Stop

    with pytest.raises(_Stop):
        _run(epochs=5, log_fn=log_fn)
    spans = tracing.table()
    # epochs 0-2 ran; the third's log raised, and its log and epoch spans
    # were closed and counted on the way out
    assert spans["epoch"]["count"] == spans["step"]["count"] == 3
    assert spans["log"]["count"] == 2
    with pytest.raises(_Stop):
        with tracing.span("outer"):
            with tracing.span("inner"):
                raise _Stop
    spans = tracing.table()
    assert spans["outer"]["count"] == spans["inner"]["count"] == 1
    assert spans["outer"]["total_s"] >= spans["inner"]["total_s"]


def test_reset_and_table_copy():
    tracing.reset()
    with tracing.span("a"):
        pass
    with tracing.span("a"):
        pass
    t = tracing.table()
    assert t["a"]["count"] == 2
    t["a"]["count"] = 99
    assert tracing.table()["a"]["count"] == 2
    assert tracing.pull(jnp.arange(3.0)).tolist() == [0.0, 1.0, 2.0]
    assert tracing.pull(jnp.float32(2.5)) == 2.5
    assert tracing.table()["sync"]["count"] == 2
    tracing.reset()
    assert tracing.table() == {}


CACHED_PROGRAM = """
import sys
import jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def f(x):
    with jax.named_scope(sys.argv[1]):
        return jnp.sin(x) * 2.0


jax.jit(f)(jnp.ones(3)).block_until_ready()
"""


def test_compile_cache_keys_on_scope_names_not_paths(tmp_path):
    """A program that differs from a cached one only in its scope names
    compiles anew (an executable carries the names it was compiled
    with); the same program from another directory hits the cache."""
    cache = tmp_path / "cache"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=SRC,
               JAX_COMPILATION_CACHE_DIR=str(cache))

    def compile_in(where, scope):
        script = tmp_path / where / "prog.py"
        script.parent.mkdir(exist_ok=True)
        script.write_text(CACHED_PROGRAM)
        out = subprocess.run([sys.executable, str(script), scope], env=env,
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr
        return {p.name for p in cache.glob("jit_f-*")}

    first = compile_in("a", "halo")
    assert len(first) == 1
    assert compile_in("b", "halo") == first
    assert len(compile_in("a", "local_agg") - first) == 1
