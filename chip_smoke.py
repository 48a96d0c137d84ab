#!/usr/bin/env python3
"""Smoke run of the paper's main training path on a TPU.

Trains the paper's §V model — 3-layer GraphSAGE, 256 hidden units — on a
169,343-node citation graph of OGBN-Arxiv's shape (128 features, 40
classes, average degree 13.8, generated from a seed), split into Q = 4
partitions, through ``repro.train.train_gnn`` over the p2p halo wire.

    python chip_smoke.py                # one chip: Q = 4 emulated on it
    python chip_smoke.py --four-chips   # Q = 4 under shard_map, one
                                        # partition per chip, against the
                                        # emulated run on one chip

One chip runs four phases: the Pallas kernels against their jnp oracles,
host set-up, the full-communication distributed forward against
``centralized_forward``, and training under ``full`` and under
``auto:error:<B>:w4`` with ``B`` half of ``full``'s transport over the
same epochs.  ``--four-chips`` runs only the shard_map training and the
emulated run it is compared with.

Every phase prints one JSON line; the last line is ``{"ok": true,
"device": {...}}``.  The script exits non-zero, without that line, when
JAX finds no TPU, when any phase fails a check, or when a compiled train
step holds no Pallas kernel (``tpu_custom_call``).  Step times are smoke
timings of a single run, not benchmark results.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core.varco import FULL_COMM, CommPolicy  # noqa: E402
from repro.dist.gnn_parallel import (DistMeta,  # noqa: E402
                                     _make_aggregate_emulated,
                                     make_train_step, make_worker_mesh,
                                     plan_statics, shard_graph)
from repro.dist.halo import attach_p2p  # noqa: E402
from repro.dist.ratectl import (init_wire_residuals,  # noqa: E402
                                make_auto_train_step, make_controller)
from repro.graph.partition import partition_graph  # noqa: E402
from repro.graph.synthetic import citation_graph  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.kernels.varco_pack import block_mask_indices  # noqa: E402
from repro.nn.gnn import (GNNConfig, centralized_forward,  # noqa: E402
                          gnn_forward, init_gnn)
from repro.train.optim import adamw  # noqa: E402
from repro.train.trainer import train_gnn  # noqa: E402

N_NODES = 169_343          # OGBN-Arxiv
Q = 4
HIDDEN = 256               # paper §V
LAYERS = 3
CONV = "sage"
LR = 5e-3                  # train_gnn's default
EPOCHS = 3
TIMED_STEPS = 3

#: distributed vs centralised forward, both f32 at "highest" matmul
#: precision: only summation order differs (ELL on the MXU vs segment-sum),
#: so the logits agree to a few f32 ulps of their scale — a dropped or
#: mis-weighted edge is off by orders of magnitude more
FORWARD_TOL = 1e-4
#: shard_map vs emulated after EPOCHS Adam steps at "highest" precision:
#: the psum and ppermute reorder f32 sums; Adam can turn a near-zero
#: gradient's rounding into a step of up to LR, so parameters are held
#: to a fraction of one step and the loss to f32 noise of its value
FOUR_CHIP_LOSS_TOL = 1e-5
FOUR_CHIP_PARAM_TOL = LR / 10

_CUSTOM_CALL = re.compile(r'custom_call_target="tpu_custom_call"')


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


_START = time.perf_counter()


def emit(phase: str, **fields) -> None:
    """One JSON line per phase; ``t_s`` is the wall time since start."""
    print(json.dumps({"phase": phase, **fields,
                      "t_s": time.perf_counter() - _START}), flush=True)


def peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def count_kernels(compiled) -> int:
    return len(_CUSTOM_CALL.findall(compiled.as_text()))


class CompileClock:
    """Sums the backend compile time JAX reports while it is installed."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1

    def take(self) -> tuple[float, int]:
        out = (self.seconds, self.count)
        self.seconds, self.count = 0.0, 0
        return out


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def kernels_phase(seed: int) -> None:
    """The Pallas kernels on the device against their jnp oracles."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(0, 1, (2048, 256)), jnp.float32)
    nbr = jnp.asarray(rng.integers(0, 2048, (512, 16)), jnp.int32)
    w = jnp.asarray(np.where(rng.random((512, 16)) < 0.7,
                             rng.normal(0, 1, (512, 16)), 0.0), jnp.float32)
    got = ops.aggregate(x, nbr, w)
    with jax.default_matmul_precision("highest"):
        want = ref.ell_spmm_reference(x, nbr, w)
    ell_diff = float(jnp.max(jnp.abs(got - want)))
    check(ell_diff <= FORWARD_TOL * max(1.0, float(jnp.max(jnp.abs(want)))),
          f"ell_spmm differs from its oracle by {ell_diff}")

    # three lane-blocks, two kept: the pack visits several kept blocks per
    # row tile and the decode zero-fills a dropped one
    xq = jnp.asarray(rng.normal(0, 1, (512, 384))
                     * 10.0 ** rng.uniform(-2, 2, (512, 1)), jnp.float32)
    kept, inv = block_mask_indices(jax.random.key(seed), 3, 1.5)
    codec = {}
    for width in (2, 4, 8):
        payload, scales = ops.pack_quant(xq, kept, width=width)
        payload_r, scales_r = ref.pack_quant_reference(xq, kept, width)
        check(payload.dtype == jnp.uint8
              and payload.shape == payload_r.shape,
              f"pack_quant w={width}: payload {payload.dtype}"
              f"{payload.shape}, oracle {payload_r.shape}")
        scale_err = float(jnp.max(jnp.abs(scales - scales_r) / scales_r))
        # the kernel and XLA may round a division differently in the last
        # ulp, which can move a level across a .5 boundary: hold the
        # decode to one level, and report the bitwise share
        dq = ref.unpack_quant_reference(payload, scales, width)
        dq_r = ref.unpack_quant_reference(payload_r, scales_r, width)
        step = jnp.repeat(scales_r, 128, axis=-1)
        level_err = float(jnp.max(jnp.abs(dq - dq_r) / step))
        same = float(jnp.mean((payload == payload_r).astype(jnp.float32)))
        check(scale_err <= 1e-6 and level_err <= 1.0 + 1e-5,
              f"pack_quant w={width}: scale err {scale_err}, "
              f"level err {level_err}")
        # decoding is exact integer work plus one multiply per lane
        out = ops.unpack_quant(payload_r, scales_r, inv, width=width)
        out_r = ref.unpack_reference(dq_r, inv)
        check(bool(jnp.array_equal(out, out_r)),
              f"unpack_quant w={width} differs from its oracle")
        codec[f"w{width}"] = {"bytes_equal_share": same,
                              "max_level_err": level_err}
    emit("kernels", ell_spmm_max_abs_diff=ell_diff, pack_quant=codec,
         peak_bytes_in_use=peak_bytes())


def setup_phase(n: int, q: int, seed: int):
    """Host set-up: graph, partition, p2p halo/ELL arrays."""
    t0 = time.perf_counter()
    g = citation_graph(n=n, seed=seed)
    t1 = time.perf_counter()
    pg = partition_graph(g, q, scheme="random", seed=seed)
    t2 = time.perf_counter()
    graph = attach_p2p(pg.device_arrays(), pg)
    jax.block_until_ready(graph)
    t3 = time.perf_counter()
    emit("setup", nodes=g.num_nodes, edges=g.num_edges,
         features=g.feat_dim, classes=g.num_classes, q=q,
         part_size=pg.part_size, ell_k=int(graph["ell_nbr"].shape[-1]),
         graph_s=t1 - t0, partition_s=t2 - t1, attach_p2p_s=t3 - t2)
    return g, pg, graph


def model(g, hidden: int, layers: int, seed: int):
    cfg = GNNConfig(conv=CONV, in_dim=g.feat_dim, hidden=hidden,
                    out_dim=g.num_classes, layers=layers)
    return cfg, init_gnn(jax.random.key(seed), cfg)   # as train_gnn does


def forward_phase(g, pg, graph, cfg, params) -> None:
    """First step's full-communication p2p forward vs the centralised one."""
    meta = DistMeta.build(pg, params, wire="p2p")

    def dist_logits(p, gr):
        agg = _make_aggregate_emulated(gr, meta, FULL_COMM, None,
                                       jnp.ones(()), jax.random.key(0))
        return gnn_forward(p, cfg, gr["features"], agg)[0]

    with jax.default_matmul_precision("highest"):
        dist = np.asarray(jax.jit(dist_logits)(params, graph))
        want = np.asarray(centralized_forward(params, cfg, g))
    got = dist[pg.owner, pg.local_index]
    diff = float(np.max(np.abs(got - want)))
    scale = max(1.0, float(np.max(np.abs(want))))
    emit("forward_check", max_abs_diff=diff, max_abs_ref=scale,
         tolerance=FORWARD_TOL * scale, finite=bool(np.isfinite(got).all()))
    check(np.isfinite(got).all() and diff <= FORWARD_TOL * scale,
          f"distributed forward differs from centralized_forward by {diff}")


def _aot(jitted, args, kwargs=None):
    t0 = time.perf_counter()
    compiled = jitted.lower(*args, **(kwargs or {})).compile()
    return compiled, time.perf_counter() - t0


def _time_steps(compiled, args) -> list[float]:
    jax.block_until_ready(compiled(*args))         # warm-up
    times = []
    for _ in range(TIMED_STEPS):
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(*args))
        times.append(time.perf_counter() - t0)
    return times


def first_step(policy, g, pg, graph, cfg, params, epochs, mesh=None):
    """Compile epoch 0's train step exactly as ``train_gnn`` builds it;
    returns ``(compiled, compile seconds, args)``."""
    meta = DistMeta.build(pg, params, wire="p2p")
    opt = adamw(LR)
    opt_state = opt.init(params)
    if policy.mode != "auto":
        step = make_train_step(cfg, policy, opt, meta, mesh=mesh)
        args = (params, opt_state, graph, jnp.asarray(0), jax.random.key(0))
        return (*_aot(step, args), args)
    ctl = make_controller(policy, meta, cfg, total_steps=epochs)
    plan, _ = ctl.plan(ctl.init(), 0)
    rm, kb, wm, ww, sw = plan_statics(meta, plan)
    step = make_auto_train_step(cfg, policy, opt, meta)
    args = (params, opt_state, graph, jax.random.key(0), jnp.asarray(rm),
            jnp.zeros((), jnp.float32) if wm is None else jnp.asarray(wm),
            jnp.asarray(plan.skip, jnp.float32),
            tuple(init_wire_residuals(meta, cfg)))
    compiled, secs = _aot(step._jit_step, args,
                          dict(packed_k=kb, wire_w=ww, store_w=sw))
    return compiled, secs, args


def train_phase(name, policy, g, pg, graph, cfg, params, clock, *,
                epochs, hidden, layers, seed, use_shard_map=False,
                mesh=None, step_graph=None):
    """Compile and time epoch 0's step, then train through ``train_gnn``."""
    compiled, compile_s, args = first_step(
        policy, g, pg, step_graph if step_graph is not None else graph,
        cfg, params, epochs, mesh=mesh)
    kernels = count_kernels(compiled)
    check(kernels > 0, f"{name}: the compiled train step holds no "
          f"tpu_custom_call — the Pallas kernels did not run")
    step_s = _time_steps(compiled, args)
    del compiled, args
    clock.take()
    t0 = time.perf_counter()
    res = train_gnn(g, q=Q, scheme="random", policy=policy, epochs=epochs,
                    hidden=hidden, layers=layers, conv=CONV, seed=seed,
                    eval_every=epochs, wire="p2p",
                    use_shard_map=use_shard_map)
    train_s = time.perf_counter() - t0
    train_compile_s, n_compiles = clock.take()
    h = res.history
    loss = h.loss[-1]
    emit(f"train:{name}", policy=res.policy_desc, epochs=epochs,
         compile_s=compile_s, tpu_custom_calls=kernels,
         smoke_step_s=step_s, train_gnn_s=train_s,
         train_gnn_compile_s=train_compile_s,
         train_gnn_compiles=n_compiles, loss_first=h.loss[0],
         loss_final=loss, loss_finite=math.isfinite(loss),
         test_acc=h.test_acc[-1],
         transport_gfloats=h.total_transport_gfloats,
         peak_bytes_in_use=peak_bytes())
    check(all(math.isfinite(v) for v in h.loss),
          f"{name}: non-finite loss {h.loss}")
    return res


def one_chip(args) -> None:
    clock = CompileClock()
    kernels_phase(args.seed)
    g, pg, graph = setup_phase(N_NODES, Q, args.seed)
    cfg, params = model(g, HIDDEN, LAYERS, args.seed)
    forward_phase(g, pg, graph, cfg, params)
    kw = dict(epochs=args.epochs, hidden=HIDDEN, layers=LAYERS,
              seed=args.seed)
    full = train_phase("full", FULL_COMM, g, pg, graph, cfg, params, clock,
                       **kw)
    budget = 0.5 * full.history.total_transport_gfloats * 32e9   # bits
    auto = CommPolicy.parse(f"auto:error:{budget:.6g}:w4", args.epochs)
    train_phase("auto", auto, g, pg, graph, cfg, params, clock, **kw)


def four_chips(args) -> None:
    check(len(jax.devices()) >= Q,
          f"--four-chips needs {Q} devices, JAX sees {len(jax.devices())}")
    clock = CompileClock()
    g, pg, graph = setup_phase(N_NODES, Q, args.seed)
    cfg, params = model(g, HIDDEN, LAYERS, args.seed)
    mesh = make_worker_mesh(Q)
    kw = dict(epochs=args.epochs, hidden=HIDDEN, layers=LAYERS,
              seed=args.seed)
    with jax.default_matmul_precision("highest"):
        sm = train_phase("shard_map", FULL_COMM, g, pg, graph, cfg, params,
                         clock, use_shard_map=True, mesh=mesh,
                         step_graph=shard_graph(graph, mesh), **kw)
        em = train_phase("emulated", FULL_COMM, g, pg, graph, cfg, params,
                         clock, **kw)
    loss_diff = max(abs(a - b) for a, b in
                    zip(sm.history.loss, em.history.loss))
    param_diff = max(float(jnp.max(jnp.abs(jnp.asarray(a) - jnp.asarray(b))))
                     for a, b in zip(jax.tree_util.tree_leaves(sm.params),
                                     jax.tree_util.tree_leaves(em.params)))
    loss_tol = FOUR_CHIP_LOSS_TOL * max(1.0, abs(em.history.loss[-1]))
    emit("compare:shard_map_vs_emulated", max_loss_diff=loss_diff,
         loss_tolerance=loss_tol, max_param_diff=param_diff,
         param_tolerance=FOUR_CHIP_PARAM_TOL)
    check(loss_diff <= loss_tol and param_diff <= FOUR_CHIP_PARAM_TOL,
          f"shard_map differs from emulated: loss {loss_diff}, "
          f"params {param_diff}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run Q=4 under shard_map over four chips against "
                         "the emulated run, and nothing else")
    ap.add_argument("--epochs", type=int, default=EPOCHS)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    if args.four_chips:
        four_chips(args)
    else:
        one_chip(args)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
