"""Full-batch distributed GNN trainer (the paper's experimental loop).

Runs Algorithm 1 for ``epochs`` steps (full-batch: one gradient step per
epoch, as the paper trains), tracking the communication ledger so accuracy
can be plotted against epochs (Fig. 3) or communicated floats (Fig. 5).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core.varco import CommPolicy
from repro.dist import faults as faultlib
from repro.dist.gnn_parallel import (DistMeta, make_eval_step,
                                     make_train_step, make_worker_mesh,
                                     shard_graph)
from repro.graph.data import GraphData
from repro.graph.partition import partition_graph
from repro.graph.stream import ShardSet, is_shard_dir, load_shards
from repro.nn.gnn import GNNConfig, init_gnn
from repro.train import checkpoint as ckpt
from repro.train.optim import Optimizer, adamw


@dataclasses.dataclass
class History:
    """Per-epoch training record.

    ``pair_transport_gf`` is the cumulative per-pair transport split
    (flattened receiver-major ``[Q*Q]`` tuple of Gfloats per logged
    epoch) — populated by the closed-loop ``auto`` policies, whose
    controllers allocate the wire budget per worker pair; empty lists of
    tuples stay empty for scalar policies.  ``layer_transport_gf`` is the
    per-layer refinement (flattened layer-major ``[L*Q*Q]`` tuples,
    per-layer ``auto`` policies only — DESIGN.md §3.7) and ``comp_err``
    the cumulative measured compression error (dropped-block energy, auto
    policies).  ``row()`` serialises the tuples as ``|``-joined cells so
    the CSV stays one value per column.
    """
    epoch: list = dataclasses.field(default_factory=list)
    loss: list = dataclasses.field(default_factory=list)
    rate: list = dataclasses.field(default_factory=list)
    train_acc: list = dataclasses.field(default_factory=list)
    val_acc: list = dataclasses.field(default_factory=list)
    test_acc: list = dataclasses.field(default_factory=list)
    halo_gfloats: list = dataclasses.field(default_factory=list)  # cumulative
    transport_gfloats: list = dataclasses.field(default_factory=list)
    wall_s: list = dataclasses.field(default_factory=list)
    pair_transport_gf: list = dataclasses.field(default_factory=list)
    layer_transport_gf: list = dataclasses.field(default_factory=list)
    comp_err: list = dataclasses.field(default_factory=list)  # cumulative

    def row(self, i: int) -> dict:
        out = {k: getattr(self, k)[i] for k in
               ("epoch", "loss", "rate", "train_acc", "val_acc", "test_acc",
                "halo_gfloats", "transport_gfloats", "wall_s")}
        if self.pair_transport_gf:
            out["pair_transport_gf"] = "|".join(
                f"{v:.6g}" for v in self.pair_transport_gf[i])
        if self.layer_transport_gf:
            out["layer_transport_gf"] = "|".join(
                f"{v:.6g}" for v in self.layer_transport_gf[i])
        if self.comp_err:
            out["comp_err"] = self.comp_err[i]
        return out

    def rows(self):
        return [self.row(i) for i in range(len(self.epoch))]

    def layer_split(self, q: int) -> list:
        """Cumulative per-layer transport (Gfloats, ``[L]``) of the last
        logged epoch — the layer-major ``[L·Q²]`` flattening of
        ``layer_transport_gf`` summed per layer.  Empty for runs without
        per-layer plans.  The one place the flattening convention is
        decoded (example driver and benchmark both call this)."""
        if not self.layer_transport_gf:
            return []
        lt = self.layer_transport_gf[-1]
        n_pairs = q * q
        return [float(sum(lt[i * n_pairs:(i + 1) * n_pairs]))
                for i in range(len(lt) // n_pairs)]

    @property
    def final_test_acc(self) -> float:
        return self.test_acc[-1] if self.test_acc else float("nan")

    @property
    def best_test_acc(self) -> float:
        return max(self.test_acc) if self.test_acc else float("nan")

    @property
    def total_halo_gfloats(self) -> float:
        return self.halo_gfloats[-1] if self.halo_gfloats else 0.0

    @property
    def total_transport_gfloats(self) -> float:
        """Gfloats the wire format actually shipped (DESIGN.md §3.3)."""
        return self.transport_gfloats[-1] if self.transport_gfloats else 0.0


@dataclasses.dataclass
class TrainResult:
    """``spans`` is the run's host span table (``repro.tracing.table``):
    ``{name: {count, total_s, first_s}}``."""
    history: History
    params: Any
    meta: DistMeta
    policy_desc: str
    spans: dict = dataclasses.field(default_factory=dict)


def train_gnn(g: "GraphData | ShardSet | str", *, q: int = 8,
              scheme: str = "random",
              policy: CommPolicy, epochs: int = 300, lr: float = 5e-3,
              weight_decay: float = 0.0, hidden: int = 256, layers: int = 3,
              conv: str = "sage", seed: int = 0, eval_every: int = 5,
              use_shard_map: bool = False, optimizer: Optimizer | None = None,
              sync: str = "grad", wire: str = "dense",
              faults: "faultlib.FaultSchedule | None" = None,
              fault_max_stale: int = 5, fault_backoff_cap: int = 16,
              checkpoint_dir: str | None = None, checkpoint_every: int = 0,
              resume: bool = False, stop_after: int | None = None,
              log_fn=None) -> TrainResult:
    """Partition ``g`` over ``q`` workers and train under ``policy``.

    ``g`` may also be an on-disk shard directory (written by
    ``repro.graph.stream.write_shards``) or a loaded ``ShardSet`` — the
    out-of-core path: partitioning happened offline, the per-pair halo /
    ELL arrays are already in the shards, and ``q``/``scheme``/the global
    graph are never consulted (Q ≥ 16 runs load only partition data).

    Mirrors the paper's §V setup by default: 3-layer SAGE, 256 hidden,
    full-batch, 300 epochs.  ``wire="packed"`` runs the reduced-volume
    packed halo exchange (DESIGN.md §3.3; feature widths must be multiples
    of 128, and compressing policies must use the ``blockmask`` compressor);
    ``wire="p2p"`` the neighbor-only ppermute ring with ELL local
    aggregation (DESIGN.md §3.5 — same constraints under compression, and
    the per-pair halo/ELL arrays are attached here automatically).

    An ``auto:<controller>:<budget-bits>`` policy (``CommPolicy.parse``)
    closes the loop: the named ``repro.dist.ratectl`` controller plans a
    per-pair ``[Q, Q]`` rate map each epoch from measured transport
    feedback, and its state threads through the epoch scan alongside the
    optimizer state (DESIGN.md §3.6).  Auto policies default the wire to
    ``"p2p"`` when the caller left ``"dense"`` (per-pair rates need a
    per-pair wire) and record the per-pair transport split in
    ``History.pair_transport_gf`` plus the cumulative measured
    compression error in ``History.comp_err``.  A trailing ``:per-layer``
    lifts the plan to per-layer ``[L, Q, Q]`` tensors — every layer's
    exchanges get their own water-filled share of each step's bit
    allowance — and fills ``History.layer_transport_gf`` (DESIGN.md
    §3.7).

    A ``faults`` :class:`repro.dist.faults.FaultSchedule` turns on the
    degraded-mode loop (DESIGN.md §3.10): each step the schedule's
    seeded link-drop mask feeds the *exchange → cached → backoff-probe →
    local-only* ladder (``fault_max_stale`` staleness cap,
    ``fault_backoff_cap`` probe backoff), every policy's step runs
    through the fault-channel oracle (scalar policies ride a uniform
    rate map), and a ``crash_at`` event drops the run elastically to
    Q − 1 — shard-backed inputs only — migrating controller and ladder
    state.  The fault channel defaults the wire to ``"p2p"`` like auto.

    ``checkpoint_dir`` + ``checkpoint_every`` persist the full train
    state atomically every N epochs (``stop_after`` additionally
    checkpoints and exits after that many epochs — the kill switch of
    the crash-consistency tests); ``resume=True`` restores it and
    continues at the saved epoch, bitwise-equal to the uninterrupted
    run.  Resume replays any recorded worker shrink but refuses a
    checkpoint whose world size cannot be reached from ``g``.

    The run's host work is timed in ``repro.tracing`` spans (set-up
    phases ``setup/*``: partition, params, device_arrays, p2p, meta,
    build; per epoch ``epoch`` around ``step``, ``sync``,
    ``eval``, ``plan``/``observe``, ``log``, ``checkpoint``), returned as
    ``TrainResult.spans`` and visible in a profiler trace of the call.
    """
    tracing.reset()
    auto = policy.mode == "auto"
    fault = faults is not None
    if (auto or fault) and wire == "dense":
        wire = "p2p"                   # per-pair rates need a per-pair wire
    sched = faults
    with tracing.span("setup/partition"):
        if is_shard_dir(g):
            g = load_shards(g)
        if isinstance(g, ShardSet):
            pg = g                     # partitioned offline; q comes with it
            q = pg.q
        else:
            pg = partition_graph(g, q, scheme=scheme, seed=seed)
    cfg = GNNConfig(conv=conv, in_dim=g.feat_dim, hidden=hidden,
                    out_dim=g.num_classes, layers=layers)
    # device allocations in this order: parameters, graph, optimizer
    # state (the device memory layout follows it)
    with tracing.span("setup/params"):
        params = init_gnn(jax.random.key(seed), cfg)
    with tracing.span("setup/device_arrays"):
        graph = pg.device_arrays()     # shards carry their halo/ELL arrays
    if not isinstance(pg, ShardSet) and (wire == "p2p" or auto):
        from repro.dist.halo import attach_p2p
        with tracing.span("setup/p2p"):
            graph = attach_p2p(graph, pg)  # auto's per-pair stats need them
    if resume:
        if not checkpoint_dir:
            raise ValueError("resume=True needs checkpoint_dir")
        path = ckpt.latest_checkpoint(checkpoint_dir)
        if path is None:
            raise FileNotFoundError(
                f"resume=True but no checkpoint under {checkpoint_dir!r}")
        peeked = ckpt.peek(path)
        alive = peeked.get("alive")
        if alive is not None and len(alive) < q:
            # the checkpointed run had already shrunk: replay the shrinks
            # so the like-tree (and every step closure) matches its world
            if not isinstance(pg, ShardSet):
                raise ValueError("resuming a shrunk run needs shard-backed "
                                 "input (a ShardSet / shard dir)")
            cur = list(range(q))
            for w in sorted(set(cur) - set(int(a) for a in alive)):
                pg = faultlib.shrink_shards(pg, cur.index(w))
                cur.remove(w)
            q = pg.q
            with tracing.span("setup/device_arrays"):
                graph = pg.device_arrays()
            if sched is not None:
                sched = dataclasses.replace(
                    sched, alive=tuple(int(a) for a in alive))
        if int(peeked.get("q", q)) != q:
            raise ValueError(f"checkpoint world size {peeked['q']} does "
                             f"not match this run's q={q}")
    with tracing.span("setup/meta"):
        meta = DistMeta.build(pg, params, wire=wire)
        opt = optimizer or adamw(lr, weight_decay=weight_decay)
        opt_state = opt.init(params)

    if auto or fault:
        from repro.dist.ratectl import (init_halo_cache, init_wire_residuals,
                                        make_auto_train_step, make_controller,
                                        uniform_plan)

    def _init_cache(meta_):
        if not auto:
            return ()
        if policy.controller == "stale":
            return init_halo_cache(meta_, cfg)
        if policy.max_width < 32 and meta_.wire == "p2p":
            # quantising wire: the cache channel carries the error-feedback
            # residuals instead (stale XOR EF, DESIGN.md §3.8)
            return init_wire_residuals(meta_, cfg)
        return ()

    def _make_step(meta_):
        if fault:
            return faultlib.make_fault_train_step(cfg, policy, opt, meta_,
                                                  mesh=mesh, sync=sync)
        if auto:
            return make_auto_train_step(cfg, policy, opt, meta_, mesh=mesh,
                                        sync=sync)
        return make_train_step(cfg, policy, opt, meta_, mesh=mesh, sync=sync)

    with tracing.span("setup/build"):
        mesh = make_worker_mesh(q) if use_shard_map else None
        if mesh is not None:
            graph = shard_graph(graph, mesh)
        ctl = ctl_state = None
        if auto:
            ctl = make_controller(policy, meta, cfg, total_steps=epochs)
            ctl_state = ctl.init()
        cache = _init_cache(meta)
        fcache = init_halo_cache(meta, cfg) if fault else ()
        dstate = faultlib.init_degrade(q) if fault else None
        step = _make_step(meta)
        evaluate = make_eval_step(cfg, meta, mesh=mesh)

    hist = History()
    halo_bits_cum = 0.0
    transport_bits_cum = 0.0
    pair_bits_cum = None
    layer_bits_cum = None
    err_cum = 0.0
    start_epoch = 0

    def _state_tree():
        tree = {"params": params, "opt": opt_state}
        if auto:
            tree["ctl"] = ctl_state
        if cache:
            tree["cache"] = tuple(cache)
        if fault:
            tree["fcache"] = tuple(fcache)
        return tree

    def _ck_extra():
        return {
            "q": int(q),
            "alive": [int(w) for w in sched.alive_workers] if fault
            else None,
            "halo": float(halo_bits_cum),
            "transport": float(transport_bits_cum),
            "err": float(err_cum),
            "pair": None if pair_bits_cum is None else pair_bits_cum.tolist(),
            "layer": None if layer_bits_cum is None
            else layer_bits_cum.tolist(),
            "degrade": None if dstate is None else {
                "age": dstate.age.tolist(),
                "backoff": dstate.backoff.tolist(),
                "next_try": dstate.next_try.tolist()},
            "policy": policy.describe(),
        }

    if resume:
        tree, start_epoch, ext = ckpt.restore_train_state(checkpoint_dir,
                                                          _state_tree())
        params, opt_state = tree["params"], tree["opt"]
        if auto:
            ctl_state = tree["ctl"]
        if "cache" in tree:
            cache = tree["cache"]
        if fault:
            fcache = tree["fcache"]
            dg = ext.get("degrade")
            if dg is not None:
                dstate = faultlib.DegradeState(
                    age=np.asarray(dg["age"], np.int64),
                    backoff=np.asarray(dg["backoff"], np.int64),
                    next_try=np.asarray(dg["next_try"], np.int64))
        halo_bits_cum = float(ext.get("halo", 0.0))
        transport_bits_cum = float(ext.get("transport", 0.0))
        err_cum = float(ext.get("err", 0.0))
        if ext.get("pair") is not None:
            pair_bits_cum = np.asarray(ext["pair"], np.float64)
        if ext.get("layer") is not None:
            layer_bits_cum = np.asarray(ext["layer"], np.float64)

    t0 = tracing.clock()
    for epoch in range(start_epoch, epochs):
        with tracing.span("epoch", step_num=epoch):
            if fault:
                crash = sched.crash_at_step(epoch)
                if crash is not None:
                    if not isinstance(pg, ShardSet):
                        raise ValueError(
                            "elastic worker-crash recovery needs shard-backed "
                            "input (a ShardSet / shard dir) — in-memory "
                            "partitions cannot be renumbered at Q - 1")
                    if q <= 2:
                        raise ValueError("cannot shrink below Q = 2 — the "
                                         "fault plane needs at least one link")
                    q_old = q
                    pg = faultlib.shrink_shards(pg, crash)
                    q = pg.q
                    graph = pg.device_arrays()
                    meta = DistMeta.build(pg, params, wire=wire)
                    mesh = make_worker_mesh(q) if use_shard_map else None
                    if mesh is not None:
                        graph = shard_graph(graph, mesh)
                    sched = sched.shrink(crash)
                    dstate = faultlib.migrate_degrade_state(dstate, crash)
                    if auto:
                        ctl = make_controller(policy, meta, cfg,
                                              total_steps=epochs)
                        ctl_state = faultlib.migrate_controller_state(
                            ctl_state, crash, q_old)
                    cache = _init_cache(meta)   # stale/EF buffers restart cold
                    fcache = init_halo_cache(meta, cfg)
                    step = _make_step(meta)
                    evaluate = make_eval_step(cfg, meta, mesh=mesh)
                    # keep cumulative pair splits shaped [..., Q, Q]: the dead
                    # worker's history leaves the ledger with it
                    if pair_bits_cum is not None:
                        pair_bits_cum = np.delete(
                            np.delete(pair_bits_cum, crash, 0), crash, 1)
                    if layer_bits_cum is not None:
                        layer_bits_cum = np.delete(
                            np.delete(layer_bits_cum, crash, 1), crash, 2)
                serve, dstate = faultlib.degrade_plan(
                    dstate, sched.effective_drops(epoch), epoch,
                    max_stale=fault_max_stale, backoff_cap=fault_backoff_cap)
                fskip, dead = faultlib.serve_masks(serve)
            if fault:
                with tracing.span("plan"):
                    if auto:
                        plan, ctl_state = ctl.plan(ctl_state, epoch)
                    else:
                        r = float(policy.rate(epoch)) \
                            if policy.compresses else 1.0
                        plan = uniform_plan(q, r)
                with tracing.span("step"):
                    params, opt_state, m, cache, fcache = step(
                        params, opt_state, graph, jax.random.key(epoch),
                        plan, fskip, dead, cache, fcache)
                if auto:
                    with tracing.span("observe"):
                        ctl_state = ctl.observe(ctl_state, m)
            elif auto:
                with tracing.span("plan"):
                    plan, ctl_state = ctl.plan(ctl_state, epoch)
                with tracing.span("step"):
                    params, opt_state, m, cache = step(
                        params, opt_state, graph, jax.random.key(epoch),
                        plan, cache)
                with tracing.span("observe"):
                    ctl_state = ctl.observe(ctl_state, m)
            else:
                with tracing.span("step"):
                    params, opt_state, m = step(params, opt_state, graph,
                                                jnp.asarray(epoch),
                                                jax.random.key(epoch))
            if auto or fault:
                pair_t = tracing.pull(m["pair_transport"])
                pair_bits_cum = pair_t if pair_bits_cum is None \
                    else pair_bits_cum + pair_t
                err_cum += float(tracing.pull(m["pair_err"]).sum())
                if "layer_transport" in m:
                    layer_t = tracing.pull(m["layer_transport"])
                    layer_bits_cum = layer_t if layer_bits_cum is None \
                        else layer_bits_cum + layer_t
            halo_bits_cum += tracing.pull(m["halo_bits"])
            transport_bits_cum += tracing.pull(m["transport_bits"])
            if epoch % eval_every == 0 or epoch == epochs - 1:
                with tracing.span("eval"):
                    accs = evaluate(params, graph)
                hist.epoch.append(epoch)
                hist.loss.append(tracing.pull(m["loss"]))
                hist.rate.append(tracing.pull(m["rate"]))
                hist.train_acc.append(tracing.pull(accs["train"]))
                hist.val_acc.append(tracing.pull(accs["val"]))
                hist.test_acc.append(tracing.pull(accs["test"]))
                hist.halo_gfloats.append(halo_bits_cum / 32.0 / 1e9)
                hist.transport_gfloats.append(
                    transport_bits_cum / 32.0 / 1e9)
                hist.wall_s.append(tracing.clock() - t0)
                if pair_bits_cum is not None:
                    hist.pair_transport_gf.append(tuple(
                        pair_bits_cum.ravel() / 32.0 / 1e9))
                    hist.comp_err.append(err_cum)
                if layer_bits_cum is not None:
                    hist.layer_transport_gf.append(tuple(
                        layer_bits_cum.ravel() / 32.0 / 1e9))
                if log_fn:
                    with tracing.span("log"):
                        log_fn(hist.row(len(hist.epoch) - 1))
            done = epoch + 1
            if checkpoint_dir and (
                    (checkpoint_every and done % checkpoint_every == 0)
                    or done == stop_after):
                with tracing.span("checkpoint"):
                    ckpt.save_train_state(checkpoint_dir, _state_tree(),
                                          done, extra=_ck_extra())
            if stop_after is not None and done >= stop_after:
                break
    return TrainResult(hist, params, meta, policy.describe(),
                       tracing.table())
