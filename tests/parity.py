"""Shared emulated ≡ shard_map parity harness (satellite of ISSUE 5).

The same parity pattern used to be duplicated across test_p2p_wire.py,
test_pair_rates.py and test_gnn_distributed.py: build a tiny partitioned
graph + SAGE config, run the emulated ``[Q, ...]`` forward, re-run the
identical program under ``shard_map`` in a subprocess (the main test
process must keep the single real CPU device — see conftest), and pin
the outputs to ≤ 1e-6.  This module is the single home of that
machinery:

* :func:`build_setup` — the shared graph/config/params/partition
  construction (in-process fixtures);
* :func:`mixed_map` — the deterministic mixed-rate ``[Q, Q]`` /
  ``[L, Q, Q]`` draws every rate-map test uses;
* :func:`run_forward_parity` — one subprocess running a whole
  ``wire × policy × rate-map`` case list against a ``Q``-device mesh,
  asserting emulated ≡ shard_map on logits and ledger bits;
* :func:`run_train_parity` — the train-step variant (several optimizer
  steps, parameter + metric comparison).

tests/test_parity_matrix.py drives :func:`run_forward_parity` as one
parametrized matrix over ``wire × policy × Q ∈ {1, 2, 4}`` including the
per-layer ``[L, Q, Q]`` tensors (DESIGN.md §3.7).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

MIXED_RATES = [1.0, 2.0, 4.0, 16.0]

MIXED_WIDTHS = [2.0, 4.0, 8.0, 32.0]


def build_setup(q: int, f: int = 256, layers: int = 2, n: int = 256,
                conv: str = "sage", seed: int = 0, p2p: bool = True,
                hidden: int | None = None, shards: bool = False):
    """The shared test scaffold: ``(g, cfg, params, pg, graph)`` with the
    p2p halo/ELL arrays attached (harmless on the all-gather wires).

    ``shards=True`` takes the out-of-core route instead: the same graph is
    written to a chunked :class:`repro.graph.stream.GraphStore`, sharded
    on disk with the same owner vector, and loaded back as a ``ShardSet``
    (bitwise-identical arrays, manifest-carried ``HaloSpec``) — so the
    same parity cases conform from disk-backed shards.
    """
    import jax

    from repro.graph import partition_graph, tiny_graph
    from repro.nn import GNNConfig, init_gnn

    g = tiny_graph(n=n, feat_dim=f)
    cfg = GNNConfig(conv=conv, in_dim=f, hidden=hidden or f,
                    out_dim=g.num_classes, layers=layers)
    params = init_gnn(jax.random.key(seed), cfg)
    if shards:
        import tempfile

        from repro.graph.partition import random_partition
        from repro.graph.stream import (load_shards, write_graph_store,
                                        write_shards)

        owner = random_partition(g, q, seed=seed)
        with tempfile.TemporaryDirectory() as td:
            store = write_graph_store(g, os.path.join(td, "store"))
            write_shards(store, owner, os.path.join(td, "shards"))
            pg = load_shards(os.path.join(td, "shards"))
        return g, cfg, params, pg, pg.device_arrays()
    pg = partition_graph(g, q, scheme="random", seed=seed)
    graph = pg.device_arrays()
    if p2p:
        from repro.dist.halo import attach_p2p
        graph = attach_p2p(graph, pg)
    return g, cfg, params, pg, graph


def mixed_map(q: int, seed: int = 0, layers: int | None = None) -> np.ndarray:
    """Deterministic mixed-rate map: ``[Q, Q]``, or ``[L, Q, Q]`` when
    ``layers`` is given (diagonal 1 everywhere)."""
    rng = np.random.default_rng(seed)
    shape = (q, q) if layers is None else (layers, q, q)
    rm = rng.choice(MIXED_RATES, size=shape).astype(np.float32)
    for sl in rm.reshape(-1, q, q):
        np.fill_diagonal(sl, 1.0)
    return rm


def mixed_width_map(q: int, seed: int = 0,
                    layers: int | None = None) -> np.ndarray:
    """Deterministic mixed wire-width map over ``WIRE_WIDTHS`` draws:
    ``[Q, Q]``, or ``[L, Q, Q]`` when ``layers`` is given (diagonal 32 —
    local rows never hit the wire; DESIGN.md §3.8)."""
    rng = np.random.default_rng(seed + 1000)
    shape = (q, q) if layers is None else (layers, q, q)
    wm = rng.choice(MIXED_WIDTHS, size=shape).astype(np.float32)
    for sl in wm.reshape(-1, q, q):
        np.fill_diagonal(sl, 32.0)
    return wm


def sub32_width_map(q: int, seed: int = 0,
                    layers: int | None = None) -> np.ndarray:
    """:func:`mixed_width_map` restricted to the sub-32 widths
    ``{2, 4, 8}`` off-diagonal: every pair quantises, so the step's
    static storage width is non-zero and the **bit-packed byte wire**
    carries the exchange (`_packed_store_w`; diagonal stays 32 — local
    rows never ship)."""
    rng = np.random.default_rng(seed + 2000)
    shape = (q, q) if layers is None else (layers, q, q)
    wm = rng.choice(MIXED_WIDTHS[:-1], size=shape).astype(np.float32)
    for sl in wm.reshape(-1, q, q):
        np.fill_diagonal(sl, 32.0)
    return wm


# ---------------------------------------------------------------------------
# Subprocess scripts.  One interpreter per Q (XLA fixes the device count at
# startup); each runs a whole case list so the graph build and mesh are paid
# once per matrix row, not once per case.
# ---------------------------------------------------------------------------

FORWARD_SCRIPT = r"""
import json, sys
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from parity import build_setup
from repro.core import CommPolicy
from repro.dist.gnn_parallel import (DistMeta, _make_aggregate_emulated,
                                     _make_aggregate_shard, _packed_k_for,
                                     _packed_pair_k_for, _packed_store_w,
                                     make_worker_mesh, shard_graph)
from repro.nn.gnn import gnn_forward

spec = json.loads(sys.argv[1])
q, f, layers, n = spec["q"], spec["f"], spec["layers"], spec["n"]
g, cfg, params, pg, graph = build_setup(q, f=f, layers=layers, n=n,
                                        hidden=spec.get("hidden"),
                                        shards=spec.get("shards", False))
mesh = make_worker_mesh(q)
gs = shard_graph(graph, mesh)

for case in spec["cases"]:
    wire, polspec, mode = case["wire"], case["policy"], case["map"]
    label = f"{wire}/{polspec}/{mode or 'scalar'}"
    meta = DistMeta.build(pg, params, wire=wire)
    pol = CommPolicy.parse(polspec, 1, compressor="blockmask")
    # rate maps arrive through the spec (mixed_map builds them host-side
    # — ONE construction shared with the in-process tests)
    rm = None if case.get("rates") is None \
        else np.asarray(case["rates"], np.float32)
    wm = None if case.get("widths") is None \
        else np.asarray(case["widths"], np.float32)
    key = jax.random.key(7)
    if rm is not None and case.get("fault") is not None:
        # fault-channel parity: seeded drop masks split into CACHED/DEAD,
        # a random fault hop cache (sender-major), identical on both
        # backends; the receiver-served buffers must round-trip too
        from repro.dist.faults import (FaultSchedule, _cache_recv_to_send,
                                       _cache_send_to_recv)
        fs = int(case["fault"])
        sched = FaultSchedule(q=q, seed=fs, drop_rate=0.3, spike_rate=0.1)
        drops = sched.effective_drops(0) > 0.0
        rng = np.random.default_rng(fs)
        dead_m = drops & (rng.random((q, q)) < 0.5)
        fskip = (drops & ~dead_m).astype(np.float32)
        dead = dead_m.astype(np.float32)
        np.fill_diagonal(fskip, 0.0)
        np.fill_diagonal(dead, 0.0)
        widths = [cfg.in_dim] + [cfg.hidden] * (cfg.layers - 1)
        d = max(q - 1, 1)
        fcache = tuple(
            jnp.asarray(rng.standard_normal(
                (q, d, meta.p2p_hop_width, w)).astype(np.float32))
            for w in widths)
        kb = dict(_packed_pair_k_for(meta, rm))
        fe = []
        agg_e = _make_aggregate_emulated(
            graph, meta, pol, None, jnp.ones(()), key, packed_k=kb,
            rate_map=jnp.asarray(rm),
            width_map=None if wm is None else jnp.asarray(wm),
            fskip=jnp.asarray(fskip), fcache=fcache, fcache_out=fe,
            dead=jnp.asarray(dead))
        le, be = gnn_forward(params, cfg, graph["features"], agg_e)

        def worker(p, gblk, rmap, wmap, fsk, dd, fc, k):
            fo = []
            agg = _make_aggregate_shard(
                gblk, meta, pol, None, jnp.ones(()), k, packed_k=kb,
                rate_map=rmap,
                width_map=wmap if wm is not None else None,
                fskip=fsk, fcache=fc, fcache_out=fo, dead=dd)
            l, b = gnn_forward(p, cfg, gblk["features"], agg)
            return l, b, tuple(fo)

        sm = jax.jit(shard_map(worker, mesh=mesh,
                               in_specs=(P(), P("workers"), P(), P(),
                                         P(), P(), P("workers"), P()),
                               out_specs=(P("workers"), P(),
                                          P("workers")),
                               check_vma=False))
        rcache = tuple(jnp.asarray(_cache_send_to_recv(np.asarray(c), q))
                       for c in fcache)
        ls, bs, fo_s = sm(params, gs, jnp.asarray(rm),
                          jnp.zeros(()) if wm is None else jnp.asarray(wm),
                          jnp.asarray(fskip), jnp.asarray(dead), rcache,
                          key)
        dl = float(jnp.abs(le - ls).max())
        db = float(jnp.abs(be - bs).max())
        dc = max(float(jnp.abs(a - jnp.asarray(
                     _cache_recv_to_send(np.asarray(b), q))).max())
                 for a, b in zip(fe, fo_s))
        assert dl <= spec["atol"], (label, "fault", dl)
        assert db <= 1e-6, (label, "fault", db)
        assert dc <= 1e-6, (label, "fault cache", dc)
        print(label, "fault OK", f"dl={dl:.2e} dc={dc:.2e}")
        continue
    if rm is not None:
        kb = dict(_packed_pair_k_for(meta, rm))
        # all-sub-32 width maps turn on the bit-packed byte wire on BOTH
        # backends (store_w > 0), so the parity matrix pins the sub-byte
        # storage path exactly like the fp32 one
        sw = 0 if wm is None else _packed_store_w(meta, wm)
        agg_e = _make_aggregate_emulated(
            graph, meta, pol, None, jnp.ones(()), key, packed_k=kb,
            rate_map=jnp.asarray(rm),
            width_map=None if wm is None else jnp.asarray(wm),
            store_w=sw)

        if wm is None:
            def worker(p, gblk, rmap, k):
                agg = _make_aggregate_shard(gblk, meta, pol, None,
                                            jnp.ones(()), k, packed_k=kb,
                                            rate_map=rmap)
                return gnn_forward(p, cfg, gblk["features"], agg)

            sm = jax.jit(shard_map(worker, mesh=mesh,
                                   in_specs=(P(), P("workers"), P(), P()),
                                   out_specs=(P("workers"), P()),
                                   check_vma=False))
            ls, bs = sm(params, gs, jnp.asarray(rm), key)
        else:
            def worker(p, gblk, rmap, wmap, k):
                agg = _make_aggregate_shard(gblk, meta, pol, None,
                                            jnp.ones(()), k, packed_k=kb,
                                            rate_map=rmap, width_map=wmap,
                                            store_w=sw)
                return gnn_forward(p, cfg, gblk["features"], agg)

            sm = jax.jit(shard_map(worker, mesh=mesh,
                                   in_specs=(P(), P("workers"), P(), P(),
                                             P()),
                                   out_specs=(P("workers"), P()),
                                   check_vma=False))
            ls, bs = sm(params, gs, jnp.asarray(rm), jnp.asarray(wm), key)
    else:
        rate = float(pol.rate(0)) if pol.compresses else 1.0
        comp = pol.compressor() if pol.compresses else None
        # static kept-block map whenever the wire payload shape follows
        # the rate: always on packed, under compression on p2p (the
        # `needs_kb` rule of make_train_step)
        kb = dict(_packed_k_for(meta, rate)) \
            if wire == "packed" or (wire == "p2p" and pol.compresses) \
            else None
        agg_e = _make_aggregate_emulated(graph, meta, pol, comp,
                                         jnp.asarray(rate), key,
                                         packed_k=kb)

        def worker(p, gblk, r, k):
            agg = _make_aggregate_shard(gblk, meta, pol, comp, r, k,
                                        packed_k=kb)
            return gnn_forward(p, cfg, gblk["features"], agg)

        sm = jax.jit(shard_map(worker, mesh=mesh,
                               in_specs=(P(), P("workers"), P(), P()),
                               out_specs=(P("workers"), P()),
                               check_vma=False))
        ls, bs = sm(params, gs, jnp.asarray(rate), key)
    le, be = gnn_forward(params, cfg, graph["features"], agg_e)
    dl = float(jnp.abs(le - ls).max())
    db = float(jnp.abs(be - bs).max())
    assert dl <= spec["atol"], (label, dl)
    assert db <= 1e-6, (label, db)
    print(label, "OK", f"dl={dl:.2e}")
print("PARITY_MATRIX_OK")
"""

CONSERVE_SCRIPT = r"""
import json, math, sys
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from parity import build_setup
from repro.core import CommPolicy
from repro.dist.gnn_parallel import (DistMeta, _make_aggregate_emulated,
                                     _make_aggregate_shard,
                                     _packed_pair_k_for, _packed_store_w,
                                     make_worker_mesh, shard_graph)
from repro.kernels.varco_pack import LANE
from repro.nn.gnn import gnn_forward

spec = json.loads(sys.argv[1])
q, f, layers, n = spec["q"], spec["f"], spec["layers"], spec["n"]
g, cfg, params, pg, graph = build_setup(q, f=f, layers=layers, n=n)
mesh = make_worker_mesh(q)
gs = shard_graph(graph, mesh)
pol = CommPolicy.parse("fixed:2", 1, compressor="blockmask")
rate = spec["rate"]
nb = f // LANE
k = max(int(nb // rate), 1)
rm = np.full((q, q), rate, np.float32)
np.fill_diagonal(rm, 1.0)
valid = np.asarray(graph["p2p_send_valid"])          # [Q, D, H]
D = q - 1
key = jax.random.key(7)


def hop_bytes(payload, scales, j, d):
    sel = valid[j, d] > 0
    m = np.asarray(payload)[sel].nbytes
    if scales is not None:
        m += np.asarray(scales)[sel].nbytes
    return m


for w in spec["widths"]:
    wm = np.full((q, q), float(w), np.float32)
    np.fill_diagonal(wm, 32.0)
    meta = DistMeta.build(pg, params, wire="p2p")
    sw = _packed_store_w(meta, wm)
    assert sw == (w if w < 32 else 0), (w, sw)
    kb = dict(_packed_pair_k_for(meta, rm))
    we = []
    agg_e = _make_aggregate_emulated(
        graph, meta, pol, None, jnp.ones(()), key, packed_k=kb,
        rate_map=jnp.asarray(rm), width_map=jnp.asarray(wm),
        store_w=sw, wire_out=we)
    le, be = gnn_forward(params, cfg, graph["features"], agg_e)
    assert len(we) == layers, (w, len(we))

    def worker(p, gblk, rmap, wmap, kk):
        wo = []
        agg = _make_aggregate_shard(gblk, meta, pol, None, jnp.ones(()),
                                    kk, packed_k=kb, rate_map=rmap,
                                    width_map=wmap, store_w=sw,
                                    wire_out=wo)
        l, b = gnn_forward(p, cfg, gblk["features"], agg)
        return l, b, tuple(wo)

    sm = jax.jit(shard_map(worker, mesh=mesh,
                           in_specs=(P(), P("workers"), P(), P(), P()),
                           out_specs=(P("workers"), P(), P("workers")),
                           check_vma=False))
    ls, bs, ws = sm(params, gs, jnp.asarray(rm), jnp.asarray(wm), key)
    assert len(ws) == layers * D, (w, len(ws))

    # per-pair ledger transport bits [recv, send], summed over exchanges
    for bvec, tag in ((be, "emulated"), (bs, "shard")):
        pt = np.asarray(bvec[2:2 + q * q], np.float64).reshape(q, q)
        assert not np.diagonal(pt).any(), (w, tag)
    pair_t = np.asarray(be[2:2 + q * q], np.float64).reshape(q, q)
    np.testing.assert_allclose(
        pair_t, np.asarray(bs[2:2 + q * q], np.float64).reshape(q, q))

    meas_e = np.zeros((q, q))
    meas_s = np.zeros((q, q))
    for e, (payload, scales) in enumerate(we):
        for j in range(q):
            for d in range(D):
                i = (j + d + 1) % q
                rows = int((valid[j, d] > 0).sum())
                m = hop_bytes(payload[j, d], None if scales is None
                              else scales[j, d], j, d)
                # every hop's transported bytes == ceil(its ledger
                # charge / 8): rows kept-blocks at LANE·w + 32 each
                blk = LANE * 32.0 if w >= 32 else LANE * w + 32.0
                assert m == math.ceil(rows * k * blk / 8.0), \
                    (w, "hop", e, j, d, m, rows, k)
                meas_e[i, j] += m
                # the shard backend's received buffer for this hop is
                # the SAME bytes (post-ppermute, receiver-major; the
                # out_spec concatenates workers along the row axis)
                sp, ss = ws[e * D + d]
                sp_i = np.asarray(sp).reshape(q, -1, sp.shape[-1])[i]
                np.testing.assert_array_equal(sp_i,
                                              np.asarray(payload[j, d]))
                ss_i = None
                if scales is not None:
                    ss_i = np.asarray(ss).reshape(q, -1, ss.shape[-1])[i]
                    np.testing.assert_array_equal(ss_i,
                                                  np.asarray(scales[j, d]))
                meas_s[i, j] += hop_bytes(sp_i, ss_i, j, d)
    np.testing.assert_array_equal(meas_e, np.ceil(pair_t / 8.0))
    np.testing.assert_array_equal(meas_s, np.ceil(pair_t / 8.0))
    print(f"w={w} OK pair_bytes_total={meas_e.sum():.0f}")

# packed wire: the all-gather ledger charges halo demand, not the padded
# buffer, so conservation is per transported ROW: k·(128·w + 32) bits
# land in k·16·w payload bytes + k fp32 scales exactly (byte-aligned)
w = spec["packed_width"]
wm = np.full((q, q), float(w), np.float32)
np.fill_diagonal(wm, 32.0)
meta = DistMeta.build(pg, params, wire="packed")
kb = dict(_packed_pair_k_for(meta, rm))
wp = []
agg_p = _make_aggregate_emulated(
    graph, meta, pol, None, jnp.ones(()), key, packed_k=kb,
    rate_map=jnp.asarray(rm), width_map=jnp.asarray(wm),
    store_w=_packed_store_w(meta, wm), wire_out=wp)
gnn_forward(params, cfg, graph["features"], agg_p)
assert len(wp) == layers
for payload, scales in wp:
    assert payload.dtype == jnp.uint8 and scales is not None
    per_row = payload[0, 0].nbytes + scales[0, 0].nbytes
    assert per_row == math.ceil(k * (LANE * w + 32.0) / 8.0), \
        (w, per_row, k)
print("CONSERVATION_OK")
"""


def run_wire_conservation(q: int, widths=(2, 4, 8, 32), f: int = 256,
                          layers: int = 2, n: int = 256, rate: float = 2.0,
                          packed_width: int = 4,
                          timeout: int = 1200) -> str:
    """Ledger-vs-buffer conservation (the tentpole's closing check): on
    BOTH backends, every p2p hop's physically transported array —
    bit-packed uint8 payload + fp32 scales under ``store_w``, fp32 rows
    at width 32 — has ``nbytes == ceil(per-pair ledger transport bits /
    8)``, hop by hop and per-pair in total, and the two backends ship
    byte-identical buffers.  The packed wire conforms per transported
    row (its ledger charges halo demand, not the padded all-gather)."""
    spec = {"q": q, "f": f, "layers": layers, "n": n, "rate": rate,
            "widths": list(widths), "packed_width": packed_width}
    return _run(CONSERVE_SCRIPT, spec, q, "CONSERVATION_OK",
                timeout=timeout)


TRAIN_SCRIPT = r"""
import json, sys
import jax, jax.numpy as jnp
from parity import build_setup
from repro.dist.gnn_parallel import (DistMeta, make_train_step,
                                     make_worker_mesh, shard_graph)
from repro.core import CommPolicy
from repro.train.optim import sgd

spec = json.loads(sys.argv[1])
q, f, layers, n = spec["q"], spec["f"], spec["layers"], spec["n"]
g, cfg, params, pg, graph = build_setup(q, f=f, layers=layers, n=n,
                                        hidden=spec["hidden"])
meta = DistMeta.build(pg, params, wire=spec["wire"])
opt = sgd(1e-2)
mesh = make_worker_mesh(q)
gs = shard_graph(graph, mesh)

for polspec in spec["policies"]:
    pol = CommPolicy.parse(polspec, 1, compressor="blockmask")
    p_e, s_e = params, opt.init(params)
    step_e = make_train_step(cfg, pol, opt, meta)
    p_s, s_s = params, opt.init(params)
    step_s = make_train_step(cfg, pol, opt, meta, mesh=mesh)
    for i in range(spec["steps"]):
        p_e, s_e, m_e = step_e(p_e, s_e, graph, jnp.asarray(i),
                               jax.random.key(i))
        p_s, s_s, m_s = step_s(p_s, s_s, gs, jnp.asarray(i),
                               jax.random.key(i))
    d = max(float(jnp.abs(a - b).max())
            for a, b in zip(jax.tree.leaves(p_e), jax.tree.leaves(p_s)))
    assert d < 1e-6, (polspec, d)
    assert abs(float(m_e["loss"]) - float(m_s["loss"])) < 1e-5, polspec
    assert abs(float(m_e["transport_bits"]) -
               float(m_s["transport_bits"])) < 1.0, polspec
    print(polspec, "OK", f"dp={d:.2e}")
print("TRAIN_PARITY_OK")
"""


EF_SCRIPT = r"""
import json, sys
import jax, jax.numpy as jnp
import numpy as np
from parity import build_setup
from repro.core import CommPolicy
from repro.dist.gnn_parallel import (DistMeta, make_worker_mesh,
                                     shard_graph)
from repro.dist.ratectl import (RatePlan, init_wire_residuals,
                                make_auto_train_step)
from repro.train.optim import sgd

spec = json.loads(sys.argv[1])
q = spec["q"]
g, cfg, params, pg, graph = build_setup(q, f=spec["f"],
                                        layers=spec["layers"], n=spec["n"],
                                        hidden=spec["hidden"])
meta = DistMeta.build(pg, params, wire="p2p")
policy = CommPolicy.parse(spec["policy"], spec["steps"])
opt = sgd(1e-2)
mesh = make_worker_mesh(q)
gs = shard_graph(graph, mesh)
plan = RatePlan(jnp.asarray(np.asarray(spec["rates"], np.float32)),
                jnp.zeros((q, q), jnp.float32),
                jnp.asarray(np.asarray(spec["widths"], np.float32)))

def run(mesh_, gg, rounding):
    p, s = params, opt.init(params)
    cache = init_wire_residuals(meta, cfg)
    step = make_auto_train_step(cfg, policy, opt, meta, mesh=mesh_,
                                rounding=rounding)
    for t in range(spec["steps"]):
        p, s, m, cache = step(p, s, gg, jax.random.key(t), plan, cache)
    return p, cache, m

for rounding in spec["roundings"]:
    p_e, c_e, m_e = run(None, graph, rounding)
    p_s, c_s, m_s = run(mesh, gs, rounding)
    dp = max(float(jnp.abs(a - b).max())
             for a, b in zip(jax.tree.leaves(p_e), jax.tree.leaves(p_s)))
    assert len(c_e) == len(c_s) and c_e, (len(c_e), len(c_s))
    assert all(a.shape == b.shape for a, b in zip(c_e, c_s))
    dc = max(float(jnp.abs(a - b).max()) for a, b in zip(c_e, c_s))
    # EF must actually be live: residual state nonzero after a
    # quantised step
    nz = max(float(jnp.abs(a).max()) for a in c_e)
    db = abs(float(m_e["transport_bits"]) - float(m_s["transport_bits"]))
    assert dp <= 1e-6, (rounding, dp)
    assert dc <= 1e-6, (rounding, dc)
    assert db < 1.0, (rounding, db)
    assert nz > 0.0, rounding
    print(rounding, "OK", f"dp={dp:.2e} dc={dc:.2e} resid_max={nz:.2e}")
print("EF_PARITY_OK")
"""


def _run(script: str, spec: dict, q: int, sentinel: str,
         timeout: int = 1200) -> str:
    # tests/ on the path so the scripts import parity.build_setup — ONE
    # scaffold construction, in-process and in the subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={q}",
               PYTHONPATH=os.pathsep.join(
                   [SRC, os.path.dirname(os.path.abspath(__file__))]))
    out = subprocess.run([sys.executable, "-c", script, json.dumps(spec)],
                         env=env, capture_output=True, text=True,
                         timeout=timeout)
    assert out.returncode == 0, \
        f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    assert sentinel in out.stdout, out.stdout
    return out.stdout


def run_forward_parity(q: int, cases: list[dict], f: int = 512,
                       layers: int = 2, n: int = 256, atol: float = 1e-6,
                       timeout: int = 1200, shards: bool = False) -> str:
    """Run ``cases`` (dicts of ``wire`` / ``policy`` / ``map`` ∈ {None,
    'pair', 'layer'} / optional ``width_map`` ∈ {None, 'pair', 'layer'} /
    optional ``seed`` / optional ``fault`` seed — a seeded
    ``FaultSchedule`` drop mask split into CACHED/DEAD plus a random
    fault hop cache, applied identically on both backends) on a
    ``q``-device mesh in one subprocess; asserts emulated ≡ shard_map
    ≤ ``atol`` per case.

    The mixed-rate (and mixed-width) operands are drawn host-side by
    :func:`mixed_map` / :func:`mixed_width_map` (so the subprocess
    exercises exactly the maps the in-process tests use) and shipped
    through the JSON spec.  ``shards=True`` builds the subprocess's graph
    from disk-backed shards (``build_setup(shards=True)``) instead of the
    in-memory partitioner — the Q ≥ 16 scale-conformance route."""
    def _widths(c):
        wmode = c.get("width_map")
        if wmode is None:
            return None
        if wmode.startswith("w"):
            # uniform off-diagonal width, e.g. "w2"/"w4": pins the byte
            # wire at exactly that static storage width on both backends
            wm = np.full((q, q), float(wmode[1:]), np.float32)
            np.fill_diagonal(wm, 32.0)
            return wm.tolist()
        draw = sub32_width_map if wmode.startswith("sub32") \
            else mixed_width_map
        return draw(q, c.get("seed", 0),
                    layers if wmode.endswith("layer") else None).tolist()

    cases = [dict(c,
                  rates=None if c["map"] is None else mixed_map(
                      q, c.get("seed", 0),
                      layers if c["map"] == "layer" else None).tolist(),
                  widths=_widths(c))
        for c in cases]
    spec = {"q": q, "f": f, "layers": layers, "n": n, "atol": atol,
            "cases": cases, "shards": shards}
    return _run(FORWARD_SCRIPT, spec, q, "PARITY_MATRIX_OK",
                timeout=timeout)


def run_ef_parity(q: int, policy: str = "auto:budget:2e8:w8",
                  roundings: tuple[str, ...] = ("rint",), f: int = 128,
                  hidden: int = 128, layers: int = 2, n: int = 128,
                  steps: int = 3, timeout: int = 900) -> str:
    """Error-feedback backend parity (DESIGN.md §3.8/§3.11 satellite):
    run ``steps`` quantised auto-policy train steps with a FIXED mixed
    rate × width plan (no controller in the loop, so both backends see
    identical operands) on the emulated and shard_map backends and pin
    parameters, the EF residual cache tuple, and transport ≤ 1e-6 —
    per requested rounding mode (``"stochastic"`` additionally pins the
    per-(sender, hop) key schedule across backends)."""
    spec = {"q": q, "f": f, "hidden": hidden, "layers": layers, "n": n,
            "steps": steps, "policy": policy,
            "roundings": list(roundings),
            "rates": mixed_map(q, 0).tolist(),
            "widths": mixed_width_map(q, 0).tolist()}
    return _run(EF_SCRIPT, spec, q, "EF_PARITY_OK", timeout=timeout)


def run_train_parity(q: int, policies: list[str], wire: str = "p2p",
                     f: int = 256, hidden: int = 128, layers: int = 3,
                     n: int = 256, steps: int = 4,
                     timeout: int = 900) -> str:
    """Train-step parity: run each policy ``steps`` optimizer steps on
    both backends and pin parameters, loss, and transport."""
    spec = {"q": q, "f": f, "hidden": hidden, "layers": layers, "n": n,
            "steps": steps, "wire": wire, "policies": policies}
    return _run(TRAIN_SCRIPT, spec, q, "TRAIN_PARITY_OK", timeout=timeout)
