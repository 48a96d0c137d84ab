"""End-to-end driver (deliverable b): full-batch distributed GNN training
with VARCO on a production-shaped problem.

* synthetic OGBN-Arxiv analogue (20k nodes / ~270k edges by default),
* 16 workers, random partitioning (the paper's hardest setting),
* 300 epochs of Algorithm 1 with the linear slope-5 scheduler,
* periodic evaluation, msgpack checkpointing, CSV history.

Run:  PYTHONPATH=src python examples/distributed_varco_train.py \
          [--workers 16] [--epochs 300] [--comm varco:linear:5]
          [--scheme random|metis-like] [--shard-map] [--wire dense|packed|p2p]

``--policy`` (alias of ``--comm``) also accepts the closed-loop specs of
``repro.dist.ratectl`` (DESIGN.md §3.6) — e.g.

    --policy auto:budget:2e9 --feat-dim 512 --hidden 512

plans per-pair compression rates every epoch so the run's total transport
lands on the named bit budget (the trailing report prints the adherence).
A trailing ``:per-layer`` (e.g. ``auto:budget:2e9:per-layer``) lifts the
plan to per-layer ``[L, Q, Q]`` rate tensors — each layer's exchanges get
their own water-filled share of the step's bit allowance (DESIGN.md §3.7)
— and the report adds the per-layer transport split.
Auto policies need lane-grid widths (feature/hidden multiples of 128) and
run on the p2p wire; widths of 512 give the controller 4 kept-block
levels per pair to allocate — at width 128 every pair is already at the
one-block floor and no budget below full communication is reachable.

``--shard-map`` runs the real collective path and needs
``XLA_FLAGS=--xla_force_host_platform_device_count=<workers>``; the default
emulated path is numerically identical (tests/test_multidevice.py).
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=20000)
    ap.add_argument("--workers", type=int, default=16)
    ap.add_argument("--epochs", type=int, default=300)
    ap.add_argument("--comm", "--policy", dest="comm",
                    default="varco:linear:5",
                    help="comm spec: full | none | fixed:<r> | "
                         "varco:<sched> | "
                         "auto:<controller>:<budget-bits>[:per-layer] "
                         "(closed-loop; e.g. auto:budget:2e9 or "
                         "auto:budget:2e9:per-layer for [L, Q, Q] "
                         "per-layer rate tensors)")
    ap.add_argument("--wire", default=None,
                    choices=["dense", "packed", "p2p"],
                    help="halo-exchange transport (auto policies default "
                         "to p2p)")
    ap.add_argument("--scheme", default="random",
                    choices=["random", "metis-like"])
    ap.add_argument("--feat-dim", type=int, default=None,
                    help="synthetic feature width (default: the dataset's "
                         "128; auto policies want >= 256 for compression "
                         "headroom)")
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--dataset", default="arxiv",
                    choices=["arxiv", "products"])
    ap.add_argument("--shard-map", action="store_true")
    ap.add_argument("--out", default="experiments/run")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    from repro.core import CommPolicy
    from repro.graph import citation_graph, copurchase_graph
    from repro.train import train_gnn
    from repro.train.checkpoint import save
    from repro.train.metrics import write_csv

    enable_compile_cache()

    gen = citation_graph if args.dataset == "arxiv" else copurchase_graph
    graph = gen(n=args.nodes) if args.feat_dim is None \
        else gen(n=args.nodes, feat_dim=args.feat_dim)
    policy = CommPolicy.parse(args.comm, args.epochs)
    auto = policy.mode == "auto"
    if auto and (args.hidden % 128 or graph.feat_dim % 128):
        ap.error(f"auto policies pack 128-lane blocks: --hidden/--feat-dim "
                 f"must be multiples of 128, got {args.hidden}/"
                 f"{graph.feat_dim}")
    wire = args.wire or ("p2p" if auto else "dense")
    print(f"dataset={graph.name} workers={args.workers} "
          f"scheme={args.scheme} comm={policy.describe()} wire={wire}")

    res = train_gnn(
        graph, q=args.workers, scheme=args.scheme, policy=policy,
        epochs=args.epochs, hidden=args.hidden, weight_decay=1e-3,
        eval_every=10, use_shard_map=args.shard_map, wire=wire,
        log_fn=lambda r: print(
            f"epoch {r['epoch']:4d}  loss {r['loss']:.4f}  "
            f"rate {r['rate']:6.1f}  val {r['val_acc']:.3f}  "
            f"test {r['test_acc']:.3f}  comm {r['halo_gfloats']:.2f} Gf",
            flush=True))

    os.makedirs(args.out, exist_ok=True)
    write_csv(os.path.join(args.out, "history.csv"), res.history.rows())
    save(os.path.join(args.out, "model.msgpack"), res.params,
         extra={"policy": res.policy_desc,
                "test_acc": res.history.final_test_acc})
    print(f"\nfinal test acc {res.history.final_test_acc:.3f} "
          f"(best {res.history.best_test_acc:.3f}); "
          f"total comm {res.history.total_halo_gfloats:.2f} Gfloat; "
          f"artifacts in {args.out}/")
    if auto:
        spent = res.history.total_transport_gfloats * 32e9
        print(f"budget adherence: shipped {spent:.4g} of "
              f"{policy.budget_bits:.4g} bits "
              f"({spent / policy.budget_bits:.1%})")
        split = res.history.layer_split(args.workers)
        if split:
            print("per-layer transport split (Gfloat): " +
                  ", ".join(f"L{i}={v:.3f}" for i, v in enumerate(split)))


if __name__ == "__main__":
    main()
