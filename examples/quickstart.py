"""Quickstart: VARCO distributed GNN training in ~40 lines.

Trains the paper's 3-layer GraphSAGE on a synthetic citation graph split
across 4 workers, comparing full communication, no communication and VARCO
variable compression (Algorithm 1).

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.compile_cache import enable_compile_cache      # noqa: E402
from repro.core import FULL_COMM, NO_COMM, varco          # noqa: E402
from repro.graph import citation_graph                     # noqa: E402
from repro.train import train_gnn                          # noqa: E402


def main():
    enable_compile_cache()
    epochs = 100
    graph = citation_graph(n=3000, seed=0)
    print(f"graph: {graph.num_nodes} nodes, {graph.num_edges} edges, "
          f"{graph.num_classes} classes")

    results = {}
    for name, policy in [
        ("full communication", FULL_COMM),
        ("no communication", NO_COMM),
        ("VARCO (linear slope 5)", varco(epochs, slope=5)),
    ]:
        res = train_gnn(graph, q=4, scheme="random", policy=policy,
                        epochs=epochs, eval_every=25, hidden=64)
        results[name] = res
        h = res.history
        print(f"{name:24s} test_acc={h.best_test_acc:.3f} "
              f"comm={h.total_halo_gfloats:.2f} Gfloat")

    full = results["full communication"].history
    var = results["VARCO (linear slope 5)"].history
    saving = 1.0 - var.total_halo_gfloats / max(full.total_halo_gfloats,
                                                1e-9)
    print(f"\nVARCO reached {var.best_test_acc:.3f} "
          f"(full comm: {full.best_test_acc:.3f}) "
          f"while communicating {100 * saving:.0f}% fewer floats.")


if __name__ == "__main__":
    main()
