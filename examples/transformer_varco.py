"""VARCO on an assigned LLM architecture: data-parallel training with
variable-rate compressed gradient all-reduce over 4 virtual devices.

This is the paper's scheme transplanted to the transformer substrate
(DESIGN.md §4): early steps ship ~1/64 of the gradient bits, annealing to
full fidelity — loss matches the uncompressed run at a fraction of the
gradient traffic.

Run:  python examples/transformer_varco.py          (sets its own XLA flag)
"""

import os

# 4 virtual CPU devices for a real shard_map data-parallel mesh — set
# before any jax import (this is a standalone script, not a test).
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")

import sys  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def main():
    from repro.compile_cache import enable_compile_cache
    from repro.configs import get_config
    from repro.core import FULL_COMM, varco
    from repro.dist.grad_compress import make_dp_mesh, \
        make_varco_dp_train_step
    from repro.launch.steps import make_optimizer
    from repro.models.transformer import init_lm
    from repro.nn.modules import param_count

    enable_compile_cache()
    cfg = get_config("granite-3-2b", smoke=True)
    steps = 40
    mesh = make_dp_mesh(4)
    rng = np.random.default_rng(0)

    # bigram-structured synthetic corpus (so the LM has something to learn)
    trans = rng.dirichlet(np.full(cfg.vocab_size, 0.05), cfg.vocab_size)
    toks = np.zeros((8, 128), np.int32)
    for b in range(8):
        toks[b, 0] = rng.integers(cfg.vocab_size)
        for t in range(1, 128):
            toks[b, t] = rng.choice(cfg.vocab_size, p=trans[toks[b, t - 1]])
    batch = {"tokens": jnp.asarray(toks)}

    for name, pol in [("full", FULL_COMM),
                      ("varco", varco(steps, slope=5, c_max=64.0))]:
        params = init_lm(jax.random.key(0), cfg)
        print(f"\n== {name} ==  ({param_count(params):,} params, "
              f"{mesh.shape['data']} workers)")
        opt = make_optimizer(cfg, lr=3e-3)
        s = opt.init(params)
        step = make_varco_dp_train_step(cfg, opt, pol, mesh)
        p = params
        bits = 0.0
        for i in range(steps):
            p, s, m = step(p, s, batch, jnp.asarray(i), jax.random.key(i))
            bits += float(m["grad_bits"])
            if i % 10 == 0 or i == steps - 1:
                print(f"step {i:3d}  loss {float(m['loss']):.4f}  "
                      f"rate {float(m['rate']):5.1f}  "
                      f"grad-traffic {bits / 8e9:.3f} GB")


if __name__ == "__main__":
    main()
