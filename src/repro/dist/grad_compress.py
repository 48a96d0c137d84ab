"""VARCO gradient compression for data-parallel LM training (DESIGN.md §4).

The paper's variable-rate scheme transplanted from halo activations to the
data-parallel gradient all-reduce: each worker compresses its local gradient
with a Definition-1 compressor (per-worker mask streams derived from a
shared key), the compressed contributions are summed
(:func:`repro.core.collectives.compressed_psum`), and the rate anneals under
the policy's scheduler — early steps ship a fraction of the gradient bits,
converging to exact synchronous SGD as ``rate -> 1``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core.collectives import compressed_psum, uncompressed_bits
from repro.core.varco import CommPolicy
from repro.train.optim import (Optimizer, apply_updates,
                               clip_by_global_norm)

AXIS = "data"


def make_dp_mesh(n_devices: int | None = None) -> Mesh:
    """1-D data-parallel mesh over the first ``n_devices`` local devices
    (all of them by default).

    Example::

        mesh = make_dp_mesh()               # axis name: "data"
        step = make_varco_dp_train_step(cfg, opt, policy, mesh)
    """
    devs = jax.devices()
    n = len(devs) if n_devices is None else n_devices
    if len(devs) < n:
        raise ValueError(f"need {n} devices, have {len(devs)}")
    return Mesh(np.asarray(devs[:n]), (AXIS,))


def make_varco_dp_train_step(cfg: ArchConfig, optimizer: Optimizer,
                             policy: CommPolicy, mesh: Mesh,
                             clip: float = 1.0):
    """Data-parallel LM train step with VARCO-compressed gradient psum.

    ``step(params, opt_state, batch, step_idx, key)`` ->
    ``(params, opt_state, {loss, ce, moe_aux, grad_norm, grad_bits, rate})``.

    The batch pytree is split over ``data`` on its leading dim; parameters
    and optimizer state are replicated.  ``grad_bits`` charges the ring
    all-reduce traffic of the (compressed) payload; the full-communication
    baseline charges the uncompressed equivalent so accuracy-per-byte curves
    share an axis.

    Example::

        cfg = get_config("granite-3-2b", smoke=True)
        policy = CommPolicy.parse("varco:linear:5", total_steps=200)
        step = make_varco_dp_train_step(cfg, make_optimizer(cfg), policy,
                                        make_dp_mesh())
        params, opt_state, m = step(params, opt_state,
                                    {"tokens": tokens}, 0,
                                    jax.random.key(0))
    """
    # deferred: models.transformer imports repro.dist.sharding at module
    # scope, so a top-level import here would be circular
    from repro.models.transformer import lm_loss

    compressor = policy.compressor() if policy.compresses else None
    q = mesh.shape[AXIS]

    def worker(params, opt_state, batch, rate, key):
        (loss, parts), grads = jax.value_and_grad(
            lm_loss, has_aux=True)(params, cfg, batch)
        if compressor is not None:
            grads, grad_bits = compressed_psum(
                grads, AXIS, compressor=compressor, rate=rate, key=key)
            grads = jax.tree_util.tree_map(lambda g: g / q, grads)
        else:
            grad_bits = uncompressed_bits(grads) * 2.0 * (q - 1)
            grads = lax.pmean(grads, AXIS)
        loss = lax.pmean(loss, AXIS)
        grads, gnorm = clip_by_global_norm(grads, clip)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        metrics = {"loss": loss, "ce": lax.pmean(parts["ce"], AXIS),
                   "moe_aux": lax.pmean(parts["moe_aux"], AXIS),
                   "grad_norm": gnorm, "grad_bits": grad_bits}
        return params, opt_state, metrics

    sm = shard_map(worker, mesh=mesh,
                   in_specs=(P(), P(), P(AXIS), P(), P()),
                   out_specs=(P(), P(), P()), check_vma=False)

    @jax.jit
    def step(params, opt_state, batch, step_idx, key):
        rate = policy.rate(step_idx)
        params, opt_state, metrics = sm(params, opt_state, batch, rate, key)
        metrics["rate"] = rate
        return params, opt_state, metrics

    return step
