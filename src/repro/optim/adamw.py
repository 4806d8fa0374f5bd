"""AdamW with optional ZeRO-1 sharding of the optimizer state.

Pure-pytree implementation (no optax): ``state = {m, v, step}``.  Under
ZeRO-1 the first/second-moment tensors are additionally sharded over the
*data* axes on their largest divisible dimension — each data-parallel rank
keeps only its shard of the optimizer state, which XLA turns into
reduce-scatter(grads) + all-gather(params) around the update.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.sharding.partition import data_axes
from repro.sharding.rules import param_shardings


def adamw_init(params):
    zeros = jax.tree.map(lambda p: jnp.zeros_like(p, dtype=jnp.float32), params)
    return {"m": zeros,
            "v": jax.tree.map(jnp.zeros_like, zeros),
            "step": jnp.zeros((), jnp.int32)}


@jax.named_scope("adamw")
def adamw_update(params, grads, state, *, lr=3e-4, b1=0.9, b2=0.95,
                 eps=1e-8, weight_decay=0.1, grad_clip=1.0):
    step = state["step"] + 1
    # global-norm clip
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                      for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, grad_clip / (gn + 1e-9))

    def upd(p, g, m, v):
        g = g.astype(jnp.float32) * scale
        m2 = b1 * m + (1 - b1) * g
        v2 = b2 * v + (1 - b2) * g * g
        mhat = m2 / (1 - b1 ** step.astype(jnp.float32))
        vhat = v2 / (1 - b2 ** step.astype(jnp.float32))
        delta = mhat / (jnp.sqrt(vhat) + eps) + weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), m2, v2

    out = jax.tree.map(upd, params, grads, state["m"], state["v"])
    new_params = jax.tree.map(lambda t: t[0], out,
                              is_leaf=lambda t: isinstance(t, tuple))
    new_m = jax.tree.map(lambda t: t[1], out,
                         is_leaf=lambda t: isinstance(t, tuple))
    new_v = jax.tree.map(lambda t: t[2], out,
                         is_leaf=lambda t: isinstance(t, tuple))
    return new_params, {"m": new_m, "v": new_v, "step": step}, gn


def zero1_shardings(param_tree, mesh: Mesh):
    """Shardings for the optimizer state: params' TP sharding PLUS data-axis
    sharding on the largest still-unsharded divisible dim (ZeRO-1)."""
    pshard = param_shardings(param_tree, mesh)
    dp = data_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]

    def one(leaf, ns):
        spec = list(ns.spec) + [None] * (len(leaf.shape) - len(ns.spec))
        # choose the largest unsharded dim divisible by the data axes
        best, best_dim = -1, None
        for i, (dim, s) in enumerate(zip(leaf.shape, spec)):
            if s is None and dim % dp_size == 0 and dim > best:
                best, best_dim = dim, i
        if best_dim is not None and dp:
            spec[best_dim] = dp if len(dp) > 1 else dp[0]
        return NamedSharding(mesh, P(*spec))

    moments = jax.tree.map(one, param_tree, pshard)
    return {"m": moments, "v": moments,
            "step": NamedSharding(mesh, P())}
