"""Plain float32 pieces the configurations' references share.

Written from the published descriptions, in straightforward ``jax.numpy``
with every matrix product at ``Precision.HIGHEST``; nothing here imports
the program.  A reference model is a module ``bench/configs/<config>.py``
with ``init(key, cfg)``, ``loss(params, tokens, cfg, dot)`` and
``model_flops_per_token(cfg, seq)``; its parameters are a dict of
float32 arrays, those of the layers stacked on a leading axis.

``dot`` carries every matrix product.  ``exact_dot`` computes it in
float32.  ``fp8_dot`` is the control: both operands are rounded to
float8 e4m3 with one scale per tensor (amax to 448) first, in the
forward and the backward products alike, the next precision below the
bfloat16 that the configurations compute in.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def exact_dot(spec: str, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _fp8(x):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, 448.0 / amax, 1.0)
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q / scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def fp8_dot(spec: str, a, b):
    return exact_dot(spec, _fp8(a), _fp8(b))


def _fp8_fwd(spec, a, b):
    qa, qb = _fp8(a), _fp8(b)
    return exact_dot(spec, qa, qb), (qa, qb)


def _fp8_bwd(spec, res, g):
    # the backward products take float8 operands too: the incoming
    # gradient rounded with its own scale; the rounding of a and b is
    # passed through as the identity
    _, vjp = jax.vjp(lambda x, y: exact_dot(spec, x, y), *res)
    return vjp(_fp8(g))


fp8_dot.defvjp(_fp8_fwd, _fp8_bwd)


DOTS = {"exact": exact_dot, "fp8": fp8_dot}


def normal(key, shape, scale):
    """The initializer of the published recipes: N(0, scale**2), f32."""
    return jax.random.normal(key, shape, jnp.float32) * scale


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def silu(x):
    return x * jax.nn.sigmoid(x)


def softplus(x):
    return jnp.logaddexp(x, 0.0)


def next_token_loss(logits, tokens):
    """Mean cross-entropy of position t's logits against token t+1."""
    lg = logits[:, :-1]
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


def adamw(params, grads, m, v, step, opt):
    """One AdamW step with global-norm clipping.  Returns the new params,
    moments and the clipped gradient (what the moments were fed)."""
    gn = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["grad_clip"] / (gn + 1e-9))
    g = jax.tree.map(lambda x: x * scale, grads)
    b1, b2 = opt["b1"], opt["b2"]
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step
    params = jax.tree.map(
        lambda p, m, v: p - opt["lr"] * (
            (m / c1) / (jnp.sqrt(v / c2) + opt["eps"])
            + opt["weight_decay"] * p),
        params, m, v)
    return params, m, v, g


# rows of a batch the reference differentiates at once: the gradient of
# a step is the mean of its blocks' gradients, so the reference fits
# beside its state and moments on one chip whatever the batch
BLOCK_ROWS = 2


def three_steps(model, cfg: dict, opt: dict, key, batches, dot=exact_dot,
                steps: int = 3, block_rows: int = BLOCK_ROWS):
    """Train the reference ``steps`` steps from ``key`` on ``batches``
    (a list of int32 [B, S] arrays).  Returns {"losses", "grad":
    per-leaf norms of the first clipped gradient, "update": per-leaf
    norms of the parameters' change after the last step, "sizes":
    per-leaf element counts}.

    Each step's loss and gradient are taken over blocks of
    ``block_rows`` rows and averaged: every block holds as many tokens,
    so the mean of the blocks' mean losses is the batch's."""
    with jax.default_matmul_precision("highest"):
        params = jax.jit(lambda k: model.init(k, cfg))(key)
        init_params = jax.tree.map(lambda x: np.asarray(x), params)
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)

        @jax.jit
        def block(params, tokens):
            return jax.value_and_grad(
                lambda p: model.loss(p, tokens, cfg, dot))(params)

        add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                      donate_argnums=(0,))

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
        def update(params, m, v, grad_sum, n, t):
            grads = jax.tree.map(lambda g: g / n, grad_sum)
            params, m, v, g = adamw(params, grads, m, v, t, opt)
            return params, m, v, leaf_norms_device(g)

        losses, gnorm = [], None
        for t, toks in enumerate(batches[:steps]):
            rows = min(block_rows, toks.shape[0])
            if toks.shape[0] % rows:
                raise ValueError(f"batch {toks.shape[0]} is not a multiple "
                                 f"of {rows} rows")
            loss_sum, grad_sum = 0.0, None
            for i in range(0, toks.shape[0], rows):
                loss, grads = block(params, toks[i:i + rows])
                loss_sum += float(loss)
                grad_sum = grads if grad_sum is None else add(grad_sum, grads)
            n = toks.shape[0] // rows
            params, m, v, gn = update(params, m, v, grad_sum,
                                      jnp.float32(n), jnp.float32(t + 1))
            losses.append(loss_sum / n)
            if gnorm is None:
                gnorm = gn
        delta = jax.jit(lambda p, p0: leaf_norms_device(
            jax.tree.map(jnp.subtract, p, p0)))(params, init_params)
    return {"losses": losses, "grad": norms_dict(gnorm),
            "update": norms_dict(delta), "sizes": leaf_sizes(params)}


def leaf_norms_device(tree):
    """On the device: per leaf (per layer for ``layers``) L2 norms."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        x = x.astype(jnp.float32)
        if name.startswith("layers/"):
            out[name] = jnp.sqrt(jnp.sum(
                x.reshape(x.shape[0], -1) ** 2, axis=1))
        else:
            out[name] = jnp.sqrt(jnp.sum(x * x))[None]
    return out


def leaf_sizes(tree) -> dict:
    """Element count of every leaf, per layer for ``layers``, keyed as
    ``norms_dict`` keys its norms."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        if name.startswith("layers/"):
            n = int(np.prod(x.shape[1:]))
            out.update({f"{name}/{i}": n for i in range(x.shape[0])})
        else:
            out[name] = int(np.prod(x.shape))
    return out


def norms_dict(dev_norms) -> dict:
    """Flatten ``leaf_norms_device`` output to {"name[/layer]": float}."""
    out = {}
    for name, arr in dev_norms.items():
        arr = np.asarray(arr, np.float64)
        if name.startswith("layers/"):
            out.update({f"{name}/{i}": float(n) for i, n in enumerate(arr)})
        else:
            out[name] = float(arr[0])
    return out
