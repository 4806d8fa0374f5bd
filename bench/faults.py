"""Faults planted in the program's train step, to show that the check
catches them.  Each replaces ``make_train_step`` as the trainer imports
it, for as long as the ``planted`` context lasts:

- ``state_unchanged``: the step computes its loss and returns the
  parameters and optimizer state it was given.
- ``half_batch``: the loss is the mean over the first half of the batch's
  tokens (rows where the batch has two or more, else positions); the
  rest are left out.
- ``loss_altered``: the step reports its loss 1 % high.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp


def _step(model, lr, fault):
    from repro.optim import adamw_update

    def loss_fn(params, batch):
        if fault == "half_batch":
            B, S = batch["tokens"].shape
            idx = jnp.arange(B * (S - 1)).reshape(B, S - 1)
            batch = dict(batch, mask=(idx < B * (S - 1) // 2)
                         .astype(jnp.float32))
        return model.loss(params, batch)

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        if fault == "state_unchanged":
            return params, opt_state, {"loss": loss, "grad_norm": 0.0}
        new_p, new_o, gn = adamw_update(params, grads, opt_state, lr=lr)
        if fault == "loss_altered":
            loss = loss * 1.01
        return new_p, new_o, {"loss": loss, "grad_norm": gn}

    return train_step


FAULTS = ("state_unchanged", "half_batch", "loss_altered")


@contextlib.contextmanager
def planted(fault: str):
    import repro.runtime.train_loop as tl
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    orig = tl.make_train_step
    tl.make_train_step = lambda model, *, lr=3e-4: _step(model, lr, fault)
    try:
        yield
    finally:
        tl.make_train_step = orig
