"""Learning-rate schedules.

The reference uses a cifar10-fast-style piecewise-linear schedule: 0 at
epoch 0, peaking at ``lr_scale`` at ``pivot_epoch``, decaying to 0 at
``num_epochs`` (``cv_train.py`` ~L30-120, SURVEY.md §2 "cv_train entry").
Expressed here as a pure function of the (possibly traced) step index so it
lives happily inside jit.
"""

from __future__ import annotations

import jax.numpy as jnp


def piecewise_linear_lr(
    step,
    *,
    steps_per_epoch: int,
    pivot_epoch: float,
    num_epochs: float,
    lr_scale: float,
):
    """LR at a given optimizer step (step may be a traced int array).

    Host ints take the pure-Python path: the jnp version puts a scalar op
    on the device EVERY round and the train loop's ``float(lr_fn(step))``
    then pays a full host<->device round trip per round.
    """
    if isinstance(step, (int, float)):
        epoch = (step + 1) / steps_per_epoch
        up = epoch / max(pivot_epoch, 1e-8)
        down = (num_epochs - epoch) / max(num_epochs - pivot_epoch, 1e-8)
        return lr_scale * min(max(min(up, down), 0.0), 1.0)
    epoch = (step + 1) / steps_per_epoch
    up = epoch / jnp.maximum(pivot_epoch, 1e-8)
    down = (num_epochs - epoch) / jnp.maximum(num_epochs - pivot_epoch, 1e-8)
    frac = jnp.clip(jnp.minimum(up, down), 0.0, 1.0)
    return lr_scale * frac
