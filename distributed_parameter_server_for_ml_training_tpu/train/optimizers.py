"""Optimizers, reproducing the reference's (inconsistent) choices explicitly.

The reference has TWO optimizer configurations with a documented discrepancy
(SURVEY.md §2.12): the parameter server applies plain ``p -= lr * g``
(server.py:133, lr 0.1) while workers *configure* SGD(momentum=0.9,
weight_decay=5e-4) but never call ``optimizer.step()`` — momentum and weight
decay are dead in distributed mode. The single-machine baseline uses the full
SGD(momentum 0.9, wd 5e-4) + MultiStepLR([10,15], gamma 0.1)
(baseline/baseline_training.py:223-224).

We reproduce both *deliberately*: :func:`server_sgd` is the distributed-mode
optimizer (matching the server math), :func:`baseline_optimizer` is the
baseline recipe, and callers may opt into the full recipe for distributed
training too (the "corrected" choice the reference never made).

:func:`adamw` is the decoder-LM task's optimizer (train/tasks.py chooses
the optimizer with the model family): the reference has no language model
and no Adam.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import optax


def server_sgd(learning_rate: float = 0.1) -> optax.GradientTransformation:
    """Plain SGD: exactly the server update ``p -= lr * g`` (server.py:133)."""
    return optax.sgd(learning_rate)


def baseline_optimizer(
    learning_rate: float = 0.1,
    momentum: float = 0.9,
    weight_decay: float = 5e-4,
    milestones: Sequence[int] = (10, 15),
    gamma: float = 0.1,
    steps_per_epoch: int = 1,
) -> optax.GradientTransformation:
    """SGD(momentum, wd) + MultiStepLR, matching baseline_training.py:223-224.

    torch semantics: weight decay is added to the raw gradient *before* the
    momentum buffer update, hence ``add_decayed_weights`` ahead of ``sgd``.
    ``milestones`` are epochs; the piecewise schedule operates on steps.
    """
    boundaries = {int(m) * int(steps_per_epoch): gamma for m in milestones}
    schedule = optax.piecewise_constant_schedule(learning_rate, boundaries)
    return optax.chain(
        optax.add_decayed_weights(weight_decay),
        optax.sgd(schedule, momentum=momentum),
    )


def adamw(learning_rate: float, *, b1: float = 0.9, b2: float = 0.95,
          eps: float = 1e-8,
          weight_decay: float = 0.1) -> optax.GradientTransformation:
    """AdamW with float32 moments and decoupled weight decay on matrices
    only (norm gains and other vectors are not decayed): with float32
    weights and gradients, 16 bytes a parameter. It is ``optax.adamw``'s
    update (a test holds the two equal on one tensor), written as its three
    steps so that the mask and the moments' type are in sight."""
    return optax.chain(
        optax.scale_by_adam(b1=b1, b2=b2, eps=eps, mu_dtype=jnp.float32),
        optax.add_decayed_weights(
            weight_decay,
            mask=lambda params: jax.tree_util.tree_map(
                lambda p: p.ndim >= 2, params)),
        optax.scale_by_learning_rate(learning_rate),
    )
