"""Adam and the paper's learning-rate decay, pure JAX on pytrees.

Written from scratch (optax is not a dependency): an ``init``/``update``
pair over arbitrary pytrees, enough for the mapping-model trainer and
the MHAS search (Adam, lr 1e-3, decay 0.999 — §V-A6).
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp


class OptState(NamedTuple):
    step: jnp.ndarray  # scalar int32
    mu: object  # first-moment pytree
    nu: object  # second-moment pytree


def _zeros_like_tree(params):
    return jax.tree.map(jnp.zeros_like, params)


def adam_init(params) -> OptState:
    return OptState(
        step=jnp.zeros((), jnp.int32),
        mu=_zeros_like_tree(params),
        nu=_zeros_like_tree(params),
    )


def adam_update(
    grads,
    state: OptState,
    params,
    lr: jnp.ndarray | float,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
):
    """One Adam step. Returns (new_params, new_state)."""
    step = state.step + 1
    stepf = step.astype(jnp.float32)
    bc1 = 1.0 - jnp.power(b1, stepf)
    bc2 = 1.0 - jnp.power(b2, stepf)

    mu = jax.tree.map(lambda m, g: b1 * m + (1.0 - b1) * g, state.mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1.0 - b2) * (g * g), state.nu, grads)

    def upd(p, m, v):
        mhat = m / bc1
        vhat = v / bc2
        delta = mhat / (jnp.sqrt(vhat) + eps)
        return (p - lr * delta).astype(p.dtype)

    new_params = jax.tree.map(upd, params, mu, nu)
    return new_params, OptState(step=step, mu=mu, nu=nu)


# -- schedules ---------------------------------------------------------------


def exponential_decay(base_lr: float, decay: float) -> Callable:
    """Paper §V-A6: model lr 0.001 decayed by 0.999 per iteration."""

    def sched(step):
        return base_lr * jnp.power(decay, step.astype(jnp.float32))

    return sched
