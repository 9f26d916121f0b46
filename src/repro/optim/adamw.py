"""AdamW + schedules (optax is not available in this environment).

Pytree-generic; used by GP hyperparameter learning and SVGP.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp


class AdamState(NamedTuple):
    step: jax.Array
    mu: Any
    nu: Any


@dataclasses.dataclass(frozen=True)
class AdamW:
    lr: float | Callable[[jax.Array], jax.Array] = 1e-3
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.0
    grad_clip: float | None = None

    def init(self, params: Any) -> AdamState:
        zeros = lambda p: jnp.zeros_like(p)
        return AdamState(
            step=jnp.zeros((), jnp.int32),
            mu=jax.tree.map(zeros, params),
            nu=jax.tree.map(zeros, params),
        )

    def update(self, grads: Any, state: AdamState, params: Any):
        step = state.step + 1
        if self.grad_clip is not None:
            gnorm = global_norm(grads)
            scale = jnp.minimum(1.0, self.grad_clip / jnp.maximum(gnorm, 1e-12))
            grads = jax.tree.map(lambda g: g * scale, grads)
        lr = self.lr(step) if callable(self.lr) else self.lr
        b1, b2 = self.b1, self.b2
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state.mu, grads)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state.nu, grads)
        c1 = 1 - b1 ** step.astype(jnp.float32)
        c2 = 1 - b2 ** step.astype(jnp.float32)

        def upd(p, m, v):
            mhat = m / c1
            vhat = v / c2
            delta = mhat / (jnp.sqrt(vhat) + self.eps)
            if self.weight_decay and p.ndim >= 2:  # decay matrices only
                delta = delta + self.weight_decay * p
            return (p - lr * delta).astype(p.dtype)

        new_params = jax.tree.map(upd, params, mu, nu)
        return new_params, AdamState(step=step, mu=mu, nu=nu)


def global_norm(tree: Any) -> jax.Array:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves))


def cosine_schedule(peak_lr: float, warmup: int, total: int) -> Callable:
    def fn(step):
        step = step.astype(jnp.float32)
        warm = peak_lr * step / max(warmup, 1)
        prog = jnp.clip((step - warmup) / max(total - warmup, 1), 0.0, 1.0)
        cos = peak_lr * 0.5 * (1 + jnp.cos(jnp.pi * prog))
        return jnp.where(step < warmup, warm, cos)

    return fn
