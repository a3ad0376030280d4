"""Functional optimizers matching the reference's Optimisers.jl contract.

The reference pins Optimisers.jl to an early revision whose API is
``st = Optimisers.state(opt, model)`` then ``m, st = opt(m, grads, st)``
(reference: src/overloads.jl:1-34 implements exactly those two tree walks;
README.md:37-38 uses ``Momentum(0.01, 0.9)``; src/sync.jl:97 uses
``ADAM()``).  The contract is *functional*: the optimizer is a pure value,
state is an explicit tree, and the update returns new params + new state.

That contract is already the idiomatic JAX shape, so here it is directly:

    opt = momentum(0.01, 0.9)
    state = opt.init(params)
    params, state = opt.apply(params, grads, state, step)

``apply`` is pure and jit-compatible (``step`` may be a traced scalar so
learning-rate schedules compile into the training step).  ``None`` leaves
in the gradient tree (non-differentiable / stateless layers — the
reference's ``nothing`` leaves) leave the corresponding parameter and
state untouched.

Implemented rules (hyperparameter semantics follow Flux/Optimisers.jl
where the reference uses them, standard forms otherwise):

* ``descent(lr)``          — plain SGD
* ``momentum(lr, rho)``    — Flux ``Momentum``: v = ρv + ηg; x -= v
* ``nesterov(lr, rho)``    — Flux ``Nesterov``
* ``adam(lr, b1, b2, eps)``— bias-corrected Adam (``ADAM()`` analog)
* ``adamw(...)``           — Adam + decoupled weight decay
* ``lars(...)``            — layerwise-adaptive momentum for large batch
                             (the ConvNeXt-XL large-batch config in
                             BASELINE.json)

Gradient/parameter transformations (wrap any optimizer):
``clip_by_global_norm(opt, max_norm)`` and ``with_ema(opt, decay)`` /
``ema_params(state)``.

Schedules (callables ``step -> lr``, usable anywhere ``lr`` is accepted):
``constant``, ``step_decay``, ``cosine_decay``, ``warmup_cosine``.
``step_decay(lr0, 0.2, 10)`` reproduces the reference's legacy LR/5 every
10 cycles (src/test.jl:50).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Union

import jax
import jax.numpy as jnp

Pytree = Any
Schedule = Callable[[Any], Any]
LR = Union[float, Schedule]

__all__ = [
    "Optimizer",
    "descent",
    "momentum",
    "nesterov",
    "adam",
    "adamw",
    "lars",
    "global_norm",
    "clip_by_global_norm",
    "with_ema",
    "ema_params",
    "constant",
    "step_decay",
    "cosine_decay",
    "warmup_cosine",
]


def _is_none(x):
    return x is None


def _lr_at(lr: LR, step):
    return lr(step) if callable(lr) else lr


def _map(f, *trees):
    """tree.map over grad trees where ``None`` marks a frozen leaf."""
    return jax.tree.map(f, *trees, is_leaf=_is_none)


def _map_with_state(step_leaf, params, state, grads):
    """Apply ``step_leaf(p, s, g) -> (p', s')`` across the three trees,
    tolerating ``None`` grad leaves and per-leaf state of any shape
    (e.g. Adam's ``(m, v)`` pairs, which a naive tree.map would descend
    into)."""
    flat_p, treedef = jax.tree.flatten(params, is_leaf=_is_none)
    flat_s = treedef.flatten_up_to(state)
    flat_g = treedef.flatten_up_to(grads)
    out = [step_leaf(p, s, g) for p, s, g in zip(flat_p, flat_s, flat_g)]
    new_p = treedef.unflatten([o[0] for o in out])
    new_s = treedef.unflatten([o[1] for o in out])
    return new_p, new_s


@dataclasses.dataclass(frozen=True)
class Optimizer:
    """A pure optimizer: ``init(params) -> state``;
    ``apply(params, grads, state, step) -> (params, state)``."""

    init: Callable[[Pytree], Pytree]
    update: Callable[[Pytree, Pytree, Pytree, Any], tuple[Pytree, Pytree]]
    name: str = "optimizer"

    def apply(self, params: Pytree, grads: Pytree, state: Pytree, step=0):
        return self.update(params, grads, state, step)

    # Allow the reference's call syntax: ``m, st = opt(m, grads, st)``
    # (src/overloads.jl:1-12).
    def __call__(self, params: Pytree, grads: Pytree, state: Pytree, step=0):
        return self.update(params, grads, state, step)


# ---------------------------------------------------------------------------
# Rules
# ---------------------------------------------------------------------------


def descent(lr: LR = 0.1) -> Optimizer:
    """Plain gradient descent: ``x -= η g``."""

    def init(params):
        return _map(lambda p: None, params)

    def update(params, grads, state, step):
        eta = _lr_at(lr, step)

        def f(p, g):
            return p if g is None else p - eta * g

        return _map(f, params, grads), state

    return Optimizer(init, update, "descent")


def momentum(lr: LR = 0.01, rho: float = 0.9) -> Optimizer:
    """Flux ``Momentum(η, ρ)``: ``v = ρ v + η g; x -= v``.

    The reference's demo optimizer (README.md:37-38).
    """

    def init(params):
        return _map(lambda p: None if p is None else jnp.zeros_like(p), params)

    def update(params, grads, state, step):
        eta = _lr_at(lr, step)

        def fv(v, g):
            return v if g is None else rho * v + eta * g

        def fp(p, v, g):
            return p if g is None else p - v

        new_v = _map(fv, state, grads)
        return _map(fp, params, new_v, grads), new_v

    return Optimizer(init, update, "momentum")


def nesterov(lr: LR = 0.01, rho: float = 0.9) -> Optimizer:
    """Flux ``Nesterov(η, ρ)`` lookahead momentum."""

    def init(params):
        return _map(lambda p: None if p is None else jnp.zeros_like(p), params)

    def update(params, grads, state, step):
        eta = _lr_at(lr, step)

        def step_leaf(p, v, g):
            if g is None:
                return p, v
            v2 = rho * v - eta * g
            d = rho * rho * v - (1 + rho) * eta * g
            return p + d, v2

        return _map_with_state(step_leaf, params, state, grads)

    return Optimizer(init, update, "nesterov")


def adam(lr: LR = 1e-3, b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8) -> Optimizer:
    """Bias-corrected Adam — the ``ADAM()`` analog (src/sync.jl:97)."""

    def init(params):
        def f(p):
            if p is None:
                return None
            return (jnp.zeros_like(p), jnp.zeros_like(p))

        return _map(f, params)

    def update(params, grads, state, step):
        eta = _lr_at(lr, step)
        t = jnp.asarray(step, jnp.float32) + 1.0
        c1 = 1.0 - jnp.power(b1, t)
        c2 = 1.0 - jnp.power(b2, t)

        def step_leaf(p, mv, g):
            if g is None:
                return p, mv
            m, v = mv
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * (g * g)
            mhat = m / c1
            vhat = v / c2
            return p - eta * mhat / (jnp.sqrt(vhat) + eps), (m, v)

        return _map_with_state(step_leaf, params, state, grads)

    return Optimizer(init, update, "adam")


def adamw(
    lr: LR = 1e-3,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 1e-2,
) -> Optimizer:
    """Adam with decoupled weight decay (for the ViT/ConvNeXt configs)."""
    base = adam(lr, b1, b2, eps)

    def update(params, grads, state, step):
        eta = _lr_at(lr, step)
        new_p, new_s = base.update(params, grads, state, step)

        def decay(np_, p, g):
            return np_ if g is None else np_ - eta * weight_decay * p

        return _map(decay, new_p, params, grads), new_s

    return Optimizer(base.init, update, "adamw")


def lars(
    lr: LR = 1.0,
    momentum_coef: float = 0.9,
    weight_decay: float = 0.0,
    trust_coefficient: float = 1e-3,
    eps: float = 1e-9,
) -> Optimizer:
    """LARS — layerwise adaptive rate scaling for large-batch training
    (the ConvNeXt-XL / ImageNet-21k large-batch config, BASELINE.json)."""

    def init(params):
        return _map(lambda p: None if p is None else jnp.zeros_like(p), params)

    def update(params, grads, state, step):
        eta = _lr_at(lr, step)

        def step_leaf(p, v, g):
            if g is None:
                return p, v
            g = g + weight_decay * p
            p_norm = jnp.linalg.norm(p.reshape(-1))
            g_norm = jnp.linalg.norm(g.reshape(-1))
            trust = jnp.where(
                (p_norm > 0) & (g_norm > 0),
                trust_coefficient * p_norm / (g_norm + eps),
                1.0,
            )
            v2 = momentum_coef * v + eta * trust * g
            return p - v2, v2

        return _map_with_state(step_leaf, params, state, grads)

    return Optimizer(init, update, "lars")


# ---------------------------------------------------------------------------
# Gradient transformations
# ---------------------------------------------------------------------------


def global_norm(tree: Pytree):
    """L2 norm over every non-``None`` leaf of a gradient tree (f32
    accumulation regardless of leaf dtype)."""
    leaves = [g for g in jax.tree.leaves(tree, is_leaf=_is_none) if g is not None]
    if not leaves:
        return jnp.zeros(())
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in leaves)
    )


def clip_by_global_norm(optimizer: Optimizer, max_norm: float) -> Optimizer:
    """Wrap an optimizer with global-norm gradient clipping (the standard
    transformer-training guard; ViT/ConvNeXt recipes clip at 1.0).

    Pure and jit-compatible: grads whose global norm exceeds
    ``max_norm`` are rescaled to exactly ``max_norm`` before the wrapped
    rule runs; smaller gradients pass through untouched.  ``None``
    (frozen) leaves are preserved.
    """

    def update(params, grads, state, step):
        norm = global_norm(grads)
        scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))

        def f(g):
            return None if g is None else (g * scale).astype(g.dtype)

        return optimizer.update(params, _map(f, grads), state, step)

    return Optimizer(
        init=optimizer.init, update=update, name=f"clip{max_norm}({optimizer.name})"
    )


def with_ema(optimizer: Optimizer, decay: float = 0.9999) -> Optimizer:
    """Track an exponential moving average of the parameters alongside
    any optimizer (the ViT/ConvNeXt eval-quality standard).

    The shadow copy lives inside the optimizer state (so it rides
    checkpointing, replication, and donation for free); read it with
    ``ema_params(opt_state)`` and evaluate via e.g.
    ``dataclasses.replace(state, params=ema_params(state.opt_state))``.
    The decay is warmup-corrected (``min(decay, (1+t)/(10+t))``) so early
    steps don't average against the random init.

    State layout honors the opt-state contract the TP/PP sharding
    machinery assumes (rules.train_state_specs/broadcast_prefix: "mirror the
    param tree, extra structure nested PER PARAM"): each param leaf maps
    to ``{"inner": <wrapped state leaf>, "ema": <shadow leaf>}``.  The
    shadow is a real copy (never an alias of the live param buffer, so
    donation can't free one array through two leaves) and stays in the
    param dtype.
    """

    def _split(params, state):
        """state tree -> (ema tree, inner tree, treedef, flat params)."""
        flat_p, treedef = jax.tree.flatten(params, is_leaf=_is_none)
        flat_s = treedef.flatten_up_to(state)
        inner = treedef.unflatten(
            [None if s is None else s["inner"] for s in flat_s]
        )
        ema = treedef.unflatten([None if s is None else s["ema"] for s in flat_s])
        return ema, inner, treedef, flat_p

    def _join(treedef, params_flat, inner, ema):
        flat_i = treedef.flatten_up_to(inner)
        flat_e = treedef.flatten_up_to(ema)
        return treedef.unflatten(
            [
                None if p is None else {"inner": i, "ema": e}
                for p, i, e in zip(params_flat, flat_i, flat_e)
            ]
        )

    def init(params):
        inner = optimizer.init(params)
        ema = _map(lambda p: None if p is None else jnp.copy(p), params)
        flat_p, treedef = jax.tree.flatten(params, is_leaf=_is_none)
        return _join(treedef, flat_p, inner, ema)

    def update(params, grads, state, step):
        ema, inner, treedef, flat_p = _split(params, state)
        new_p, new_inner = optimizer.update(params, grads, inner, step)
        t = jnp.asarray(step, jnp.float32)
        d = jnp.minimum(decay, (1.0 + t) / (10.0 + t))

        def f(e, p):
            if e is None:
                return None
            return (d * e + (1.0 - d) * p).astype(p.dtype)

        new_ema = _map(f, ema, new_p)
        return new_p, _join(treedef, flat_p, new_inner, new_ema)

    return Optimizer(init=init, update=update, name=f"ema{decay}({optimizer.name})")


def ema_params(opt_state: Pytree) -> Pytree:
    """The EMA shadow parameters from a ``with_ema`` optimizer state."""

    def _is_slot(x):
        return x is None or (isinstance(x, dict) and set(x) == {"inner", "ema"})

    leaves, treedef = jax.tree.flatten(opt_state, is_leaf=_is_slot)
    if not any(isinstance(s, dict) and "ema" in s for s in leaves):
        raise ValueError("opt_state does not carry an EMA (use optim.with_ema)")
    return treedef.unflatten([None if s is None else s["ema"] for s in leaves])


# ---------------------------------------------------------------------------
# Learning-rate schedules
# ---------------------------------------------------------------------------


def constant(lr: float) -> Schedule:
    return lambda step: lr


def step_decay(lr0: float, factor: float = 0.2, every: int = 10) -> Schedule:
    """Multiply the LR by ``factor`` every ``every`` steps.

    ``step_decay(lr, 0.2, 10)`` is the reference's legacy schedule — LR/5
    every 10 cycles (src/test.jl:50).
    """

    def sched(step):
        k = jnp.floor(jnp.asarray(step, jnp.float32) / every)
        return lr0 * jnp.power(factor, k)

    return sched


def cosine_decay(lr0: float, total_steps: int, final_fraction: float = 0.0) -> Schedule:
    def sched(step):
        t = jnp.clip(jnp.asarray(step, jnp.float32) / max(total_steps, 1), 0.0, 1.0)
        cos = 0.5 * (1.0 + jnp.cos(jnp.pi * t))
        return lr0 * (final_fraction + (1.0 - final_fraction) * cos)

    return sched


def warmup_cosine(lr0: float, warmup_steps: int, total_steps: int) -> Schedule:
    cos = cosine_decay(lr0, max(total_steps - warmup_steps, 1))

    def sched(step):
        s = jnp.asarray(step, jnp.float32)
        warm = lr0 * s / max(warmup_steps, 1)
        return jnp.where(s < warmup_steps, warm, cos(s - warmup_steps))

    return sched
