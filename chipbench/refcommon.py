"""What the plain references share: the precision in which matrix
products run, the loss, and the two optimizers the configurations use.

Nothing here imports the program.  ``Precision("f32")`` is the reference
proper (float32 throughout, products at ``highest``: on a TPU a float32
product otherwise runs in bfloat16 passes).  ``"bf16"`` rounds to
bfloat16 where the configurations state the program computes in it: the
operands of every product and every activation a layer hands on.
``"fp8"`` is the control, the same computed one precision down: the same
places rounded to float8_e4m3 with one scale per tensor (without a scale
that type holds no activation), cotangents left unrounded, the mildest
form there is."""

from __future__ import annotations

import jax
import jax.numpy as jnp

MODES = ("f32", "bf16", "fp8")


@jax.custom_vjp
def _fake_fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


_fake_fp8.defvjp(lambda x: (_fake_fp8(x), None), lambda _, g: (g,))


class Precision:
    """How the operands of a product are rounded before it."""

    def __init__(self, mode: str = "f32"):
        if mode not in MODES:
            raise ValueError(f"precision {mode!r} is not one of {MODES}")
        self.mode = mode

    def operand(self, x):
        x = x.astype(jnp.float32)
        if self.mode == "bf16":
            return x.astype(jnp.bfloat16).astype(jnp.float32)
        if self.mode == "fp8":
            return _fake_fp8(x)
        return x

    def store(self, x):
        """An activation as a layer hands it on."""
        return x if self.mode == "f32" else self.operand(x)

    def einsum(self, spec: str, a, b):
        return jnp.einsum(spec, self.operand(a), self.operand(b),
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)

    def conv(self, x, w, stride: int, pad: int):
        return jax.lax.conv_general_dilated(
            self.operand(x), self.operand(w), (stride, stride),
            ((pad, pad), (pad, pad)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)


def cross_entropy_sum(logits, labels):
    """Sum over rows of -log softmax(logits)[label]; integer labels."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=-1))


# -- optimizers: (params, grads, state, step) -> (params, state) -------------

def momentum_init(params):
    return jax.tree.map(jnp.zeros_like, params)


def momentum_step(hp, params, grads, state, step):
    """Flux ``Momentum``: v = rho v + lr g; p = p - v."""
    v = jax.tree.map(lambda v, g: hp["rho"] * v + hp["lr"] * g, state, grads)
    return jax.tree.map(lambda p, v: p - v, params, v), v


def momentum_first_grad(hp, state):
    """After the first step v = lr g."""
    return jax.tree.map(lambda v: v / hp["lr"], state)


def adamw_init(params):
    return jax.tree.map(lambda p: (jnp.zeros_like(p), jnp.zeros_like(p)),
                        params)


def adamw_step(hp, params, grads, state, step):
    """Bias-corrected Adam with decoupled weight decay."""
    t = jnp.asarray(step, jnp.float32) + 1.0
    c1 = 1.0 - hp["b1"] ** t
    c2 = 1.0 - hp["b2"] ** t

    def leaf(p, g, mv):
        m = hp["b1"] * mv[0] + (1 - hp["b1"]) * g
        v = hp["b2"] * mv[1] + (1 - hp["b2"]) * g * g
        new = p - hp["lr"] * (m / c1) / (jnp.sqrt(v / c2) + hp["eps"])
        return new - hp["lr"] * hp["weight_decay"] * p, (m, v)

    out = jax.tree.map(leaf, params, grads, state,
                       is_leaf=lambda x: isinstance(x, tuple))
    is_pair = lambda x: isinstance(x, tuple)  # noqa: E731
    return (jax.tree.map(lambda o: o[0], out, is_leaf=is_pair),
            jax.tree.map(lambda o: o[1], out, is_leaf=is_pair))


def adamw_first_grad(hp, state):
    """After the first step m = (1 - b1) g."""
    return jax.tree.map(lambda mv: mv[0] / (1 - hp["b1"]), state,
                        is_leaf=lambda x: isinstance(x, tuple))


OPTIMIZERS = {
    "momentum": (momentum_init, momentum_step, momentum_first_grad),
    "adamw": (adamw_init, adamw_step, adamw_first_grad),
}
