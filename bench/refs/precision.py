"""Matrix products at a stated precision, the same on every backend.

``highest``: float32 operands at HIGHEST precision. ``high``: the
three-pass bfloat16 product a TPU runs for Precision.HIGH -- each operand
split into a bfloat16 high part and a bfloat16 remainder, the products
hi*hi + hi*lo + lo*hi accumulated in float32 -- written out, for the
forward product and for both products of its gradient, so that a CPU
computes the same thing. ``high`` is the control one step below a
configuration that states float32 at ``highest``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _split(x):
    x = x.astype(jnp.float32)
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)


def _three_pass(f, x, y):
    """f bilinear: f(x, y) from bfloat16 parts, lo*lo dropped."""
    x_hi, x_lo = _split(x)
    y_hi, y_lo = _split(y)
    return f(x_hi, y_hi) + (f(x_hi, y_lo) + f(x_lo, y_hi))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _einsum_high(s, a, b):
    return _three_pass(lambda x, y: jnp.einsum(s, x, y, precision=HIGHEST),
                       a, b)


def _fwd(s, a, b):
    return _einsum_high(s, a, b), (a, b)


def _bwd(s, res, g):
    a, b = res

    def da(bp, gp):
        return jax.vjp(lambda x: jnp.einsum(s, x, bp, precision=HIGHEST),
                       a)[1](gp)[0]

    def db(ap, gp):
        return jax.vjp(lambda y: jnp.einsum(s, ap, y, precision=HIGHEST),
                       b)[1](gp)[0]

    return (_three_pass(lambda gp, bp: da(bp, gp), g, b),
            _three_pass(lambda gp, ap: db(ap, gp), g, a))


_einsum_high.defvjp(_fwd, _bwd)


def einsum(precision: str):
    if precision == "highest":
        return lambda s, a, b: jnp.einsum(s, a.astype(jnp.float32),
                                          b.astype(jnp.float32),
                                          precision=HIGHEST)
    if precision == "high":
        return lambda s, a, b: _einsum_high(s, a.astype(jnp.float32),
                                            b.astype(jnp.float32))
    raise ValueError(f"unknown precision {precision!r}")
