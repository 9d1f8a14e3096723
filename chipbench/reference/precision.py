"""How the plain references multiply: in float32 at ``highest`` precision
(the reference), or in fp8 (the control, one precision step below the
configurations' bfloat16, whose programs hold both the operands and the
results of their matmuls in bfloat16).

The fp8 rounding is the usual recipe of fp8 training: a tensor is scaled
so that its largest magnitude lands on the format's largest value,
rounded to ``float8_e4m3fn``, and scaled back; a cotangent is rounded the
same way in ``float8_e5m2``. In the control both operands and the
result of every matmul are rounded so; sums inside a matmul and
everything between matmuls stay in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["einsum_fn", "MODES"]

MODES = ("f32", "fp8")


def _round_to(x, fmt):
    big = float(jnp.finfo(fmt).max)
    # the scale stays a normal float32 (a TPU flushes subnormals to 0),
    # and the quotient is clipped: float8_e4m3fn has no infinity
    s = jnp.maximum(jnp.max(jnp.abs(x)) / big, jnp.finfo(jnp.float32).tiny)
    y = jnp.clip(x / s, -big, big).astype(fmt).astype(jnp.float32)
    return (y * s).astype(x.dtype)


@jax.custom_vjp
def _fp8(x):
    return _round_to(x, jnp.float8_e4m3fn)


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, g):
    return (_round_to(g, jnp.float8_e5m2),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def einsum_fn(mode: str):
    """``einsum(spec, a, b)`` in the given mode, float32 in and out."""
    if mode not in MODES:
        raise ValueError(f"unknown precision mode {mode!r}; known: {MODES}")
    hi = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)
    if mode == "f32":
        return hi
    return lambda spec, a, b: _fp8(hi(spec, _fp8(a), _fp8(b)))
