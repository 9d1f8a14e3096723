"""Pallas TPU kernels for the ADSP commit hot loop.

The two elementwise-fused ops that run once per microstep / commit over
every parameter in the model (hundreds of GB moved per step at scale —
pure memory-bound, so fusing them into single HBM passes matters):

  * accumulate:  U ← U + η′·g          (2 reads + 1 write per element,
                                         vs 3R+1W unfused read-mul-add)
  * ps_apply:    δ ← μ·δ − η·U ; W ← W + δ
                                        (3 reads + 2 writes, single pass)

Each call runs over one parameter leaf in its own shape and layout,
tiled by ``kernels.tiling`` (large blocks, no relayout copy); operands
of another dtype than the result are cast in-register, after the load.
Per-leaf dispatch over a parameter pytree lives in ops.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .tiling import stream

__all__ = ["accumulate", "ps_apply"]


# Hyper-params ride along as a (1, n) operand broadcast to every block —
# portable across jax versions (scalar-prefetch signatures vary); f32,
# since Mosaic extracts only 32-bit scalars from a vector.

def _accum_kernel(u_ref, g_ref, lr_ref, o_ref):
    o_ref[...] = (u_ref[...] + lr_ref[0, 0].astype(u_ref.dtype)
                  * g_ref[...].astype(u_ref.dtype))


def accumulate(u: jax.Array, g: jax.Array, local_lr, *, interpret: bool):
    """U + η′·g over one leaf; ``g`` is cast like ``u``."""
    lr = jnp.full((1, 1), local_lr, jnp.float32)
    return stream(_accum_kernel, (u, g), (lr,), (u.dtype,), interpret=interpret,
                  updates=((0, 0),))[0]


def _ps_apply_kernel(w_ref, d_ref, u_ref, hp_ref, w_out, d_out):
    mu = hp_ref[0, 0]
    lr = hp_ref[0, 1]
    dt = w_ref.dtype
    delta = mu.astype(dt) * d_ref[...].astype(dt) - lr.astype(dt) * u_ref[...].astype(dt)
    d_out[...] = delta
    w_out[...] = w_ref[...] + delta


def ps_apply(w, prev_delta, u, global_lr, momentum, *, interpret: bool):
    """Returns (new_w, new_delta), both in ``w``'s dtype; ``prev_delta``
    and ``u`` are cast like ``w``."""
    hp = jnp.asarray([[momentum, global_lr]], jnp.float32)
    return stream(_ps_apply_kernel, (w, prev_delta, u), (hp,), (w.dtype, w.dtype),
                  interpret=interpret, updates=((0, 0), (1, 1)))
