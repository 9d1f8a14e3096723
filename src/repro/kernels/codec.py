"""Pallas TPU kernels for the commit-transport codecs (DESIGN.md §10).

Encode/decode run once per commit over every parameter in the model, so
like the fused commit ops they are pure memory-bound passes worth fusing
into single HBM trips:

  * quantize_int8:   q ← clip(round(e/s)) ; r ← e − q·s
                     (1 read + 2 writes: the int8 payload and the
                     error-feedback residual come out of one pass over e,
                     vs three unfused elementwise kernels)
  * dequantize_int8: x ← q·s
  * encode_bf16:     q ← bf16(e) ; r ← e − f32(q)   (same single-pass shape)

Each call runs over one leaf in its own shape and layout, tiled by
``kernels.tiling``; with an int8 payload taking part, row bands are
multiples of 32 sublanes. ``e`` is read in its own dtype and widened to
f32 in-register. The per-leaf scale is a jnp reduction computed by the
caller — only the elementwise passes live here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .tiling import stream

__all__ = ["quantize_int8", "dequantize_int8", "encode_bf16"]


def _quantize_kernel(e_ref, s_ref, q_ref, r_ref):
    scale = s_ref[0, 0]
    e = e_ref[...].astype(jnp.float32)
    q = jnp.clip(jnp.round(e / scale), -127.0, 127.0)
    q_ref[...] = q.astype(jnp.int8)
    r_ref[...] = e - q * scale


def quantize_int8(e: jax.Array, scale: jax.Array, *, interpret: bool):
    """One leaf → (int8 payload, f32 error-feedback residual).

    ``scale`` is a (1, 1) f32 (positive; the caller guards zero) broadcast
    to every block like the fused-commit hyperparameter operands.
    """
    return stream(_quantize_kernel, (e,), (scale,), (jnp.int8, jnp.float32),
                  interpret=interpret)


def _dequantize_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[0, 0]


def dequantize_int8(q: jax.Array, scale: jax.Array, *, interpret: bool):
    """One int8 payload leaf → f32 (the PS-side decode pass)."""
    return stream(_dequantize_kernel, (q,), (scale,), (jnp.float32,),
                  interpret=interpret)[0]


def _encode_bf16_kernel(e_ref, q_ref, r_ref):
    e = e_ref[...].astype(jnp.float32)
    q = e.astype(jnp.bfloat16)
    q_ref[...] = q
    r_ref[...] = e - q.astype(jnp.float32)


def encode_bf16(e: jax.Array, *, interpret: bool):
    """One leaf → (bf16 payload, f32 residual) in one pass."""
    return stream(_encode_bf16_kernel, (e,), (), (jnp.bfloat16, jnp.float32),
                  interpret=interpret)
