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

Arrays arrive as flattened 2-D buffers tiled into lane-aligned VMEM
blocks; because the int8 payload participates, tiles are (32, 1024)
(int8 min sublane count is 32; f32/bf16 operands are fine at any
multiple of 8/16). The ops.py wrappers pad ragged tails and reshape;
the per-leaf scale is a jnp reduction computed by the caller — only the
elementwise passes live here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["quantize_int8", "dequantize_int8", "encode_bf16", "QBLOCK"]

QBLOCK = (32, 1024)  # int8-safe sublane × lane-aligned VMEM tile


def _quantize_kernel(e_ref, s_ref, q_ref, r_ref):
    scale = s_ref[0, 0]
    q = jnp.clip(jnp.round(e_ref[...] / scale), -127.0, 127.0)
    q_ref[...] = q.astype(jnp.int8)
    r_ref[...] = e_ref[...] - q * scale


def quantize_int8(e: jax.Array, scale: jax.Array, *, interpret: bool):
    """(R, C) f32 → (int8 payload, f32 error-feedback residual).

    ``scale`` is a (1, 1) f32 (positive; the caller guards zero) broadcast
    to every block like the fused-commit hyperparameter operands.
    """
    blk = QBLOCK
    r, c = e.shape
    grid = (r // blk[0], c // blk[1])
    return pl.pallas_call(
        _quantize_kernel,
        out_shape=(
            jax.ShapeDtypeStruct(e.shape, jnp.int8),
            jax.ShapeDtypeStruct(e.shape, jnp.float32),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec(blk, lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec(blk, lambda i, j: (i, j)),
            pl.BlockSpec(blk, lambda i, j: (i, j)),
        ),
        interpret=interpret,
    )(e, scale)


def _dequantize_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[0, 0]


def dequantize_int8(q: jax.Array, scale: jax.Array, *, interpret: bool):
    """(R, C) int8 payload → f32 (the PS-side decode pass)."""
    blk = QBLOCK
    r, c = q.shape
    grid = (r // blk[0], c // blk[1])
    return pl.pallas_call(
        _dequantize_kernel,
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec(blk, lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec(blk, lambda i, j: (i, j)),
        interpret=interpret,
    )(q, scale)


def _encode_bf16_kernel(e_ref, q_ref, r_ref):
    q = e_ref[...].astype(jnp.bfloat16)
    q_ref[...] = q
    r_ref[...] = e_ref[...] - q.astype(jnp.float32)


def encode_bf16(e: jax.Array, *, interpret: bool):
    """(R, C) f32 → (bf16 payload, f32 residual) in one pass."""
    blk = QBLOCK
    r, c = e.shape
    grid = (r // blk[0], c // blk[1])
    return pl.pallas_call(
        _encode_bf16_kernel,
        out_shape=(
            jax.ShapeDtypeStruct(e.shape, jnp.bfloat16),
            jax.ShapeDtypeStruct(e.shape, jnp.float32),
        ),
        grid=grid,
        in_specs=[pl.BlockSpec(blk, lambda i, j: (i, j))],
        out_specs=(
            pl.BlockSpec(blk, lambda i, j: (i, j)),
            pl.BlockSpec(blk, lambda i, j: (i, j)),
        ),
        interpret=interpret,
    )(e)
