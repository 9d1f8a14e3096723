"""Pallas TPU kernels for the ADSP commit hot loop.

The two elementwise-fused ops that run once per microstep / commit over
every parameter in the model (hundreds of GB moved per step at scale —
pure memory-bound, so fusing them into single HBM passes matters):

  * accumulate:  U ← U + η′·g          (2 reads + 1 write per element,
                                         vs 3R+1W unfused read-mul-add)
  * ps_apply:    δ ← μ·δ − η·U ; W ← W + δ
                                        (3 reads + 2 writes, single pass)

Arrays are processed as flattened 1-D buffers tiled into (8, 1024) VMEM
blocks (8×128-lane aligned). The ops.py wrappers pad ragged tails and
reshape; per-leaf dispatch over a parameter pytree lives in ops.py too.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["accumulate", "ps_apply", "BLOCK", "block_for"]

BLOCK = (8, 1024)  # sublane × lane-aligned VMEM tile (f32: 32 KiB)


def block_for(dtype) -> tuple[int, int]:
    """VMEM tile for a dtype: the minimum sublane count doubles for
    2-byte dtypes (bf16 tiling is (16, 128)-aligned on TPU)."""
    return (16, 1024) if jnp.dtype(dtype).itemsize == 2 else BLOCK


# Hyper-params ride along as a (1, n) operand broadcast to every block —
# portable across jax versions (scalar-prefetch signatures vary).

def _accum_kernel(u_ref, g_ref, lr_ref, o_ref):
    o_ref[...] = u_ref[...] + lr_ref[0, 0].astype(u_ref.dtype) * g_ref[...]


def accumulate(u: jax.Array, g: jax.Array, local_lr, *, interpret: bool):
    blk = block_for(u.dtype)
    r, c = u.shape
    grid = (r // blk[0], c // blk[1])
    # f32 operand: Mosaic extracts only 32-bit scalars from a vector
    lr = jnp.full((1, 1), local_lr, jnp.float32)
    return pl.pallas_call(
        _accum_kernel,
        out_shape=jax.ShapeDtypeStruct(u.shape, u.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec(blk, lambda i, j: (i, j)),
            pl.BlockSpec(blk, lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec(blk, lambda i, j: (i, j)),
        interpret=interpret,
    )(u, g, lr)


def _ps_apply_kernel(w_ref, d_ref, u_ref, hp_ref, w_out, d_out):
    mu = hp_ref[0, 0]
    lr = hp_ref[0, 1]
    delta = mu.astype(d_ref.dtype) * d_ref[...] - lr.astype(u_ref.dtype) * u_ref[...]
    d_out[...] = delta
    w_out[...] = w_ref[...] + delta


def ps_apply(w, prev_delta, u, global_lr, momentum, *, interpret: bool):
    """Returns (new_w, new_delta); all (R, C) aligned like `accumulate`."""
    blk = block_for(w.dtype)
    r, c = w.shape
    grid = (r // blk[0], c // blk[1])
    hp = jnp.asarray([[momentum, global_lr]], jnp.float32)
    return pl.pallas_call(
        _ps_apply_kernel,
        out_shape=(
            jax.ShapeDtypeStruct(w.shape, w.dtype),
            jax.ShapeDtypeStruct(w.shape, w.dtype),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec(blk, lambda i, j: (i, j)),
            pl.BlockSpec(blk, lambda i, j: (i, j)),
            pl.BlockSpec(blk, lambda i, j: (i, j)),
            pl.BlockSpec((1, 2), lambda i, j: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec(blk, lambda i, j: (i, j)),
            pl.BlockSpec(blk, lambda i, j: (i, j)),
        ),
        interpret=interpret,
    )(w, prev_delta, u, hp)
