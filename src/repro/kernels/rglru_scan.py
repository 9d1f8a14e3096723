"""RG-LRU linear-recurrence Pallas TPU kernel (chunked scan).

Computes h_t = a_t ⊙ h_{t−1} + b_t over the time axis for (B, S, W)
inputs. Grid: (B, W/block_w, S/block_s) with the time-chunk axis LAST
(sequential); the carry h lives in a (1, block_w) VMEM scratch persisting
across time chunks of the same (batch, channel-block) program family.

Within a chunk the recurrence is unrolled as a first-order scan in
registers (time is inherently sequential; the channel dimension is the
vector axis, block_w = 1024 lanes wide). TPU-adaptation note (DESIGN.md):
GPU implementations of linear recurrences lean on warp-parallel
Blelloch scans; on TPU the VPU prefers deep vector pipelines over lane
shuffles, so we parallelize across channels/batch (embarrassingly
parallel) and keep time sequential per program — the arithmetic intensity
is O(1) FLOP/byte either way (memory-bound), so the win is tiling for
sequential HBM streams, not FLOP reduction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["rglru_scan"]


def _rglru_kernel(a_ref, b_ref, o_ref, h_ref, *, block_s):
    sj = pl.program_id(2)

    @pl.when(sj == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    # rows are read and written through the refs: a dynamic index on a
    # loaded value (a[t]) has no Mosaic lowering, a dynamic ref slice does
    def step(t, h):  # h: (1, block_w)
        row = pl.ds(t, 1)
        h = a_ref[0, row, :] * h + b_ref[0, row, :]
        o_ref[0, row, :] = h
        return h

    h_ref[...] = jax.lax.fori_loop(0, block_s, step, h_ref[...])


def rglru_scan(a, b, *, block_w: int = 1024, block_s: int = 256,
               interpret: bool):
    """a, b: (B, S, W) float32 → h: (B, S, W). S % block_s == 0 and
    W % block_w == 0 (ops.py pads W; padding channels scan harmlessly)."""
    bsz, s, w = a.shape
    block_w = min(block_w, w)
    block_s = min(block_s, s)
    assert s % block_s == 0 and w % block_w == 0, (s, w, block_s, block_w)

    kernel = functools.partial(_rglru_kernel, block_s=block_s)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((bsz, s, w), a.dtype),
        grid=(bsz, w // block_w, s // block_s),
        in_specs=[
            pl.BlockSpec((1, block_s, block_w), lambda bi, wi, sj: (bi, sj, wi)),
            pl.BlockSpec((1, block_s, block_w), lambda bi, wi, sj: (bi, sj, wi)),
        ],
        out_specs=pl.BlockSpec((1, block_s, block_w), lambda bi, wi, sj: (bi, sj, wi)),
        scratch_shapes=[pltpu.VMEM((1, block_w), a.dtype)],
        interpret=interpret,
    )(a, b)
