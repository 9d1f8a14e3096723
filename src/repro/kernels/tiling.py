"""How the per-leaf streaming kernels tile a parameter leaf.

The ADSP update kernels (``fused_commit``, ``codec``,
``fused_codec_commit``) are elementwise passes over every parameter
leaf, so what they cost is HBM traffic plus a fixed overhead per grid
step. Each call is built over the leaf in its own shape, so XLA hands
the buffer to the kernel as it lies in HBM: no relayout copy in, none
out. The blocks are large (``BLOCK_BYTES`` over all operands of a step),
so a call takes hundreds of grid steps, not tens of thousands.

A leaf's plan is a pure function of its shape and the dtypes of the
operands that take part (``plan``):

* the last two dims are the tiled pair; leading dims go in the grid
  with squeezed block dims, except the innermost, which takes as many
  rows of whole trailing tiles as the budget allows;
* where the trailing pair is too large for one block it is cut in whole
  rows, in multiples of the sublane count (32 wherever an int8 operand
  takes part, 16 with a 2-byte one, else 8), and where a row band of
  that many rows is still too large, its columns too, in multiples of
  128 lanes;
* cuts are balanced (``cdiv`` of the dim over the number of blocks,
  rounded up to the alignment); a ragged edge is a partial last block,
  never a pad;
* a rank-1 leaf (norms, biases) is one whole block where Mosaic takes
  it: a length in whole packed vregs (a multiple of 16 × the sublane
  count) inside the budget. Any other rank-0 or rank-1 leaf takes a
  ``(1, n)`` view: the only reshape, and the only plan with
  ``relayout`` set.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["LeafPlan", "plan", "block_bytes", "stream", "BLOCK_BYTES", "VMEM_LIMIT_BYTES"]

LANES = 128
BLOCK_BYTES = 8 << 20  # one grid step's blocks, all operands, single-buffered
# the pipeline double-buffers every block, and the kernel body keeps its
# f32 intermediates of a block on the VMEM stack (about 1.4 x the blocks
# for the int8 quantize): above the 16 MiB scoped default, well inside
# v5e's 128 MiB of VMEM
VMEM_LIMIT_BYTES = 6 * BLOCK_BYTES


@dataclasses.dataclass(frozen=True)
class LeafPlan:
    """How one leaf is tiled: the shape the kernel is built over, its
    block (``None`` = a squeezed dim), the grid, and whether reaching
    ``view`` took a reshape of the leaf."""

    view: tuple[int, ...]
    block: tuple[int | None, ...]
    grid: tuple[int, ...]
    relayout: bool

    @property
    def steps(self) -> int:
        return math.prod(self.grid)


def _cut(n: int, most: int, align: int) -> int:
    """Balanced block size along a dim of ``n``: at most ``most`` (a
    multiple of ``align``), rounded up to ``align``; ``n`` if one block
    holds it."""
    if n <= most:
        return n
    per = -(-n // -(-n // most))
    return -(-per // align) * align


def _up(n: int, k: int) -> int:
    return -(-n // k) * k


def block_bytes(rows: int, a: int, b: int, dtypes) -> int:
    """VMEM bytes of one grid step's blocks: ``rows`` trailing tiles of
    ``(a, b)`` for each operand, padded to its dtype's (sublane, 128)
    tile."""
    return rows * sum(jnp.dtype(d).itemsize * _up(a, 32 // jnp.dtype(d).itemsize)
                      * _up(b, LANES) for d in dtypes)


def plan(shape, dtypes) -> LeafPlan:
    """The tiling of a leaf of ``shape`` over operands of ``dtypes``
    (inputs and outputs alike)."""
    shape = tuple(shape)
    sub = max(32 // jnp.dtype(d).itemsize for d in dtypes)
    if (len(shape) == 1 and shape[0] % (16 * sub) == 0
            and block_bytes(1, 1, shape[0], dtypes) <= BLOCK_BYTES):
        return LeafPlan(shape, shape, (1,), False)
    relayout = len(shape) < 2
    view = (1, math.prod(shape)) if relayout else shape
    *lead, a, b = view
    squeezed = [None] * len(lead)
    pair = block_bytes(1, a, b, dtypes)
    band = block_bytes(1, sub, b, dtypes)
    if pair <= BLOCK_BYTES:  # the trailing pair whole
        if lead:
            squeezed[-1] = _cut(lead[-1], BLOCK_BYTES // pair, 1)
        block = (*squeezed, a, b)
    elif band <= BLOCK_BYTES:  # bands of whole rows
        block = (*squeezed, _cut(a, BLOCK_BYTES // band * sub, sub), b)
    else:  # a band of ``sub`` rows, cut in columns
        cols = BLOCK_BYTES // block_bytes(1, sub, LANES, dtypes) * LANES
        block = (*squeezed, min(a, sub), _cut(b, cols, LANES))
    grid = tuple(-(-n // (k or 1)) for n, k in zip(view, block))
    return LeafPlan(view, block, grid, relayout)


def stream(kernel, leaves, hyper, out_dtypes, *, interpret: bool, updates=()):
    """One ``pallas_call`` of the elementwise ``kernel`` over same-shaped
    ``leaves``, each in its own dtype, tiled by ``plan``. ``hyper`` are
    small ``(1, n)`` f32 operands handed whole to every grid step.
    Returns a tuple of outputs shaped like the leaves, in ``out_dtypes``.

    ``updates`` pairs (leaf, output) where the output is the next value
    of a state leaf: where their dtypes agree, the output is written
    over the leaf's buffer. A donated, dead leaf is then updated in
    place; XLA copies a leaf that is still live first. Without it, a
    donated leaf costs a copy of the result into the donated buffer.
    Interpret mode (the CPU check) gives every output a fresh buffer:
    there is no HBM to save, and the interpreter's aliasing is unfinished
    (it breaks under explicit mesh shardings)."""
    shape = leaves[0].shape
    p = plan(shape, [x.dtype for x in leaves] + list(out_dtypes))
    if p.relayout:
        leaves = [x.reshape(p.view) for x in leaves]
    spec = pl.BlockSpec(p.block, lambda *i: i)
    outs = pl.pallas_call(
        kernel,
        out_shape=tuple(jax.ShapeDtypeStruct(p.view, d) for d in out_dtypes),
        grid=p.grid,
        in_specs=[spec] * len(leaves)
        + [pl.BlockSpec(h.shape, lambda *i: (0, 0)) for h in hyper],
        out_specs=tuple(spec for _ in out_dtypes),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        input_output_aliases={} if interpret else {
            i: o for i, o in updates if leaves[i].dtype == jnp.dtype(out_dtypes[o])},
        interpret=interpret,
    )(*leaves, *hyper)
    return tuple(o.reshape(shape) for o in outs) if p.relayout else outs
