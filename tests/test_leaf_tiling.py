"""The per-leaf streaming kernels run over each leaf in its own shape
(``repro.kernels.tiling``): every wrapper bit for bit against its
``kernels/ref.py`` twin at each class of leaf shape, in interpret mode,
and the tiling plan of the granite3-8b-l3 training cell's leaves.

Operands take the dtypes the training cell runs: bf16 parameters,
commit deltas and updates, f32 error-feedback residuals, int8 or bf16
payloads. The twins run under jit like every real call site (eager mode
skips XLA's FMA contraction of e − q·s and differs below one ulp)."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_array_equal

from repro.kernels import ops, ref, tiling

LR, MU, SCALE = 0.7, 0.9, 0.013

# each class of leaf shape the plan distinguishes
SHAPES = {
    "rank0": (),
    "rank1_whole": (4096,),  # one whole 1-D block
    "rank1_view": (257,),  # a (1, n) view
    "rank2_ragged_lanes": (300, 200),  # last dim not a multiple of 128
    "rank3_ragged_rows": (3, 700, 1000),  # rows not a multiple of the block
    "rank4_sublanes8": (2, 50, 8, 128),  # an int8 payload with 8 sublanes
    "both_axes_cut": (40, 100_000),  # larger than a block on both tiled axes
}

# wrapper: (operand kinds, hyper-parameters, dtypes the kernel sees); the
# wrapper in ``ops`` and its twin in ``ref`` share the signature
P, R, X, Q, QB = "param", "residual", "update_f32", "int8", "bf16_payload"
BF, F32, I8 = jnp.bfloat16, jnp.float32, jnp.int8
WRAPPERS = {
    "accumulate_tree": (ops.accumulate_tree, ref.fused_accumulate,
                        (P, P), (LR,), (BF, BF, BF)),
    "ps_apply_tree": (ops.ps_apply_tree, ref.fused_ps_apply,
                      (P, P, P), (LR, MU), (BF,) * 5),
    "quantize_int8": (ops.quantize_int8, ref.quantize_int8,
                      (P,), (SCALE,), (BF, I8, F32)),
    "dequantize_int8": (ops.dequantize_int8, ref.dequantize_int8,
                        (Q,), (SCALE,), (I8, F32)),
    "encode_bf16": (ops.encode_bf16, ref.encode_bf16, (X,), (), (F32, BF, F32)),
    "quantize_int8_ef": (ops.quantize_int8_ef, ref.quantize_int8_ef,
                         (P, R), (SCALE,), (BF, F32, I8, F32)),
    "encode_bf16_ef": (ops.encode_bf16_ef, ref.encode_bf16_ef,
                       (P, R), (), (BF, F32, BF, F32)),
    "int8_decode_apply": (ops.int8_decode_apply, ref.int8_decode_apply,
                          (P, P, Q), (SCALE, LR, MU), (BF, BF, I8, BF, BF)),
    "bf16_decode_apply": (ops.bf16_decode_apply, ref.bf16_decode_apply,
                          (P, P, QB), (LR, MU), (BF,) * 5),
    "int8_decode_accum": (ops.int8_decode_accum, ref.int8_decode_accum,
                          (P, Q), (SCALE, LR), (BF, I8, BF)),
    "bf16_decode_accum": (ops.bf16_decode_accum, ref.bf16_decode_accum,
                          (P, QB), (LR,), (BF, BF, BF)),
}


def _operand(rng, kind, shape):
    if kind == Q:
        return jnp.asarray(rng.integers(-127, 128, size=shape), I8)
    sd = {P: 1.0, R: 0.01, X: 1.0, QB: 0.1}[kind]
    dt = {P: BF, R: F32, X: F32, QB: BF}[kind]
    return jnp.asarray(rng.normal(size=shape) * sd, dt)


def _bits(x):
    x = np.asarray(x)
    return x.view({1: np.int8, 2: np.int16, 4: np.int32}[x.dtype.itemsize])


@pytest.mark.parametrize("shape_name", list(SHAPES))
@pytest.mark.parametrize("name", list(WRAPPERS))
def test_leaf_kernels_match_ref_bitwise(name, shape_name):
    call, twin, kinds, hyper, dtypes = WRAPPERS[name]
    shape = SHAPES[shape_name]
    if shape_name == "both_axes_cut":  # the case is what its name says
        p = tiling.plan(shape, dtypes)
        assert p.grid[-1] > 1 and p.grid[-2] > 1, p
    rng = np.random.default_rng(len(name) * 1000 + len(shape_name))
    args = [_operand(rng, k, shape) for k in kinds]
    # hyper-parameters go in as traced arguments on both sides, as at
    # the call sites (a folded constant changes XLA's FMA contraction)
    got = call(*args, *hyper, interpret=True)
    want = jax.jit(twin)(*args, *hyper)
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.shape == shape and g.dtype == w.dtype, (g.shape, g.dtype, w.dtype)
        assert_array_equal(_bits(g), _bits(w))


# ---------------------------------------------------------------------------
# the plan of the training cell's leaves
# ---------------------------------------------------------------------------

# granite3-8b-l3 (chipbench/configs/granite3-8b-l3.json) as the program
# stacks it: embed, final norm, attention k/o/q/v, MLP gate/in/out, the
# two norms, the untied head
CELL_LEAVES = [(49408, 4096), (4096,), (3, 4096, 8, 128), (3, 4096, 4096),
               (3, 4096, 32, 128), (3, 4096, 8, 128), (3, 4096, 12800),
               (3, 4096, 12800), (3, 12800, 4096), (3, 4096), (3, 4096),
               (4096, 49408)]
# the dtypes each of the cell's three kernels sees, inputs then outputs
CELL_KERNELS = {
    "accumulate_tree": (BF, BF, BF),
    "quantize_int8_ef": (BF, F32, I8, F32),
    "int8_decode_apply": (BF, BF, I8, BF, BF),
}


def test_cell_leaves_are_the_programs():
    from repro.configs import get_config
    from repro.models import lm

    cfg = dataclasses.replace(get_config("granite-3-8b"), num_layers=3,
                              tie_embeddings=False)
    tree = jax.eval_shape(lambda: lm.lm_init(jax.random.PRNGKey(0), cfg))
    assert [x.shape for x in jax.tree.leaves(tree)] == CELL_LEAVES


def _step_bytes(p, dtypes):
    """VMEM bytes of one grid step's blocks, with each dtype's tile padding."""
    *lead, a, b = (1,) * (2 - len(p.block)) + p.block
    return tiling.block_bytes(math.prod(k or 1 for k in lead), a, b, dtypes)


@pytest.mark.parametrize("shape", CELL_LEAVES, ids=lambda s: "x".join(map(str, s)))
def test_cell_leaf_plan(shape):
    for dtypes in CELL_KERNELS.values():
        p = tiling.plan(shape, dtypes)
        assert not p.relayout and p.view == shape
        assert _step_bytes(p, dtypes) <= tiling.BLOCK_BYTES
        # the grid covers the leaf, with less than one block over
        assert p.grid == tuple(-(-n // (k or 1)) for n, k in zip(shape, p.block))
        # large blocks: the whole leaf, or at least half the budget
        assert (p.steps == 1 and p.block == shape
                or _step_bytes(p, dtypes) >= tiling.BLOCK_BYTES // 2)
        # the tiled pair keeps to the chip's (sublane, 128) tiles or is whole
        sub = max(32 // jnp.dtype(d).itemsize for d in dtypes)
        if len(shape) >= 2:
            assert p.block[-1] == shape[-1] or p.block[-1] % 128 == 0
            assert p.block[-2] == shape[-2] or p.block[-2] % sub == 0


@pytest.mark.parametrize("kernel", list(CELL_KERNELS))
def test_cell_tree_grid_steps(kernel):
    """Hundreds of grid steps a call over the whole cell, not the tens of
    thousands of (16|32, 1024) tiles."""
    steps = sum(tiling.plan(s, CELL_KERNELS[kernel]).steps for s in CELL_LEAVES)
    assert 12 <= steps < 2_000, steps
