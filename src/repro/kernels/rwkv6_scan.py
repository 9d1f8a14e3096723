"""RWKV6 WKV-recurrence Pallas TPU kernel (chunked over time).

Per (batch, head) program family, carrying the (N, N) state matrix in VMEM
scratch across time chunks (grid axis LAST = sequential):

    S ← diag(w_t)·S + k_tᵀ v_t
    o_t = r_t · (S_prev + u ⊙ k_tᵀ v_t)

N = 64 for all assigned RWKV configs, so the state is 64×64×4 B = 16 KiB —
comfortably VMEM-resident; r/k/v/w stream through in (block_s, N) tiles.

TPU adaptation (DESIGN.md): CUDA RWKV kernels assign one thread per
channel and keep state in registers/shared memory with warp-level
parallelism over heads. The TPU analogue is this grid-parallel (B, H)
decomposition with the state as a VMEM-resident matrix and the per-token
outer products k_tᵀv_t / row-gathers r_t·S expressed as (N, N) VPU ops —
sequential in t, vectorized in the state plane.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["rwkv6_scan"]


def _column(row, eye):
    """(1, N) row → (N, 1) column as a masked lane reduction (a small
    transpose the VPU/XLU lowers for any N)."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, stout_ref, st_ref,
                *, block_s, n_s):
    hi = pl.program_id(1)
    sj = pl.program_id(2)

    @pl.when(sj == 0)
    def _init():
        st_ref[...] = jnp.zeros_like(st_ref)

    n = st_ref.shape[0]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    u_col = _column(u_ref[pl.ds(hi, 1), :], eye)  # (N, 1) bonus, key axis

    # per-token rows come through dynamic ref slices (Mosaic has no
    # lowering for a dynamic index on a loaded value); the key-axis
    # operands become (N, 1) columns so every update is an (N, N) VPU op
    def step(t, st):  # st: (N, N), key axis × value axis
        row = pl.ds(t, 1)
        k_col = _column(k_ref[0, 0, row, :], eye)
        r_col = _column(r_ref[0, 0, row, :], eye)
        w_col = _column(w_ref[0, 0, row, :], eye)
        kv = k_col * v_ref[0, 0, row, :]  # (N, N) outer product k_tᵀ v_t
        o_ref[0, 0, row, :] = jnp.sum(r_col * (st + u_col * kv), axis=0,
                                      keepdims=True)
        return w_col * st + kv

    st_ref[...] = jax.lax.fori_loop(0, block_s, step, st_ref[...])

    @pl.when(sj == n_s - 1)
    def _emit_state():
        stout_ref[0, 0] = st_ref[...]


def rwkv6_scan(r, k, v, w, bonus, *, block_s: int = 256, interpret: bool):
    """r,k,v,w: (B, S, H, N) (w float32 decay); bonus: (H, N).

    Returns (out (B, S, H, N) float32, final_state (B, H, N, N) float32).
    S % block_s == 0 (ops.py pads with w=1, k=0 ⇒ state-preserving no-ops).
    """
    b, s, h, n = r.shape
    block_s = min(block_s, s)
    assert s % block_s == 0
    n_s = s // block_s

    # layout (B, H, S, N): head becomes a grid axis
    rt, kt, vt, wt = (
        jnp.moveaxis(t.astype(jnp.float32), 2, 1) for t in (r, k, v, w)
    )
    kernel = functools.partial(_wkv_kernel, block_s=block_s, n_s=n_s)
    out, st = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((b, h, s, n), jnp.float32),
            jax.ShapeDtypeStruct((b, h, n, n), jnp.float32),
        ),
        grid=(b, h, n_s),
        in_specs=[
            pl.BlockSpec((1, 1, block_s, n), lambda bi, hi, sj: (bi, hi, sj, 0)),
            pl.BlockSpec((1, 1, block_s, n), lambda bi, hi, sj: (bi, hi, sj, 0)),
            pl.BlockSpec((1, 1, block_s, n), lambda bi, hi, sj: (bi, hi, sj, 0)),
            pl.BlockSpec((1, 1, block_s, n), lambda bi, hi, sj: (bi, hi, sj, 0)),
            pl.BlockSpec((h, n), lambda bi, hi, sj: (0, 0)),  # whole bonus
        ],
        out_specs=(
            pl.BlockSpec((1, 1, block_s, n), lambda bi, hi, sj: (bi, hi, sj, 0)),
            pl.BlockSpec((1, 1, n, n), lambda bi, hi, sj: (bi, hi, 0, 0)),
        ),
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        interpret=interpret,
    )(rt, kt, vt, wt, bonus.astype(jnp.float32))
    return jnp.moveaxis(out, 1, 2), st
