"""Blockwise (flash) attention Pallas TPU kernel — GQA, causal, optional
sliding window.

Grid: (B, Hq, S/block_q, S/block_k) with the kv-block axis LAST, i.e.
innermost-sequential on TPU. The online-softmax running state
(max m, denom l, accumulator acc) lives in VMEM scratch and is carried
across the kv-block grid steps of the same (b, h, q-block) program family;
the output block is written on the final kv step. This is the canonical
TPU flash pattern: every operand block is a proper VMEM tile —
(block_q, D) for q/out and (block_k, D) for k/v — so the working set is
~(2·block_q + 2·block_k)·D·4 B ≈ 1 MiB at 512/512/128, independent of S.

GQA is expressed in the BlockSpec index maps: kv operands for q-head h
index kv-head h // (Hq/Hkv) — no host-side head replication, no extra HBM.

Masking is positional (causal and/or sliding window). Fully-masked kv
blocks are skipped with pl.when — the block fetch still happens (grid is
static) but the MXU work is elided; the ops.py wrapper additionally trims
whole diagonals when causal by choosing block_k = block_q.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention"]

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 block_q, block_k, n_kv, causal, window, scale):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # block-level skip test (static shapes, dynamic ids)
    q_lo = qi * block_q
    q_hi = q_lo + block_q - 1
    k_lo = kj * block_k
    k_hi = k_lo + block_k - 1
    live = jnp.asarray(True)
    if causal:
        live &= k_lo <= q_hi
    if window:
        live &= k_hi > q_lo - window

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (block_q, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (block_k, D)
        v = v_ref[0, 0].astype(jnp.float32)
        logits = q @ k.T
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones_like(logits, dtype=jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= qpos - kpos < window
        logits = jnp.where(mask, logits, NEG_INF)
        m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
        m_new = jnp.maximum(m_prev, logits.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
        m_ref[...] = m_new
        l_ref[...] = l_new
        acc_ref[...] = acc_prev * alpha + p @ v

    @pl.when(kj == n_kv - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 512, block_k: int = 512,
                    interpret: bool):
    """q: (B, S, Hq, D); k, v: (B, S, Hkv, D) → (B, S, Hq, D).

    S must be a multiple of the block sizes (ops.py pads + re-masks)."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    n_kv = s // block_k

    qt = jnp.moveaxis(q, 2, 1)  # (B, Hq, S, D)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)

    kernel = functools.partial(
        _attn_kernel, block_q=block_q, block_k=block_k, n_kv=n_kv,
        causal=causal, window=window, scale=1.0 / np.sqrt(d),
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, hq, s, d), q.dtype),
        grid=(b, hq, s // block_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, h, qi, kj: (bi, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bi, h, qi, kj: (bi, h // group, kj, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bi, h, qi, kj: (bi, h // group, kj, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), lambda bi, h, qi, kj: (bi, h, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return jnp.moveaxis(out, 1, 2)
