"""Pallas TPU kernels fusing the commit-transport codec passes with the
PS commit apply (DESIGN.md §16).

The unfused commit hot path runs three elementwise HBM passes per leaf
per shard — codec encode, PS-side decode, commit apply — plus the
residual add the encode folds in. Each pass is memory-bound, so at model
scale the commit round pays 3–4 full HBM round trips for arithmetic one
pass could do. These kernels collapse them:

  push (worker side, one pass):
    * quantize_int8_ef: e ← u + r ; q ← clip(round(e/s)) ; r ← e − q·s
    * encode_bf16_ef:   e ← u + r ; q ← bf16(e) ; r ← e − f32(q)
      (the error-feedback add rides inside the quantize pass, so ``e``
      is never materialized in HBM; the per-leaf scale reduction stays a
      jnp amax the compiler fuses into the read)

  pull (PS side, one pass — decode + Eqn. 1 apply / plain average):
    * int8_decode_apply:  u ← q·s ; δ ← μ·δ − η·u ; W ← W + δ
    * bf16_decode_apply:  u ← f32(q) ; δ ← μ·δ − η·u ; W ← W + δ
    * int8_decode_accum:  u ← q·s ; W ← W − η·u
    * bf16_decode_accum:  u ← f32(q) ; W ← W − η·u

The in-kernel arithmetic mirrors the reference chain cast for cast
(decode to f32, cast like the params, delta in the commit-state dtype),
so the fused pull is bit-identical to decode → apply for f32 trees —
the contract tests/test_update_rules.py pins per codec and shard count.

Each call runs over one leaf in its own shape and layout, tiled by
``kernels.tiling`` (row bands in multiples of 32 sublanes where an
int8 payload takes part); the push side reads the update in its own dtype
and widens it in-register. The ops.py wrappers carry the per-leaf scale
/ hyper-params as (1, n) operands broadcast to every block, exactly like
``fused_commit`` / ``codec``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .tiling import stream

__all__ = [
    "quantize_int8_ef",
    "encode_bf16_ef",
    "int8_decode_apply",
    "bf16_decode_apply",
    "int8_decode_accum",
    "bf16_decode_accum",
]


# ---------------------------------------------------------------------------
# push side: error-feedback add fused into the encode pass
# ---------------------------------------------------------------------------

def _quantize_ef_kernel(u_ref, r_ref, s_ref, q_ref, ro_ref):
    scale = s_ref[0, 0]
    e = u_ref[...].astype(jnp.float32) + r_ref[...]
    q = jnp.clip(jnp.round(e / scale), -127.0, 127.0)
    q_ref[...] = q.astype(jnp.int8)
    ro_ref[...] = e - q * scale


def quantize_int8_ef(u: jax.Array, r: jax.Array, scale: jax.Array, *,
                     interpret: bool):
    """One leaf's update (any float dtype) + f32 residual → (int8
    payload, next residual) with the error-feedback add folded into the
    quantize pass."""
    return stream(_quantize_ef_kernel, (u, r), (scale,), (jnp.int8, jnp.float32),
                  interpret=interpret, updates=((1, 1),))


def _encode_bf16_ef_kernel(u_ref, r_ref, q_ref, ro_ref):
    e = u_ref[...].astype(jnp.float32) + r_ref[...]
    q = e.astype(jnp.bfloat16)
    q_ref[...] = q
    ro_ref[...] = e - q.astype(jnp.float32)


def encode_bf16_ef(u: jax.Array, r: jax.Array, *, interpret: bool):
    """One leaf's update + f32 residual → (bf16 payload, next residual)."""
    return stream(_encode_bf16_ef_kernel, (u, r), (), (jnp.bfloat16, jnp.float32),
                  interpret=interpret, updates=((1, 1),))


# ---------------------------------------------------------------------------
# pull side: decode fused with the commit apply
# ---------------------------------------------------------------------------

def _int8_apply_kernel(w_ref, d_ref, q_ref, s_ref, hp_ref, w_out, d_out):
    mu, lr = hp_ref[0, 0], hp_ref[0, 1]
    u = (q_ref[...].astype(jnp.float32) * s_ref[0, 0]).astype(w_ref.dtype)
    delta = (mu.astype(d_ref.dtype) * d_ref[...]
             - lr.astype(u.dtype) * u).astype(d_ref.dtype)
    d_out[...] = delta
    w_out[...] = w_ref[...] + delta


def int8_decode_apply(w, prev_delta, q, scale, hp, *, interpret: bool):
    """δ ← μ·δ − η·(q·s) ; W ← W + δ in one pass. ``hp`` is a (1, 2) f32
    [momentum, global_lr] operand; ``scale`` the per-leaf (1, 1) f32."""
    return stream(_int8_apply_kernel, (w, prev_delta, q), (scale, hp),
                  (w.dtype, prev_delta.dtype), interpret=interpret,
                  updates=((0, 0), (1, 1)))


def _bf16_apply_kernel(w_ref, d_ref, q_ref, hp_ref, w_out, d_out):
    mu, lr = hp_ref[0, 0], hp_ref[0, 1]
    u = q_ref[...].astype(jnp.float32).astype(w_ref.dtype)
    delta = (mu.astype(d_ref.dtype) * d_ref[...]
             - lr.astype(u.dtype) * u).astype(d_ref.dtype)
    d_out[...] = delta
    w_out[...] = w_ref[...] + delta


def bf16_decode_apply(w, prev_delta, q, hp, *, interpret: bool):
    """Same single pass with the bf16-payload decode (a widening cast)."""
    return stream(_bf16_apply_kernel, (w, prev_delta, q), (hp,),
                  (w.dtype, prev_delta.dtype), interpret=interpret,
                  updates=((0, 0), (1, 1)))


def _int8_accum_kernel(w_ref, q_ref, s_ref, hp_ref, w_out):
    lr = hp_ref[0, 0]
    u = (q_ref[...].astype(jnp.float32) * s_ref[0, 0]).astype(w_ref.dtype)
    w_out[...] = (w_ref[...] - lr.astype(u.dtype) * u).astype(w_ref.dtype)


def int8_decode_accum(w, q, scale, hp, *, interpret: bool):
    """Stateless plain-average pull: W ← W − η·(q·s) in one pass."""
    return stream(_int8_accum_kernel, (w, q), (scale, hp), (w.dtype,),
                  interpret=interpret, updates=((0, 0),))[0]


def _bf16_accum_kernel(w_ref, q_ref, hp_ref, w_out):
    lr = hp_ref[0, 0]
    u = q_ref[...].astype(jnp.float32).astype(w_ref.dtype)
    w_out[...] = (w_ref[...] - lr.astype(u.dtype) * u).astype(w_ref.dtype)


def bf16_decode_accum(w, q, hp, *, interpret: bool):
    """Stateless plain-average pull for bf16 payloads."""
    return stream(_bf16_accum_kernel, (w, q), (hp,), (w.dtype,),
                  interpret=interpret, updates=((0, 0),))[0]
