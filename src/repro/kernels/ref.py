"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each function is the mathematically-direct implementation the kernels in
this package must match (assert_allclose in tests/test_kernels.py, with
hypothesis sweeps over shapes/dtypes).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "fused_accumulate",
    "fused_ps_apply",
    "quantize_int8",
    "dequantize_int8",
    "encode_bf16",
    "quantize_int8_ef",
    "encode_bf16_ef",
    "int8_decode_apply",
    "bf16_decode_apply",
    "int8_decode_accum",
    "bf16_decode_accum",
    "flash_attention",
    "rglru_scan",
    "rwkv6_scan",
]


# ---------------------------------------------------------------------------
# ADSP commit ops (the paper's hot loop: Alg. 2 lines 7 and PS line 4)
# ---------------------------------------------------------------------------

def fused_accumulate(u: jax.Array, g: jax.Array, local_lr: float) -> jax.Array:
    """U ← U + η′ · g   (worker-side accumulative update), in ``u``'s dtype."""
    dt = u.dtype
    return u + jnp.asarray(local_lr, jnp.float32).astype(dt) * g.astype(dt)


def fused_ps_apply(
    w: jax.Array,
    prev_delta: jax.Array,
    u: jax.Array,
    global_lr: float,
    momentum: float,
) -> tuple[jax.Array, jax.Array]:
    """PS update with explicit momentum (Eqn. 1, μ possibly reduced by the
    implicit-momentum correction): δ ← μ·δ_prev − η·U ; W ← W + δ, all
    in ``w``'s dtype."""
    dt = w.dtype
    mu, lr = (jnp.asarray(x, jnp.float32).astype(dt) for x in (momentum, global_lr))
    delta = mu * prev_delta.astype(dt) - lr * u.astype(dt)
    return w + delta, delta


# ---------------------------------------------------------------------------
# Codec passes (DESIGN.md §10): the per-leaf encode / decode kernels
# ---------------------------------------------------------------------------

def quantize_int8(x, scale):
    """Symmetric int8 quantize of e = f32(x) and its residual e − q·s."""
    e = x.astype(jnp.float32)
    q = jnp.clip(jnp.round(e / scale), -127.0, 127.0)
    return q.astype(jnp.int8), e - q * scale


def dequantize_int8(q, scale):
    """PS-side int8 decode: q·s as f32."""
    return q.astype(jnp.float32) * scale


def encode_bf16(x):
    """bf16 payload of e = f32(x) and its residual e − f32(q)."""
    e = x.astype(jnp.float32)
    q = e.astype(jnp.bfloat16)
    return q, e - q.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Fused codec+commit passes (DESIGN.md §16) — the decode/apply chain each
# single-pass kernel in fused_codec_commit.py must reproduce bit for bit
# ---------------------------------------------------------------------------

def quantize_int8_ef(u, r, scale):
    """Error-feedback int8 encode: e = u + r, symmetric quantize, next
    residual — the reference chain of add → quantize in one expression."""
    e = u.astype(jnp.float32) + r
    q = jnp.clip(jnp.round(e / scale), -127.0, 127.0).astype(jnp.int8)
    return q, e - q.astype(jnp.float32) * scale


def encode_bf16_ef(u, r):
    """Error-feedback bf16 encode: e = u + r cast and residualized."""
    e = u.astype(jnp.float32) + r
    q = e.astype(jnp.bfloat16)
    return q, e - q.astype(jnp.float32)


def int8_decode_apply(w, prev_delta, q, scale, global_lr, momentum):
    """Dequantize + Eqn. 1 PS apply: exactly decode(q)·cast-like-params
    followed by ``fused_ps_apply`` — the unfused chain the kernel fuses."""
    u = (q.astype(jnp.float32) * scale).astype(w.dtype)
    delta = (momentum * prev_delta - global_lr * u).astype(prev_delta.dtype)
    return w + delta, delta


def bf16_decode_apply(w, prev_delta, q, global_lr, momentum):
    """Widening bf16 decode + Eqn. 1 PS apply (unfused chain)."""
    u = q.astype(jnp.float32).astype(w.dtype)
    delta = (momentum * prev_delta - global_lr * u).astype(prev_delta.dtype)
    return w + delta, delta


def int8_decode_accum(w, q, scale, global_lr):
    """Dequantize + stateless plain-average pull (unfused chain)."""
    u = (q.astype(jnp.float32) * scale).astype(w.dtype)
    return (w - global_lr * u).astype(w.dtype)


def bf16_decode_accum(w, q, global_lr):
    """bf16 decode + stateless plain-average pull (unfused chain)."""
    u = q.astype(jnp.float32).astype(w.dtype)
    return (w - global_lr * u).astype(w.dtype)


# ---------------------------------------------------------------------------
# Flash attention (GQA, causal, optional sliding window)
# ---------------------------------------------------------------------------

def flash_attention(
    q: jax.Array,  # (B, S, Hq, D)
    k: jax.Array,  # (B, S, Hkv, D)
    v: jax.Array,  # (B, S, Hkv, D)
    *,
    causal: bool = True,
    window: int = 0,
) -> jax.Array:
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, s, hkv, g, d)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32)
    logits = logits / np.sqrt(d)
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(s)[None, :]
    mask = jnp.ones((s, s), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= qpos - kpos < window
    logits = jnp.where(mask, logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    ctx = jnp.einsum("bhgqk,bkhd->bqhgd", w.astype(v.dtype), v)
    return ctx.reshape(b, s, hq, d)


# ---------------------------------------------------------------------------
# RG-LRU linear recurrence
# ---------------------------------------------------------------------------

def rglru_scan(a: jax.Array, b: jax.Array, h0: jax.Array | None = None) -> jax.Array:
    """h_t = a_t ⊙ h_{t−1} + b_t, over axis 1. a, b: (B, S, W) float32."""
    bsz, s, w = a.shape
    h = h0 if h0 is not None else jnp.zeros((bsz, w), a.dtype)

    def step(h, ab):
        at, bt = ab
        h = at * h + bt
        return h, h

    _, hs = jax.lax.scan(step, h, (jnp.moveaxis(a, 1, 0), jnp.moveaxis(b, 1, 0)))
    return jnp.moveaxis(hs, 0, 1)


# ---------------------------------------------------------------------------
# RWKV6 WKV recurrence
# ---------------------------------------------------------------------------

def rwkv6_scan(
    r: jax.Array,  # (B, S, H, N)
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,  # decay ∈ (0,1), float32
    bonus: jax.Array,  # (H, N)
    state0: jax.Array | None = None,  # (B, H, N, N)
) -> tuple[jax.Array, jax.Array]:
    b, s, h, n = r.shape
    st = state0 if state0 is not None else jnp.zeros((b, h, n, n), jnp.float32)

    def step(st, xs):
        rt, kt, vt, wt = xs
        kv = kt[..., :, None] * vt[..., None, :]
        out = jnp.einsum("bhn,bhnm->bhm", rt, st + bonus[None, :, :, None] * kv)
        st = wt[..., :, None] * st + kv
        return st, out

    xs = tuple(jnp.moveaxis(t.astype(jnp.float32), 1, 0) for t in (r, k, v, w))
    stT, outs = jax.lax.scan(step, st, xs)
    return jnp.moveaxis(outs, 0, 1), stT
