"""jit'd public wrappers around the Pallas kernels.

Handles padding to block multiples for attention and the scans, pytree
dispatch for the commit ops (each kernel runs over one leaf as it lies,
``kernels.tiling``), and the interpret-mode switch. ``_interp`` is the one
place it is resolved: ``interpret=None`` (the default) means interpret
mode exactly when no TPU backend is present, so the same call sites run
in the CPU container (validation) and natively on the chip; asking for
interpret mode on a TPU is an error, never a silent slow path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import codec as _cd
from . import flash_attention as _fa
from . import fused_codec_commit as _fcc
from . import fused_commit as _fc
from . import rglru_scan as _rg
from . import rwkv6_scan as _rw

__all__ = [
    "flash_attention",
    "rglru_scan",
    "rwkv6_scan",
    "accumulate_tree",
    "ps_apply_tree",
    "quantize_int8",
    "dequantize_int8",
    "encode_bf16",
    "quantize_int8_ef",
    "encode_bf16_ef",
    "int8_decode_apply",
    "bf16_decode_apply",
    "int8_decode_accum",
    "bf16_decode_accum",
    "default_interpret",
]


@functools.lru_cache(maxsize=None)
def default_interpret() -> bool:
    """Interpret-mode default for every Pallas wrapper (and the rule
    registry in ``repro.ps``): interpret unless a TPU backend is present.
    Cached — the backend probe runs once per process, not once per
    wrapper trace."""
    return jax.default_backend() != "tpu"


def _interp(interpret):
    if interpret is None:
        return default_interpret()
    if interpret and jax.default_backend() == "tpu":
        raise ValueError(
            "Pallas interpret mode was requested on a TPU backend; the "
            "kernels compile natively there (pass interpret=None)")
    return interpret


def _pad_to(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, block_q=512,
                    block_k=512, interpret=None):
    """(B, S, Hq, D) GQA flash attention; pads S to a block multiple.

    Padding queries attend only to padding keys (causal mask handles the
    real→pad direction; pad-query outputs are sliced off).

    Differentiable: the Pallas call carries no autodiff rule, so the
    backward recomputes through the reference attention (custom_vjp) —
    the train path can use the kernel forward today; a fused backward
    kernel is future work."""
    return _fa_vjp(q, k, v, causal, window, block_q, block_k,
                   _interp(interpret))


def _fa_primal(q, k, v, causal, window, block_q, block_k, interpret):
    s = q.shape[1]
    bq = min(block_q, max(s, 16))
    bk = min(block_k, max(s, 16))
    mult = max(bq, bk)
    qp, _ = _pad_to(q, 1, mult)
    kp, _ = _pad_to(k, 1, mult)
    vp, _ = _pad_to(v, 1, mult)
    out = _fa.flash_attention(
        qp, kp, vp, causal=causal, window=window,
        block_q=bq, block_k=bk, interpret=interpret,
    )
    return out[:, :s]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _fa_vjp(q, k, v, causal, window, block_q, block_k, interpret):
    return _fa_primal(q, k, v, causal, window, block_q, block_k, interpret)


def _fa_fwd(q, k, v, causal, window, block_q, block_k, interpret):
    return (_fa_primal(q, k, v, causal, window, block_q, block_k, interpret),
            (q, k, v))


def _fa_bwd(causal, window, block_q, block_k, interpret, res, g):
    from . import ref as _ref  # lazy: ref is the autodiff twin, not a dep

    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _ref.flash_attention(
            q_, k_, v_, causal=causal, window=window), q, k, v)
    return vjp(g)


_fa_vjp.defvjp(_fa_fwd, _fa_bwd)


# ---------------------------------------------------------------------------
# recurrences
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("block_w", "block_s", "interpret"))
def rglru_scan(a, b, *, block_w=1024, block_s=256, interpret=None):
    """(B, S, W) h_t = a_t h_{t−1} + b_t; pads W (neutral) and S (a=1, b=0)."""
    bsz, s, w = a.shape
    bw = min(block_w, w)
    bs = min(block_s, s)
    ap, padw = _pad_to(a, 2, bw)
    bp, _ = _pad_to(b, 2, bw)
    # pad time with identity steps (a=1, b=0) — state preserved
    padt = (-s) % bs
    if padt:
        ap = jnp.concatenate([ap, jnp.ones((bsz, padt, ap.shape[2]), ap.dtype)], axis=1)
        bp = jnp.concatenate([bp, jnp.zeros((bsz, padt, bp.shape[2]), bp.dtype)], axis=1)
    h = _rg.rglru_scan(ap, bp, block_w=bw, block_s=bs, interpret=_interp(interpret))
    return h[:, :s, :w]


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def rwkv6_scan(r, k, v, w, bonus, *, block_s=256, interpret=None):
    """(B, S, H, N) WKV recurrence → (out, final_state (B, H, N, N))."""
    b, s, h, n = r.shape
    bs = min(block_s, s)
    padt = (-s) % bs
    if padt:
        zeros = jnp.zeros((b, padt, h, n), r.dtype)
        ones = jnp.ones((b, padt, h, n), jnp.float32)
        r = jnp.concatenate([r, zeros], axis=1)
        k = jnp.concatenate([k, zeros], axis=1)  # k=0 ⇒ kv=0 ⇒ state kept
        v = jnp.concatenate([v, zeros], axis=1)
        w = jnp.concatenate([w, ones], axis=1)  # w=1 ⇒ no decay
    out, st = _rw.rwkv6_scan(r, k, v, w, bonus, block_s=bs, interpret=_interp(interpret))
    return out[:, :s], st


# ---------------------------------------------------------------------------
# ADSP commit ops over parameter pytrees
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("interpret",))
def accumulate_tree(u, g, local_lr, *, interpret=None):
    """U ← U + η′·g leaf-wise via the fused Pallas kernel, one call per
    leaf over the leaf as it lies (``kernels.tiling``)."""
    interp = _interp(interpret)
    return jax.tree.map(
        lambda ul, gl: _fc.accumulate(ul, gl, local_lr, interpret=interp), u, g)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ps_apply_tree(w, prev_delta, u, global_lr, momentum, *, interpret=None):
    """W ← W + (μ·δ − η·U); returns (new_w, new_delta) pytrees."""
    interp = _interp(interpret)
    pairs = jax.tree.map(
        lambda wl, dl, ul: _fc.ps_apply(wl, dl, ul, global_lr, momentum,
                                        interpret=interp),
        w, prev_delta, u)
    new_w = jax.tree.map(lambda p: p[0], pairs, is_leaf=lambda x: isinstance(x, tuple))
    new_d = jax.tree.map(lambda p: p[1], pairs, is_leaf=lambda x: isinstance(x, tuple))
    return new_w, new_d


# ---------------------------------------------------------------------------
# transport codec passes (per-array; pytree dispatch lives in repro.transport)
# ---------------------------------------------------------------------------

def _s11(x):
    return jnp.full((1, 1), x, jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize_int8(x, scale, *, interpret=None):
    """Symmetric int8 quantization of one array with a given positive
    scalar ``scale``: returns (q int8, error-feedback residual f32), both
    shaped like ``x``, out of a single fused HBM pass."""
    return _cd.quantize_int8(x, _s11(scale), interpret=_interp(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def dequantize_int8(q, scale, *, interpret=None):
    """PS-side decode of an int8 payload: q·scale as f32."""
    return _cd.dequantize_int8(q, _s11(scale), interpret=_interp(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def encode_bf16(x, *, interpret=None):
    """bf16 cast of one array: (q bf16, residual f32) in a single pass."""
    return _cd.encode_bf16(x, interpret=_interp(interpret))


# ---------------------------------------------------------------------------
# fused codec+commit passes (DESIGN.md §16): push-side encode with the
# error-feedback add folded in; pull-side decode fused with the PS apply
# ---------------------------------------------------------------------------

def _hp2(momentum, global_lr):
    return jnp.stack([
        jnp.asarray(momentum, jnp.float32),
        jnp.asarray(global_lr, jnp.float32),
    ]).reshape(1, 2)


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize_int8_ef(u, r, scale, *, interpret=None):
    """Error-feedback int8 encode of one array in a single pass:
    e = u + r is formed in-register (never written to HBM), quantized
    with the given positive scalar ``scale``, and the next residual
    e − q·scale comes out of the same pass. ``u`` is read in its own
    dtype and widened to f32 in-register."""
    return _fcc.quantize_int8_ef(u, r, _s11(scale), interpret=_interp(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def encode_bf16_ef(u, r, *, interpret=None):
    """Error-feedback bf16 encode: e = u + r cast and residualized in one
    pass, without materializing e."""
    return _fcc.encode_bf16_ef(u, r, interpret=_interp(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def int8_decode_apply(w, prev_delta, q, scale, global_lr, momentum, *,
                      interpret=None):
    """Fused PS pull for an int8 payload: dequantize + Eqn. 1 apply in
    one pass. Returns (new_w, new_delta); arithmetic mirrors the
    reference decode → momentum_delta chain cast for cast."""
    return _fcc.int8_decode_apply(w, prev_delta, q, _s11(scale),
                                  _hp2(momentum, global_lr),
                                  interpret=_interp(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def bf16_decode_apply(w, prev_delta, q, global_lr, momentum, *, interpret=None):
    """Fused PS pull for a bf16 payload: widening cast + Eqn. 1 apply."""
    return _fcc.bf16_decode_apply(w, prev_delta, q, _hp2(momentum, global_lr),
                                  interpret=_interp(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def int8_decode_accum(w, q, scale, global_lr, *, interpret=None):
    """Fused stateless pull (plain average) for an int8 payload:
    W ← W − η·(q·s) in one pass."""
    return _fcc.int8_decode_accum(w, q, _s11(scale), _s11(global_lr),
                                  interpret=_interp(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def bf16_decode_accum(w, q, global_lr, *, interpret=None):
    """Fused stateless pull (plain average) for a bf16 payload."""
    return _fcc.bf16_decode_accum(w, q, _s11(global_lr),
                                  interpret=_interp(interpret))
