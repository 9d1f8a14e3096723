"""Shared building blocks for the architecture zoo (pure-JAX, pytree params).

Initialization mirrors common practice (truncated-normal fan-in scaling);
weights are created in float32 and cast to the config dtype at use time so
checkpoints stay full-precision while compute runs in bf16 on TPU.

``annotate`` applies logical-axis sharding constraints resolved through a
rules table (MaxText-style). Rules may only reference *auto* mesh axes —
inside the ADSP shard_map, worker axes are manual and must not appear.
"""

from __future__ import annotations

from typing import Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

__all__ = [
    "default_rules",
    "annotate",
    "dense_init",
    "rmsnorm",
    "layernorm",
    "mlp_init",
    "mlp_apply",
    "rope",
    "dtype_of",
]


# Logical axis names used throughout the zoo.
def default_rules(model_axis: str = "model", data_axis: str | None = None) -> dict:
    """logical-axis → mesh-axis (or None). data_axis is only set for
    adsp_granularity 'pod'/'accum' where the batch dim is GSPMD-visible."""
    return {
        "batch": data_axis,
        "seq": None,
        "embed": None,
        "heads": model_axis,
        "kv_heads": model_axis,
        "qkv": model_axis,
        "mlp": model_axis,
        "vocab": model_axis,
        "experts": model_axis,
        "lru": model_axis,
    }


def annotate(x: jax.Array, logical: Sequence[str | None], rules: Mapping) -> jax.Array:
    """with_sharding_constraint by logical axes; divisibility-guarded.

    Without an ambient mesh (plain CPU tests, single-device serving) there
    is nothing to constrain against and ``x`` passes through; under one,
    a constraint the mesh rejects raises."""
    if not rules:
        return x
    sizes = _ambient_mesh_axes()
    if not sizes:
        return x
    spec = []
    for dim, name in zip(x.shape, logical):
        axis = rules.get(name) if name else None
        spec.append(axis if axis and dim % _axis_size(axis, sizes) == 0 else None)
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))


def _ambient_mesh_axes() -> dict[str, int]:
    """Axis name -> size of the ambient mesh; {} when none is set."""
    m = jax.sharding.get_abstract_mesh()
    if m is None or m.empty:
        return {}
    return dict(zip(m.axis_names, m.axis_sizes))


def _axis_size(axis, sizes: Mapping[str, int]) -> int:
    names = axis if isinstance(axis, tuple) else (axis,)
    n = 1
    for a in names:
        n *= sizes.get(a, 1 << 30)  # absent axis: never divisible
    return n


def dtype_of(cfg) -> jnp.dtype:
    return jnp.dtype(cfg.dtype)


def dense_init(rng, fan_in: int, *out_dims: int, scale: float | None = None):
    """(fan_in, *out_dims) truncated-normal fan-in init, float32."""
    scale = scale if scale is not None else 1.0 / np.sqrt(fan_in)
    shape = (fan_in, *out_dims)
    return (jax.random.truncated_normal(rng, -2.0, 2.0, shape, jnp.float32) * scale)


def rmsnorm(x: jax.Array, gamma: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + gamma.astype(jnp.float32))).astype(dt)


def layernorm(x: jax.Array, gamma: jax.Array, beta: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * gamma.astype(jnp.float32) + beta.astype(jnp.float32)).astype(dt)


def norm_init(cfg, d: int):
    if cfg.norm_variant == "layernorm":
        return {"gamma": jnp.ones((d,), jnp.float32), "beta": jnp.zeros((d,), jnp.float32)}
    return {"gamma": jnp.zeros((d,), jnp.float32)}  # rmsnorm stores γ−1


def norm_apply(cfg, p, x):
    if cfg.norm_variant == "layernorm":
        return layernorm(x, p["gamma"], p["beta"], cfg.norm_eps)
    return rmsnorm(x, p["gamma"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GELU)
# ---------------------------------------------------------------------------

def mlp_init(rng, d_model: int, d_ff: int, variant: str):
    ks = jax.random.split(rng, 3)
    if variant == "swiglu":
        return {
            "wi": dense_init(ks[0], d_model, d_ff),
            "wg": dense_init(ks[1], d_model, d_ff),
            "wo": dense_init(ks[2], d_ff, d_model),
        }
    return {
        "wi": dense_init(ks[0], d_model, d_ff),
        "wo": dense_init(ks[2], d_ff, d_model),
    }


def mlp_apply(p, x, variant: str, rules) -> jax.Array:
    dt = x.dtype
    if variant == "swiglu":
        h = jax.nn.silu(x @ p["wi"].astype(dt)) * (x @ p["wg"].astype(dt))
    else:
        h = jax.nn.gelu(x @ p["wi"].astype(dt))
    h = annotate(h, ("batch", "seq", "mlp"), rules)
    return h @ p["wo"].astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq) int32."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, half)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)
