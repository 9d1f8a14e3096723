"""Plain reference of a dense GQA decoder (the granite-3.0 family) as the
configuration file states it: pre-norm RMSNorm blocks of grouped-query
attention with rotary positions (half-split rotation) and a SwiGLU MLP,
a final RMSNorm and an output head, tied to the embedding where the
configuration ties them. Float32, every matmul at
``highest``, full (seq × seq) causal attention, layer by layer.

It imports nothing of the program. It also makes the weights that both
sides run, from the seed, in the layout the program reads: one tree,
made on the device in one jitted call, in the configuration's dtype.

Departures from the published model are the program's, and the
configuration file lists them: RMSNorm scales stored as γ − 1, no
embedding, residual, attention or logit multipliers (attention scaled by
1/√head_dim), and a softmax over the head's padded rows.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .precision import einsum_fn

__all__ = ["init_params", "loss"]


def _sizes(m: dict):
    hd = m.get("head_dim") or m["d_model"] // m["num_heads"]
    v = (m["vocab_size"] + 255) // 256 * 256
    return m["d_model"], m["num_heads"], m["num_kv_heads"], hd, m["d_ff"], m["num_layers"], v


def init_params(key, m: dict):
    """Truncated-normal weights (±2σ), σ = 1/√fan_in, RMSNorm scales at
    1, in ``m['dtype']``. The embedding's σ is 1, or 1/√d_model where it
    is also the output head (its fan-in there)."""
    d, hq, hkv, hd, f, L, v = _sizes(m)
    dt = jnp.dtype(m["dtype"])
    tied = m["tie_embeddings"]

    def make(key):
        ks = iter(jax.random.split(key, 9))

        def w(shape, fan_in):
            x = jax.random.truncated_normal(next(ks), -2.0, 2.0, shape, jnp.float32)
            return (x * fan_in ** -0.5).astype(dt)

        zeros = lambda *s: jnp.zeros(s, dt)  # noqa: E731  (stored as γ − 1)
        tree = {
            "embed": w((v, d), d if tied else 1),
            "final_norm": {"gamma": zeros(d)},
            "group0": {"0_global": {
                "norm1": {"gamma": zeros(L, d)},
                "attn": {"wq": w((L, d, hq, hd), d), "wk": w((L, d, hkv, hd), d),
                         "wv": w((L, d, hkv, hd), d), "wo": w((L, hq * hd, d), hq * hd)},
                "norm2": {"gamma": zeros(L, d)},
                "mlp": {"wi": w((L, d, f), d), "wg": w((L, d, f), d), "wo": w((L, f, d), f)},
            }},
        }
        if not tied:
            tree["unembed"] = w((d, v), d)
        return tree

    return jax.jit(make)(key)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + g)


def _rope(x, theta):
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def loss(params, tokens, m: dict, mode: str = "f32", positions: int | None = None):
    """Mean next-token cross entropy over the first ``positions``
    predictions of each row (default all seq − 1); params in any float
    dtype, computed in float32 (or fp8 products in ``mode='fp8'``)."""
    d, hq, hkv, hd, f, L, v = _sizes(m)
    es = einsum_fn(mode)
    eps, theta = m["norm_eps"], m["rope_theta"]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    b, s = tokens.shape
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, p):
        p = jax.tree.map(f32, p)
        h = _rms(x, p["norm1"]["gamma"], eps)
        q = _rope(es("bsd,dhk->bshk", h, p["attn"]["wq"]), theta)
        k = _rope(es("bsd,dhk->bshk", h, p["attn"]["wk"]), theta)
        vv = es("bsd,dhk->bshk", h, p["attn"]["wv"])
        k, vv = (jnp.repeat(t, hq // hkv, axis=2) for t in (k, vv))
        sc = es("bqhk,bshk->bhqs", q, k) / jnp.sqrt(jnp.float32(hd))
        w = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        ctx = es("bhqs,bshk->bqhk", w, vv).reshape(b, s, hq * hd)
        x = x + es("bsf,fd->bsd", ctx, p["attn"]["wo"])
        h = _rms(x, p["norm2"]["gamma"], eps)
        g = jax.nn.silu(es("bsd,df->bsf", h, p["mlp"]["wi"])) * es("bsd,df->bsf", h, p["mlp"]["wg"])
        return x + es("bsf,fd->bsd", g, p["mlp"]["wo"]), None

    x = f32(params["embed"])[tokens]
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["group0"]["0_global"])
    x = _rms(x, f32(params["final_norm"]["gamma"]), eps)
    if m["tie_embeddings"]:
        logits = es("bsd,vd->bsv", x, f32(params["embed"]))
    else:
        logits = es("bsd,dv->bsv", x, f32(params["unembed"]))
    logp = jax.nn.log_softmax(logits, -1)
    n = s - 1 if positions is None else positions
    nll = -jnp.take_along_axis(logp[:, :n], tokens[:, 1:n + 1, None], -1)[..., 0]
    return jnp.mean(nll)
