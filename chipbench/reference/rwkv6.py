"""Plain reference of RWKV-6 "Finch" (arXiv:2404.05892) as the
configuration file states it. Float32, every matmul at ``highest``, the
WKV recurrence run token by token:

    S_t = diag(w_t)·S_{t−1} + k_tᵀ v_t,   o_t = r_t·(S_{t−1} + u ⊙ k_tᵀ v_t)

with r, k, v, g and the decay w from token-shift interpolation, a
per-head group norm on o, and the squared-ReLU channel mix.

It imports nothing of the program. It also makes the weights that both
sides run, from the seed, in the layout the program reads: one tree,
made on the device in one jitted call, in the configuration's dtype.

Departures from the published model are the program's, and the
configuration file lists them: RMSNorm (scale stored as γ − 1) in place
of LayerNorm and no norm after the embedding; one rank-32 projection of
the normed input for all five interpolations and one for the decay
(published: ranks 32 and 64, the mixes applied to the shifted input);
k scaled by 1/√N; group-norm epsilon 1e-5.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .precision import einsum_fn

__all__ = ["init_params", "logits_at"]

LORA = 32


def init_params(key, m: dict):
    """Weights as the program's initialiser draws them: truncated normal
    (±2σ), σ = 1/√fan_in (the embedding 1, the low-rank mixes and the
    channel-mix receptance 0.02), interpolation bases 0.5, decay bases
    spread from −7 to −4.5 over the channels, bonus N(0, 0.02²), in
    ``m['dtype']``."""
    d, f, n, L = m["d_model"], m["d_ff"], m["rwkv_head_dim"], m["num_layers"]
    v = (m["vocab_size"] + 255) // 256 * 256
    h = d // n
    dt = jnp.dtype(m["dtype"])

    def make(key):
        ks = iter(jax.random.split(key, 16))

        def w(shape, scale):
            x = jax.random.truncated_normal(next(ks), -2.0, 2.0, shape, jnp.float32)
            return (x * scale).astype(dt)

        def full(shape, value):
            return jnp.full(shape, value, dt)

        sd, sf = d ** -0.5, f ** -0.5
        return {
            "embed": w((v, d), 1.0),
            "final_norm": {"gamma": full((d,), 0.0)},
            "unembed": w((d, v), sd),
            "group0": {"0_rwkv": {
                "norm1": {"gamma": full((L, d), 0.0)},
                "time": {
                    "mix_base": full((L, 5, d), 0.5),
                    "mix_lora_a": w((L, d, LORA), 0.02),
                    "mix_lora_b": w((L, LORA, 5 * d), 0.02),
                    "wr": w((L, d, d), sd), "wk": w((L, d, d), sd),
                    "wv": w((L, d, d), sd), "wg": w((L, d, d), sd),
                    "wo": w((L, d, d), sd),
                    "decay_base": jnp.broadcast_to(
                        jnp.linspace(-7.0, -4.5, d, dtype=jnp.float32), (L, d)).astype(dt),
                    "decay_lora_a": w((L, d, LORA), 0.02),
                    "decay_lora_b": w((L, LORA, d), 0.02),
                    "bonus": (jax.random.normal(next(ks), (L, h, n), jnp.float32)
                              * 0.02).astype(dt),
                    "ln_gamma": full((L, d), 1.0),
                },
                "norm2": {"gamma": full((L, d), 0.0)},
                "chan": {
                    "mix_k": full((L, d), 0.5), "mix_r": full((L, d), 0.5),
                    "wk": w((L, d, f), sd), "wr": w((L, d, d), 0.02),
                    "wv": w((L, f, d), sf),
                },
            }},
        }

    return jax.jit(make)(key)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + g)


def _shift(x):
    return jnp.concatenate([jnp.zeros_like(x[:1]), x[:-1]], 0)


def _wkv(r, k, v, w, u):
    """r, k, v, w: (S, H, N); u: (H, N). Token by token."""
    def step(st, xs):
        rt, kt, vt, wt = xs
        kv = kt[:, :, None] * vt[:, None, :]
        out = jnp.einsum("hn,hnm->hm", rt, st + u[:, :, None] * kv,
                         precision=jax.lax.Precision.HIGHEST)
        return wt[:, :, None] * st + kv, out

    st0 = jnp.zeros((r.shape[1], r.shape[2], r.shape[2]), jnp.float32)
    return jax.lax.scan(step, st0, (r, k, v, w))[1]


def logits_at(params, tokens, start: int, count: int, m: dict, mode: str = "f32"):
    """Float32 logits (count, vocab) at positions start … start+count−1
    of one sequence ``tokens`` (S,): what the model predicts for tokens
    start+1 … start+count."""
    d, n = m["d_model"], m["rwkv_head_dim"]
    h = d // n
    es = einsum_fn(mode)
    eps = m["norm_eps"]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731

    def layer(x, p):
        p = jax.tree.map(f32, p)
        t, c = p["time"], p["chan"]
        s = x.shape[0]
        a = _rms(x, p["norm1"]["gamma"], eps)
        delta = _shift(a) - a
        lora = es("sr,re->se", jnp.tanh(es("sd,dr->sr", a, t["mix_lora_a"])), t["mix_lora_b"])
        mixes = t["mix_base"][None] + lora.reshape(s, 5, d)
        xm = a[:, None] + delta[:, None] * jax.nn.sigmoid(mixes)
        xr, xk, xv, xw, xg = (xm[:, i] for i in range(5))
        r = es("sd,de->se", xr, t["wr"]).reshape(s, h, n)
        k = es("sd,de->se", xk, t["wk"]).reshape(s, h, n) / jnp.sqrt(jnp.float32(n))
        vv = es("sd,de->se", xv, t["wv"]).reshape(s, h, n)
        g = jax.nn.silu(es("sd,de->se", xg, t["wg"]))
        dec = t["decay_base"][None] + es(
            "sr,rd->sd", jnp.tanh(es("sd,dr->sr", xw, t["decay_lora_a"])), t["decay_lora_b"])
        w = jnp.exp(-jnp.exp(dec)).reshape(s, h, n)
        o = _wkv(r, k, vv, w, t["bonus"])
        mu = o.mean(-1, keepdims=True)
        var = ((o - mu) ** 2).mean(-1, keepdims=True)
        o = ((o - mu) * jax.lax.rsqrt(var + 1e-5)).reshape(s, d) * t["ln_gamma"]
        x = x + es("sd,de->se", o * g, t["wo"])
        a = _rms(x, p["norm2"]["gamma"], eps)
        da = _shift(a) - a
        xk = a + da * jax.nn.sigmoid(c["mix_k"])
        xr = a + da * jax.nn.sigmoid(c["mix_r"])
        kk = jnp.square(jax.nn.relu(es("sd,df->sf", xk, c["wk"])))
        rr = jax.nn.sigmoid(es("sd,de->se", xr, c["wr"]))
        return x + rr * es("sf,fd->sd", kk, c["wv"]), None

    x = f32(params["embed"])[tokens]
    x, _ = jax.lax.scan(layer, x, params["group0"]["0_rwkv"])
    x = jax.lax.dynamic_slice_in_dim(x, start, count, 0)
    x = _rms(x, f32(params["final_norm"]["gamma"]), eps)
    return es("sd,dv->sv", x, f32(params["unembed"]))
