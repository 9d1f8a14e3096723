"""Architecture assembly: embeddings + scanned layer groups + LM head.

One code path serves all 10 assigned architectures:

* layer groups from cfg.layer_groups are lax.scan-ed (stacked params) so
  compile time is O(pattern) not O(num_layers);
* block kinds: "global"/"dense" (full or sliding-window GQA + MLP),
  "local" (windowed GQA + MLP), "moe" (GQA + routed experts),
  "recurrent" (RG-LRU), "rwkv" (RWKV6 time+channel mix);
* encoder–decoder (whisper): a bidirectional encoder over precomputed
  frame embeddings (modality-frontend stub) + cross-attention in every
  decoder block;
* VLM (phi-3-vision): precomputed patch embeddings prepended to the token
  sequence (vision-tower stub); loss masked to token positions.

Three entry points per architecture (all pure, jit/shard_map friendly):
  lm_loss(cfg, params, batch)                 — training objective
  lm_prefill(cfg, params, batch)              — build decode caches
  lm_decode_step(cfg, params, batch, caches)  — one token, O(1)/O(window)

Batch layout: {"tokens": (B, S) int32} plus "frames" (B, F, d) for audio
and "patches" (B, P, d) for VLM. Labels are tokens shifted by one with the
final position masked, so a (B, S) batch trains S−1 predictions.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from .attention import attention_apply, attention_init, decode_attention_apply
from .config import ModelConfig
from .layers import annotate, dense_init, dtype_of, mlp_apply, mlp_init, norm_apply, norm_init
from .moe import moe_apply, moe_init
from .rglru import rglru_apply, rglru_decode, rglru_init, rglru_init_state
from .rwkv6 import (
    rwkv_channel_apply,
    rwkv_channel_decode,
    rwkv_channel_init,
    rwkv_init_state,
    rwkv_time_apply,
    rwkv_time_decode,
    rwkv_time_init,
)

__all__ = [
    "lm_init",
    "lm_init_cast",
    "lm_loss",
    "lm_logits",
    "lm_prefill",
    "lm_prefill_chunk",
    "lm_decode_step",
    "init_decode_caches",
    "cache_slot_insert",
    "cache_slot_extract",
    "cache_slot_clear",
    "max_chunk_len",
]

ATTN_KINDS = ("global", "local", "dense", "moe")


def _rwkv_impl(attn_impl: str) -> str:
    # "scan" (the dry-run default elsewhere) maps to the chunked matmul
    # form for RWKV — the sequential scan is kept for tests/oracle use
    # via attn_impl="naive". See EXPERIMENTS.md §Perf (rwkv6 iteration).
    if attn_impl == "pallas":
        return "pallas"
    if attn_impl == "naive":
        return "scan"
    return "chunked"


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _block_init(rng, cfg: ModelConfig, kind: str, cross: bool):
    d = cfg.d_model
    ks = jax.random.split(rng, 8)
    p: dict = {"norm1": norm_init(cfg, d)}
    if kind in ("global", "local", "dense", "moe"):
        p["attn"] = attention_init(ks[0], cfg)
        p["norm2"] = norm_init(cfg, d)
        if kind == "moe":
            p["moe"] = moe_init(ks[1], cfg)
        else:
            p["mlp"] = mlp_init(ks[1], d, cfg.d_ff, cfg.mlp_variant)
        if cross:
            p["norm_cross"] = norm_init(cfg, d)
            p["cross"] = attention_init(ks[2], cfg)
    elif kind == "recurrent":
        p["rec"] = rglru_init(ks[0], cfg)
        p["norm2"] = norm_init(cfg, d)
        p["mlp"] = mlp_init(ks[1], d, cfg.d_ff, cfg.mlp_variant)
    elif kind == "rwkv":
        p["time"] = rwkv_time_init(ks[0], cfg)
        p["norm2"] = norm_init(cfg, d)
        p["chan"] = rwkv_channel_init(ks[1], cfg)
    else:
        raise ValueError(f"unknown block kind {kind}")
    return p


def _group_init(rng, cfg: ModelConfig, pattern, reps: int, cross: bool):
    def one(r):
        ks = jax.random.split(r, len(pattern))
        return {k_i: _block_init(ks[i], cfg, kind, cross) for i, (k_i, kind) in enumerate(_pattern_keys(pattern))}

    return jax.vmap(one)(jax.random.split(rng, reps))


def _pattern_keys(pattern):
    """Stable dict keys per sublayer: '<idx>_<kind>'."""
    return [(f"{i}_{kind}", kind) for i, kind in enumerate(pattern)]


def lm_init(rng, cfg: ModelConfig):
    ks = jax.random.split(rng, 8 + len(cfg.layer_groups))
    v = cfg.padded_vocab
    params: dict = {
        "embed": dense_init(ks[0], v, cfg.d_model, scale=1.0),
        "final_norm": norm_init(cfg, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = dense_init(ks[1], cfg.d_model, v)
    if cfg.pos_variant == "learned":
        params["pos_embed"] = dense_init(ks[2], cfg.max_seq_len, cfg.d_model, scale=0.02)
    cross = cfg.is_encoder_decoder
    for gi, (pattern, reps) in enumerate(cfg.layer_groups):
        params[f"group{gi}"] = _group_init(ks[3 + gi], cfg, pattern, reps, cross)
    if cfg.encoder is not None:
        e = cfg.encoder
        ecfg = dataclasses.replace(
            cfg, d_model=e.d_model, num_heads=e.num_heads,
            num_kv_heads=e.num_heads, d_ff=e.d_ff, qkv_bias=False,
            layer_pattern=("global",), num_layers=e.num_layers,
        )
        params["enc_pos"] = dense_init(ks[6], e.num_frames, e.d_model, scale=0.02)
        params["encoder"] = _group_init(ks[7], ecfg, ("global",), e.num_layers, cross=False)
        params["enc_norm"] = norm_init(ecfg, e.d_model)
        if e.d_model != cfg.d_model:
            params["enc_proj"] = dense_init(jax.random.fold_in(ks[7], 1), e.d_model, cfg.d_model)
    return params


def lm_init_cast(rng, cfg: ModelConfig):
    """``lm_init`` with every float leaf cast to ``cfg.dtype`` inside one
    jit, so the float32 draw of each leaf is fused into its cast and a
    whole float32 copy of the model is never resident on the device."""

    def init(key):
        dt = dtype_of(cfg)
        return jax.tree.map(
            lambda x: x.astype(dt) if jnp.issubdtype(x.dtype, jnp.floating) else x,
            lm_init(key, cfg))

    return jax.jit(init)(rng)


# ---------------------------------------------------------------------------
# forward (full sequence: train / prefill)
# ---------------------------------------------------------------------------

def _attn_window(cfg, kind):
    if kind == "local":
        return cfg.local_window
    return cfg.sliding_window  # 0 ⇒ full attention


def _block_apply(cfg, kind, p, x, positions, rules, attn_impl, enc_out=None,
                 attn_block: int = 512):
    """Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if kind in ATTN_KINDS:
        h = norm_apply(cfg, p["norm1"], x)
        h = attention_apply(
            cfg, p["attn"], h, positions,
            window=_attn_window(cfg, kind), causal=True,
            rules=rules, impl=attn_impl,
            block_q=attn_block, block_k=attn_block,
        )
        x = x + h
        if enc_out is not None and "cross" in p:
            h = norm_apply(cfg, p["norm_cross"], x)
            h = _cross_attention(cfg, p["cross"], h, enc_out, rules)
            x = x + h
        h = norm_apply(cfg, p["norm2"], x)
        if kind == "moe":
            h, aux = moe_apply(cfg, p["moe"], h, rules)
        else:
            h = mlp_apply(p["mlp"], h, cfg.mlp_variant, rules)
        x = x + h
    elif kind == "recurrent":
        h = norm_apply(cfg, p["norm1"], x)
        x = x + rglru_apply(cfg, p["rec"], h, rules, impl=attn_impl if attn_impl == "pallas" else "scan")
        h = norm_apply(cfg, p["norm2"], x)
        x = x + mlp_apply(p["mlp"], h, cfg.mlp_variant, rules)
    elif kind == "rwkv":
        h = norm_apply(cfg, p["norm1"], x)
        x = x + rwkv_time_apply(cfg, p["time"], h, rules, impl=_rwkv_impl(attn_impl))
        h = norm_apply(cfg, p["norm2"], x)
        x = x + rwkv_channel_apply(cfg, p["chan"], h, rules)
    return x, aux


def _cross_attention(cfg, p, x, enc_out, rules):
    """Query from decoder stream, keys/values from encoder output."""
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bfd,dhk->bfhk", enc_out, p["wk"].astype(dt))
    v = jnp.einsum("bfd,dhk->bfhk", enc_out, p["wv"].astype(dt))
    scale = 1.0 / np.sqrt(q.shape[-1])
    hq, hkv = q.shape[2], k.shape[2]
    qg = q.reshape(*q.shape[:2], hkv, hq // hkv, q.shape[-1])
    logits = jnp.einsum("bshgk,bfhk->bhgsf", qg, k) * scale
    w = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(dt)
    ctx = jnp.einsum("bhgsf,bfhk->bshgk", w, v)
    ctx = ctx.reshape(*x.shape[:2], -1)
    return ctx @ p["wo"].astype(dt)


def _run_groups(cfg, params, x, positions, rules, attn_impl, enc_out=None, remat=True,
                attn_block: int = 512):
    """Apply all layer groups via lax.scan over stacked params."""
    total_aux = jnp.zeros((), jnp.float32)
    for gi, (pattern, reps) in enumerate(cfg.layer_groups):
        gp = params[f"group{gi}"]

        def body(carry, layer_params, _pattern=pattern):
            h, aux = carry
            for key, kind in _pattern_keys(_pattern):
                h, a = _block_apply(cfg, kind, layer_params[key], h, positions,
                                    rules, attn_impl, enc_out,
                                    attn_block=attn_block)
                aux = aux + a
            # pin the scan carry (and thus its backward cotangent, which
            # GSPMD reshards across layer iterations) to the compute dtype —
            # without this the residual-stream gradient travels in f32,
            # doubling the dominant all-gather bytes (§Perf, granite iter 2).
            return (h.astype(dtype_of(cfg)), aux), None

        if remat:
            body = jax.checkpoint(body, prevent_cse=False)
        (x, total_aux), _ = jax.lax.scan(body, (x, total_aux), gp)
    return x, total_aux


def _encode(cfg, params, frames, rules, attn_impl):
    e = cfg.encoder
    dt = dtype_of(cfg)
    x = frames.astype(dt) + params["enc_pos"][None, : frames.shape[1]].astype(dt)
    ecfg = dataclasses.replace(
        cfg, d_model=e.d_model, num_heads=e.num_heads, num_kv_heads=e.num_heads,
        d_ff=e.d_ff, qkv_bias=False, pos_variant="learned", sliding_window=0,
    )
    positions = jnp.broadcast_to(jnp.arange(frames.shape[1]), frames.shape[:2])

    def body(h, layer_params):
        hh = norm_apply(ecfg, layer_params["0_global"]["norm1"], h)
        hh = attention_apply(ecfg, layer_params["0_global"]["attn"], hh, positions,
                             causal=False, rules=rules, impl=attn_impl)
        h = h + hh
        hh = norm_apply(ecfg, layer_params["0_global"]["norm2"], h)
        h = h + mlp_apply(layer_params["0_global"]["mlp"], hh, cfg.mlp_variant, rules)
        return h, None

    x, _ = jax.lax.scan(body, x, params["encoder"])
    x = norm_apply(ecfg, params["enc_norm"], x)
    if "enc_proj" in params:
        x = x @ params["enc_proj"].astype(dt)
    return x


def _embed_inputs(cfg, params, batch, dt):
    """Token (+ prefix patch) embeddings. Returns (x, positions, n_prefix)."""
    tokens = batch["tokens"]
    x = params["embed"].astype(dt)[tokens]
    n_prefix = 0
    if cfg.frontend == "vision" and "patches" in batch:
        x = jnp.concatenate([batch["patches"].astype(dt), x], axis=1)
        n_prefix = batch["patches"].shape[1]
    b, s = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    if cfg.pos_variant == "learned":
        x = x + params["pos_embed"].astype(dt)[None, :s]
    return x, positions, n_prefix


def lm_logits(cfg: ModelConfig, params, batch, *, rules=None, attn_impl="scan", remat=True,
              attn_block: int = 512):
    rules = rules or {}
    dt = dtype_of(cfg)
    x, positions, n_prefix = _embed_inputs(cfg, params, batch, dt)
    x = annotate(x, ("batch", "seq", "embed"), rules)
    enc_out = None
    if cfg.encoder is not None:
        enc_out = _encode(cfg, params, batch["frames"], rules, attn_impl)
    x, aux = _run_groups(cfg, params, x, positions, rules, attn_impl, enc_out, remat,
                         attn_block=attn_block)
    x = norm_apply(cfg, params["final_norm"], x)
    if n_prefix:
        x = x[:, n_prefix:]
    head = params["embed"] if cfg.tie_embeddings else params["unembed"]
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, head.astype(dt))
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, head.astype(dt))
    return annotate(logits, ("batch", "seq", "vocab"), rules), aux


def lm_loss(cfg: ModelConfig, params, batch, *, rules=None, attn_impl="scan", remat=True,
            attn_block: int = 512):
    """Next-token cross entropy (final position masked)."""
    logits, aux = lm_logits(cfg, params, batch, rules=rules, attn_impl=attn_impl,
                            remat=remat, attn_block=attn_block)
    tokens = batch["tokens"]
    labels = jnp.roll(tokens, -1, axis=1)
    mask = jnp.ones_like(tokens, jnp.float32).at[:, -1].set(0.0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    loss = jnp.sum(nll * mask) / jnp.maximum(mask.sum(), 1.0)
    if cfg.moe is not None:
        loss = loss + cfg.moe.router_aux_weight * aux
    return loss


# ---------------------------------------------------------------------------
# decode path
# ---------------------------------------------------------------------------

def _block_cache(cfg, kind, batch: int, cache_len: int, dt):
    if kind in ATTN_KINDS:
        window = _attn_window(cfg, kind)
        cap = min(window, cache_len) if window else cache_len
        hkv, hd = cfg.num_kv_heads, cfg.head_dim_
        c = {
            "k": jnp.zeros((batch, cap, hkv, hd), dt),
            "v": jnp.zeros((batch, cap, hkv, hd), dt),
            "pos": jnp.zeros((batch,), jnp.int32),
        }
        if cfg.is_encoder_decoder:
            e = cfg.encoder
            c["cross_k"] = jnp.zeros((batch, e.num_frames, hkv, hd), dt)
            c["cross_v"] = jnp.zeros((batch, e.num_frames, hkv, hd), dt)
        return c
    if kind == "recurrent":
        return rglru_init_state(cfg, batch)
    if kind == "rwkv":
        return rwkv_init_state(cfg, batch)
    raise ValueError(kind)


def init_decode_caches(cfg: ModelConfig, batch: int, cache_len: int):
    """Stacked (per layer group) decode caches, zero-filled."""
    dt = dtype_of(cfg)
    caches = []
    for pattern, reps in cfg.layer_groups:
        one = {
            key: _block_cache(cfg, kind, batch, cache_len, dt)
            for key, kind in _pattern_keys(pattern)
        }
        caches.append(jax.tree.map(lambda x: jnp.broadcast_to(x, (reps, *x.shape)), one))
    return caches


# ---------------------------------------------------------------------------
# cache slot surgery (continuous-batching serving, repro.serve)
#
# Every stacked cache leaf carries the slot/batch dim at axis 1:
# attention k/v (reps, B, C, Hkv, hd), pos (reps, B), recurrent h
# (reps, B, w), rwkv wkv (reps, B, H, N, N), ... — so a serving slot pool
# can splice one request's state in or out with a single tree map. The
# source tree must have been built over the same cfg and cache capacity
# (lm_prefill with reserve chosen so prompt_len + reserve == pool cap).
# ---------------------------------------------------------------------------

def cache_slot_insert(caches, slot: int, src_caches, src_slot: int = 0):
    """Pool caches with ``slot`` replaced by ``src_caches[src_slot]``.

    Overwrites every leaf of the slot (attention K/V + pos, recurrent /
    rwkv states), so whatever a previous occupant left behind is gone —
    eviction needs no separate clear before the next insert."""
    return jax.tree.map(
        lambda dst, s: dst.at[:, slot].set(s[:, src_slot]), caches, src_caches
    )


def cache_slot_extract(caches, slot: int):
    """One slot's state as a batch-1 cache tree (decode-ready)."""
    return jax.tree.map(lambda x: x[:, slot : slot + 1], caches)


def cache_slot_clear(caches, slot: int):
    """Zero one slot (free-slot hygiene; inserts overwrite regardless)."""
    return jax.tree.map(lambda x: x.at[:, slot].set(jnp.zeros_like(x[:, slot])), caches)


def lm_decode_step(cfg: ModelConfig, params, batch, caches, *, rules=None):
    """One decode step. batch: {"tokens": (B, 1)}; caches from
    init_decode_caches / lm_prefill. Returns (logits (B, 1, V), caches)."""
    rules = rules or {}
    dt = dtype_of(cfg)
    tokens = batch["tokens"]
    x = params["embed"].astype(dt)[tokens]  # (B,1,d)
    if cfg.pos_variant == "learned":
        # per-row positions: slots in a continuous-batching pool sit at
        # different sequence offsets, so each row gathers its own embedding
        pos_b = _slot_positions(caches, tokens.shape[0])
        x = x + params["pos_embed"].astype(dt)[pos_b][:, None]

    new_caches = []
    for gi, (pattern, reps) in enumerate(cfg.layer_groups):
        gp = params[f"group{gi}"]

        def body(h, xs, _pattern=pattern):
            layer_params, layer_cache = xs
            new_cache = {}
            for key, kind in _pattern_keys(_pattern):
                h, new_cache[key] = _block_decode(
                    cfg, kind, layer_params[key], h, layer_cache[key], rules
                )
            return h, new_cache

        x, nc = jax.lax.scan(body, x, (gp, caches[gi]))
        new_caches.append(nc)

    x = norm_apply(cfg, params["final_norm"], x)
    head = params["embed"] if cfg.tie_embeddings else params["unembed"]
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, head.astype(dt))
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, head.astype(dt))
    return logits, new_caches


def _slot_positions(caches, batch: int):
    """Per-row token counts (B,) from the first attention cache's ``pos``;
    zeros for position-free (pure recurrent) stacks."""
    leaf = caches[0]
    for key in leaf:
        if "pos" in leaf[key]:
            return leaf[key]["pos"][0]
    return jnp.zeros((batch,), jnp.int32)


def _block_decode(cfg, kind, p, x, cache, rules):
    if kind in ATTN_KINDS:
        h = norm_apply(cfg, p["norm1"], x)
        h, new_cache = decode_attention_apply(
            cfg, p["attn"], h, cache, window=_attn_window(cfg, kind), rules=rules
        )
        x = x + h
        if "cross" in p and "cross_k" in cache:
            h = norm_apply(cfg, p["norm_cross"], x)
            h = _cross_decode(cfg, p["cross"], h, cache)
            x = x + h
            new_cache["cross_k"] = cache["cross_k"]
            new_cache["cross_v"] = cache["cross_v"]
        h = norm_apply(cfg, p["norm2"], x)
        if kind == "moe":
            h, _ = moe_apply(cfg, p["moe"], h, rules)
        else:
            h = mlp_apply(p["mlp"], h, cfg.mlp_variant, rules)
        return x + h, new_cache
    if kind == "recurrent":
        h = norm_apply(cfg, p["norm1"], x)
        h, new_state = rglru_decode(cfg, p["rec"], h, cache, rules)
        x = x + h
        h = norm_apply(cfg, p["norm2"], x)
        return x + mlp_apply(p["mlp"], h, cfg.mlp_variant, rules), new_state
    if kind == "rwkv":
        h = norm_apply(cfg, p["norm1"], x)
        h, st = rwkv_time_decode(cfg, p["time"], h, cache, rules)
        x = x + h
        h = norm_apply(cfg, p["norm2"], x)
        h, st = rwkv_channel_decode(cfg, p["chan"], h, st, rules)
        return x + h, st
    raise ValueError(kind)


def _cross_decode(cfg, p, x, cache):
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k, v = cache["cross_k"], cache["cross_v"]
    hq, hkv = q.shape[2], k.shape[2]
    qg = q.reshape(*q.shape[:2], hkv, hq // hkv, q.shape[-1])
    scale = 1.0 / np.sqrt(q.shape[-1])
    logits = jnp.einsum("bshgk,bfhk->bhgsf", qg, k) * scale
    w = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(dt)
    ctx = jnp.einsum("bhgsf,bfhk->bshgk", w, v).reshape(*x.shape[:2], -1)
    return ctx @ p["wo"].astype(dt)


# ---------------------------------------------------------------------------
# prefill: run the full-sequence forward while filling decode caches.
# For the dry-run the relevant artifact is the compiled full forward; we
# fill attention caches with the projected K/V and recurrent states with
# the final scan state.
# ---------------------------------------------------------------------------

def lm_prefill(cfg: ModelConfig, params, batch, *, rules=None, attn_impl="scan",
               reserve: int = 1):
    """Returns (last-position logits (B, V), caches ready for decode).

    Implemented as the full forward (same FLOPs as training fwd) plus
    cache extraction; recurrent/rwkv caches are rebuilt by replaying the
    per-block scans (cheap relative to the matmuls at these widths).
    ``reserve`` extra cache slots are allocated for subsequent decode
    steps (dense caches must hold prefill + decoded tokens).
    """
    rules = rules or {}
    logits, _ = lm_logits(cfg, params, batch, rules=rules, attn_impl=attn_impl, remat=False)
    b, s = batch["tokens"].shape
    if cfg.frontend == "vision" and "patches" in batch:
        s += batch["patches"].shape[1]  # prefix embeddings occupy cache slots
    caches = init_decode_caches(cfg, b, s + reserve)
    caches = _fill_caches(cfg, params, batch, caches, rules, attn_impl)
    return logits[:, -1], caches


def _fill_caches(cfg, params, batch, caches, rules, attn_impl):
    """Replay the forward, capturing K/V and recurrent states per layer."""
    dt = dtype_of(cfg)
    x, positions, n_prefix = _embed_inputs(cfg, params, batch, dt)
    enc_out = None
    if cfg.encoder is not None:
        enc_out = _encode(cfg, params, batch["frames"], rules, attn_impl)

    new_caches = []
    for gi, (pattern, reps) in enumerate(cfg.layer_groups):
        gp = params[f"group{gi}"]

        def body(carry, xs, _pattern=pattern):
            h = carry
            layer_params, layer_cache = xs
            out_cache = {}
            for key, kind in _pattern_keys(_pattern):
                p = layer_params[key]
                c = layer_cache[key]
                h, out_cache[key] = _prefill_block(cfg, kind, p, h, c, positions, rules, attn_impl, enc_out)
            return h, out_cache

        x, nc = jax.lax.scan(body, x, (gp, caches[gi]))
        new_caches.append(nc)
    return new_caches


def _prefill_block(cfg, kind, p, x, cache, positions, rules, attn_impl, enc_out):
    from .attention import _project_qkv  # reuse projections

    if kind in ATTN_KINDS:
        h = norm_apply(cfg, p["norm1"], x)
        _, k, v = _project_qkv(cfg, p["attn"], h, positions, rules)
        cap = cache["k"].shape[1]
        s = k.shape[1]
        new_cache = dict(cache)
        if s >= cap:  # keep last `cap` keys (ring layout: slot = pos % cap)
            ks_, vs_ = k[:, s - cap :], v[:, s - cap :]
            if _attn_window(cfg, kind):
                roll = (s - cap) % cap if cap else 0
                shift = (s % cap) - 0  # align slot p%cap
                ks_ = jnp.roll(ks_, shift=s % cap, axis=1)
                vs_ = jnp.roll(vs_, shift=s % cap, axis=1)
            new_cache["k"], new_cache["v"] = ks_, vs_
        else:
            new_cache["k"] = cache["k"].at[:, :s].set(k)
            new_cache["v"] = cache["v"].at[:, :s].set(v)
        new_cache["pos"] = jnp.full((x.shape[0],), s, jnp.int32)
        h2 = attention_apply(cfg, p["attn"], h, positions, window=_attn_window(cfg, kind),
                             causal=True, rules=rules, impl=attn_impl)
        x = x + h2
        if enc_out is not None and "cross" in p:
            hc = norm_apply(cfg, p["norm_cross"], x)
            x = x + _cross_attention(cfg, p["cross"], hc, enc_out, rules)
            dt = x.dtype
            new_cache["cross_k"] = jnp.einsum("bfd,dhk->bfhk", enc_out, p["cross"]["wk"].astype(dt))
            new_cache["cross_v"] = jnp.einsum("bfd,dhk->bfhk", enc_out, p["cross"]["wv"].astype(dt))
        h = norm_apply(cfg, p["norm2"], x)
        if kind == "moe":
            h, _ = moe_apply(cfg, p["moe"], h, rules)
        else:
            h = mlp_apply(p["mlp"], h, cfg.mlp_variant, rules)
        return x + h, new_cache

    if kind == "recurrent":
        from .rglru import _causal_conv, _gates, lru_scan

        h = norm_apply(cfg, p["norm1"], x)
        dt = x.dtype
        u = h @ p["rec"]["wx"].astype(dt)
        vgate = jax.nn.gelu(h @ p["rec"]["wg"].astype(dt))
        uc = _causal_conv(u, p["rec"]["conv"])
        a, bb = _gates(p["rec"], uc, dt)
        hs = lru_scan(a, bb)
        new_state = {
            "h": hs[:, -1],
            "conv_tail": u[:, -(cfg.conv1d_width - 1):].astype(jnp.float32),
        }
        y = (hs.astype(dt) * vgate) @ p["rec"]["wo"].astype(dt)
        x = x + y
        h = norm_apply(cfg, p["norm2"], x)
        return x + mlp_apply(p["mlp"], h, cfg.mlp_variant, rules), new_state

    if kind == "rwkv":
        from .rwkv6 import _heads, _streams, _token_shift, wkv_scan, _groupnorm
        from .rwkv_chunked import wkv_chunked

        h = norm_apply(cfg, p["norm1"], x)
        dt = x.dtype
        n = cfg.rwkv_head_dim
        prev = _token_shift(h)
        r, k, v, w, g = _streams(p["time"], h, prev, dt)
        r, k, v, w = (_heads(t, n) for t in (r, k, v, w))
        k = k * (1.0 / np.sqrt(n))
        _wkv = wkv_scan if attn_impl == "naive" else wkv_chunked
        out, stT = _wkv(r, k, v, w.astype(jnp.float32), p["time"]["bonus"])
        y = _groupnorm(out, p["time"]["ln_gamma"], n).astype(dt) * g
        x_after_time = x + y @ p["time"]["wo"].astype(dt)
        h2 = norm_apply(cfg, p["norm2"], x_after_time)
        y2 = rwkv_channel_apply(cfg, p["chan"], h2, rules)
        new_state = {
            "wkv": stT,
            "last_x_time": h[:, -1].astype(jnp.float32),
            "last_x_chan": h2[:, -1].astype(jnp.float32),
        }
        return x_after_time + y2, new_state
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# chunked prefill: advance decode caches by c tokens per call, resumable.
#
# The carry is the decode cache tree itself — ring K/V + pos for
# attention kinds, {h, conv_tail} for RG-LRU, {wkv, last_x_*} for RWKV —
# so a prompt can be prefetched in fixed-size chunks interleaved with
# decode steps (repro.serve), evicted mid-prefill and resumed later.
# Rows are ragged: ``n_valid`` masks each row's tail with identity
# transitions (attention: scatter dropped + keys masked; rglru: a=1,
# b=0; rwkv: w=1, k=v=0), so one dispatch advances every active lane and
# a row with n_valid = 0 is an exact no-op on its state.
# ---------------------------------------------------------------------------

def max_chunk_len(cfg: ModelConfig, cache_len: int) -> int | None:
    """Largest prefill chunk the decode caches can absorb in one call:
    the smallest ring-buffer capacity across windowed attention layers
    (a bigger chunk would overwrite keys its own early queries still
    need). None when no layer rings (dense attention / recurrent)."""
    caps = []
    for pattern, _ in cfg.layer_groups:
        for kind in pattern:
            if kind in ATTN_KINDS:
                w = _attn_window(cfg, kind)
                if w:
                    caps.append(min(w, cache_len))
    return min(caps) if caps else None


def lm_prefill_chunk(cfg: ModelConfig, params, batch, caches, start, *,
                     rules=None, attn_impl="scan", n_valid=None):
    """One prefill chunk: batch {"tokens": (B, c)}, per-row ``start``
    (B,) tokens already consumed, ``n_valid`` (B,) valid tokens in this
    chunk (None = all c). Returns (logits at each row's last valid
    position (B, V), updated caches). Token streams match monolithic
    ``lm_prefill`` + decode exactly; logits agree to float tolerance
    (reduction order differs, as with every blockwise attention)."""
    if cfg.frontend or cfg.encoder is not None:
        raise ValueError(
            "chunked prefill drives token-only decoders; "
            f"{cfg.name} needs a modality frontend at prefill"
        )
    rules = rules or {}
    dt = dtype_of(cfg)
    tokens = batch["tokens"]
    b, c = tokens.shape
    start = jnp.asarray(start, jnp.int32)
    if start.ndim == 0:
        start = jnp.broadcast_to(start, (b,))
    nv = (jnp.full((b,), c, jnp.int32) if n_valid is None
          else jnp.asarray(n_valid, jnp.int32))
    positions = start[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    valid = jnp.arange(c, dtype=jnp.int32)[None, :] < nv[:, None]  # (B, c)

    x = params["embed"].astype(dt)[tokens]
    if cfg.pos_variant == "learned":
        safe = jnp.clip(positions, 0, cfg.max_seq_len - 1)
        x = x + params["pos_embed"].astype(dt)[safe]

    new_caches = []
    for gi, (pattern, reps) in enumerate(cfg.layer_groups):
        gp = params[f"group{gi}"]

        def body(h, xs, _pattern=pattern):
            layer_params, layer_cache = xs
            out_cache = {}
            for key, kind in _pattern_keys(_pattern):
                h, out_cache[key] = _chunk_block(
                    cfg, kind, layer_params[key], h, layer_cache[key],
                    positions, nv, valid, rules, attn_impl,
                )
            return h, out_cache

        x, nc = jax.lax.scan(body, x, (gp, caches[gi]))
        new_caches.append(nc)

    x = norm_apply(cfg, params["final_norm"], x)
    last = jnp.clip(nv - 1, 0, c - 1)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]  # (B, d)
    head = params["embed"] if cfg.tie_embeddings else params["unembed"]
    if cfg.tie_embeddings:
        logits = jnp.einsum("bd,vd->bv", x_last, head.astype(dt))
    else:
        logits = jnp.einsum("bd,dv->bv", x_last, head.astype(dt))
    return logits, new_caches


def _chunk_block(cfg, kind, p, x, cache, positions, nv, valid, rules, attn_impl):
    from .attention import chunk_attention_apply

    if kind in ATTN_KINDS:
        h = norm_apply(cfg, p["norm1"], x)
        h2, new_cache = chunk_attention_apply(
            cfg, p["attn"], h, cache, positions, nv, valid,
            window=_attn_window(cfg, kind), rules=rules,
        )
        x = x + h2
        h = norm_apply(cfg, p["norm2"], x)
        if kind == "moe":
            h, _ = moe_apply(cfg, p["moe"], h, rules)
        else:
            h = mlp_apply(p["mlp"], h, cfg.mlp_variant, rules)
        return x + h, new_cache

    if kind == "recurrent":
        from .rglru import _gates, lru_scan

        h = norm_apply(cfg, p["norm1"], x)
        dt = x.dtype
        c = x.shape[1]
        u = h @ p["rec"]["wx"].astype(dt)  # (B, c, w)
        vgate = jax.nn.gelu(h @ p["rec"]["wg"].astype(dt))
        kw = cfg.conv1d_width
        tail = cache["conv_tail"].astype(dt)  # (B, K-1, w)
        win = jnp.concatenate([tail, u], axis=1)  # (B, K-1+c, w)
        uc = sum(win[:, i : i + c] * p["rec"]["conv"][i].astype(dt)
                 for i in range(kw))
        a, bb = _gates(p["rec"], uc, dt)
        vm = valid[..., None]
        a = jnp.where(vm, a, 1.0)  # identity transition on padding rows
        bb = jnp.where(vm, bb, 0.0)
        hs = lru_scan(a, bb, h0=cache["h"])
        # new conv tail = raw u at the last K-1 *valid* positions (win
        # index nv maps to u index nv-(K-1); nv < K-1 keeps old tail).
        tail_idx = nv[:, None, None] + jnp.arange(kw - 1)[None, :, None]
        new_tail = jnp.take_along_axis(win.astype(jnp.float32), tail_idx, axis=1)
        new_tail = jnp.where((nv > 0)[:, None, None], new_tail, cache["conv_tail"])
        new_state = {"h": hs[:, -1], "conv_tail": new_tail}
        y = (hs.astype(dt) * vgate) @ p["rec"]["wo"].astype(dt)
        x = x + y
        h = norm_apply(cfg, p["norm2"], x)
        return x + mlp_apply(p["mlp"], h, cfg.mlp_variant, rules), new_state

    if kind == "rwkv":
        from .rwkv6 import _channel_core, _groupnorm, _heads, _streams, _token_shift, wkv_scan
        from .rwkv_chunked import wkv_chunked

        h = norm_apply(cfg, p["norm1"], x)
        dt = x.dtype
        n = cfg.rwkv_head_dim
        c = x.shape[1]
        prev = _token_shift(h, last=cache["last_x_time"].astype(dt))
        r, k, v, w, g = _streams(p["time"], h, prev, dt)
        r, k, v, w = (_heads(t, n) for t in (r, k, v, w))
        k = k * (1.0 / np.sqrt(n))
        vm = valid[..., None, None]
        k = jnp.where(vm, k, 0.0)  # identity state transition on padding
        v = jnp.where(vm, v, 0.0)
        w = jnp.where(vm, w.astype(jnp.float32), 1.0)
        _wkv = wkv_scan if attn_impl == "naive" else wkv_chunked
        out, stT = _wkv(r, k, v, w, p["time"]["bonus"], state0=cache["wkv"])
        y = _groupnorm(out, p["time"]["ln_gamma"], n).astype(dt) * g
        x_after_time = x + y @ p["time"]["wo"].astype(dt)
        h2 = norm_apply(cfg, p["norm2"], x_after_time)
        prev2 = _token_shift(h2, last=cache["last_x_chan"].astype(h2.dtype))
        y2 = _channel_core(p["chan"], h2, prev2, h2.dtype, rules)
        last = jnp.clip(nv - 1, 0, c - 1)[:, None, None]
        any_v = (nv > 0)[:, None]

        def at_last(t):
            return jnp.take_along_axis(t, last, axis=1)[:, 0].astype(jnp.float32)

        new_state = {
            "wkv": stT,
            "last_x_time": jnp.where(any_v, at_last(h), cache["last_x_time"]),
            "last_x_chan": jnp.where(any_v, at_last(h2), cache["last_x_chan"]),
        }
        return x_after_time + y2, new_state
    raise ValueError(kind)
