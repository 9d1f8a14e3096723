"""Operations and bytes the benchmark charges each piece of work, from
shapes alone. Every roofline share and every ``mfu`` divides one of
these by a device time from the trace and a peak from ``peaks.json``.

The sizes come from a configuration file's ``model`` block (the keys of
``repro.models.config.ModelConfig``), read as plain numbers here.
"""

from __future__ import annotations

__all__ = [
    "dense_matmul_params", "dense_train_flops_per_token",
    "flash_forward_cost", "int8_decode_apply_bytes", "param_leaf_sizes",
    "rwkv_layer_params", "rwkv_decode_step_cost", "roofline_seconds",
]

BF16, F32, INT8 = 2, 4, 1
_RWKV_LORA = 32  # low-rank size of the program's RWKV-6 mixes (rwkv6._LORA)


def _head_dim(m: dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["num_heads"]


def padded_vocab(m: dict) -> int:
    return (m["vocab_size"] + 255) // 256 * 256


def dense_matmul_params(m: dict) -> int:
    """Weights a dense GQA decoder multiplies by per token: the attention
    projections and the SwiGLU MLP of every layer, and the output head
    over the published vocabulary (the embedding lookup multiplies
    nothing, and the padded rows of the head are waste, not work)."""
    d, hd = m["d_model"], _head_dim(m)
    attn = d * hd * (m["num_heads"] + 2 * m["num_kv_heads"]) + m["num_heads"] * hd * d
    mlp = 3 * d * m["d_ff"]
    return m["num_layers"] * (attn + mlp) + d * m["vocab_size"]


def dense_train_flops_per_token(m: dict, seq: int) -> float:
    """Useful training FLOPs per token of a (batch, ``seq``) causal
    sequence: 6·N for the matmuls, forward and backward, plus attention's
    scores and weighted sum. Those are 2 matmuls of 2·hq·hd FLOPs per
    (query, key) pair; causal attention has seq·(seq+1)/2 pairs, so per
    token (seq+1)/2 of them; backward costs twice forward: 3 × 4 ×
    hq·hd·(seq+1)/2 = 6·hq·hd·(seq+1) per layer."""
    attn = 6.0 * m["num_heads"] * _head_dim(m) * (seq + 1) * m["num_layers"]
    return 6.0 * dense_matmul_params(m) + attn


def flash_forward_cost(m: dict, batch: int, seq: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one causal forward flash-attention call over
    q (batch, seq, hq, hd) and k, v (batch, seq, hkv, hd) in bf16: the
    causal pairs' two matmuls, and q, k, v read and the output written
    once each."""
    hq, hkv, hd = m["num_heads"], m["num_kv_heads"], _head_dim(m)
    pairs = seq * (seq + 1) / 2
    flops = 4.0 * batch * hq * hd * pairs
    nbytes = BF16 * batch * seq * hd * (2 * hq + 2 * hkv)
    return flops, float(nbytes)


def param_leaf_sizes(m: dict) -> list[int]:
    """Element count of each parameter leaf of a dense GQA decoder as the
    program stacks it: embed, final norm, the head unless it is tied to
    the embedding, and one stacked leaf per layer weight (norms, q, k, v,
    o, MLP in, gate, out)."""
    d, hd, L, v = m["d_model"], _head_dim(m), m["num_layers"], padded_vocab(m)
    hq, hkv, f = m["num_heads"], m["num_kv_heads"], m["d_ff"]
    head = [] if m["tie_embeddings"] else [d * v]
    return [v * d, d, *head,
            L * d, L * d * hq * hd, L * d * hkv * hd, L * d * hkv * hd,
            L * hq * hd * d, L * d, L * d * f, L * d * f, L * f * d]


def int8_decode_apply_bytes(n: int) -> float:
    """HBM bytes of the fused decode + commit apply over one leaf of ``n``
    elements: bf16 params read and written, the bf16 commit delta read
    and written, the int8 update read, and its f32 scale."""
    return float(n * (BF16 + BF16 + BF16 + BF16 + INT8) + F32)


def rwkv_layer_params(m: dict) -> int:
    """Weights of one RWKV-6 block as the program builds it."""
    d, f = m["d_model"], m["d_ff"]
    time = 5 * d * d + d * _RWKV_LORA + _RWKV_LORA * 5 * d + d * _RWKV_LORA + _RWKV_LORA * d
    chan = d * f + d * d + f * d
    return time + chan


def rwkv_decode_step_cost(m: dict, slots: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one pool-wide decode step of ``slots`` rows.

    Bytes: every bf16 weight of the blocks and the head read once, the
    embedding rows of the step's tokens, and each slot's recurrent state
    read and written: per layer the (heads, N, N) float32 WKV matrix and
    the two float32 token-shift vectors. FLOPs: 2 per weight per row,
    and the WKV update (kᵀv, decay, read-out: about 4·d·N per row and
    layer)."""
    d, L, n = m["d_model"], m["num_layers"], m["rwkv_head_dim"]
    v = padded_vocab(m)
    weights = L * rwkv_layer_params(m) + d * v
    state = L * (d * n + 2 * d) * F32
    nbytes = BF16 * (weights + slots * d) + 2 * slots * state
    flops = 2.0 * slots * weights + 4.0 * slots * L * d * n
    return flops, float(nbytes)


def roofline_seconds(flops: float, nbytes: float, peak: dict,
                     flops_key: str = "bf16_flops_per_s") -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_c = flops / peak[flops_key]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
