"""The FLOP and byte counts, against numbers worked by hand for both
configurations (PERF.md shows the same arithmetic)."""

import json

import pytest

from chipbench import flops
from chipbench.harness import HERE


def _model(name):
    return json.loads((HERE / "configs" / f"{name}.json").read_text())["model"]


PEAK = json.loads((HERE / "peaks.json").read_text())["TPU v5 lite"]


def test_granite_train_flops_per_token():
    m = _model("granite3-8b-l3")
    # per layer: q,k,v 4096·128·(32+8+8), o 32·128·4096, MLP 3·4096·12800
    layer = 4096 * 128 * 48 + 4096 * 4096 + 3 * 4096 * 12800
    assert layer == 199_229_440
    assert flops.dense_matmul_params(m) == 3 * layer + 4096 * 49155 == 799_027_200
    # a tied head multiplies like an untied one; the lookup multiplies nothing
    assert flops.dense_matmul_params(dict(m, tie_embeddings=True)) == 799_027_200
    attn = 6 * 32 * 128 * 2049 * 3  # causal pairs per token (S+1)/2, fwd + bwd
    assert flops.dense_train_flops_per_token(m, 2048) == 6 * 799_027_200 + attn == 4_945_231_872


def test_granite_flash_forward_cost():
    f, b = flops.flash_forward_cost(_model("granite3-8b-l3"), 1, 2048)
    assert f == 4 * 32 * 128 * (2048 * 2049 // 2) == 34_376_515_584
    assert b == 2 * 2048 * 128 * (2 * 32 + 2 * 8) == 41_943_040
    t, bound = flops.roofline_seconds(f, b, PEAK)
    assert bound == "compute" and t == pytest.approx(f / 197e12)


def test_granite_commit_bytes():
    m = _model("granite3-8b-l3")
    sizes = flops.param_leaf_sizes(m)
    assert len(sizes) == 12
    assert sum(sizes) == 49408 * 4096 * 2 + 4096 + 3 * (2 * 4096 + 199_229_440) == 1_002_467_328
    # tied, the head is the embedding: one leaf fewer
    assert sum(flops.param_leaf_sizes(dict(m, tie_embeddings=True))) == 1_002_467_328 - 49408 * 4096
    assert flops.int8_decode_apply_bytes(10) == 10 * 9 + 4


def test_rwkv_decode_step_cost():
    m = _model("rwkv6-3b")
    d, f = 2560, 8960
    layer = 5 * d * d + (d * 32 + 32 * 5 * d + d * 32 + 32 * d) + d * f + d * d + f * d
    assert flops.rwkv_layer_params(m) == layer == 85_852_160
    fl, nb = flops.rwkv_decode_step_cost(m, 32)
    weights = 32 * 85_852_160 + 2560 * 65536
    state = 32 * (2560 * 64 + 2 * 2560) * 4  # per slot: WKV + two shift vectors, f32
    assert nb == 2 * (weights + 32 * 2560) + 2 * 32 * state == 7_214_366_720
    assert fl == 2 * 32 * weights + 4 * 32 * 32 * 2560 * 64
    t, bound = flops.roofline_seconds(fl, nb, PEAK)
    assert bound == "memory" and t == pytest.approx(nb / 819e9)
