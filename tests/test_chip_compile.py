"""Every Pallas kernel compiles for a described TPU v5e at real widths.

Interpret mode (the CPU path of tests/test_kernels.py) cannot see what
the chip's compiler refuses: block shapes off the (8, 128) tiling, a
dynamic index on a loaded value, more VMEM than a kernel may hold. These
cases hand the TPU compiler each kernel at the widths of the model that
runs it and assert that the Pallas call survived as ``tpu_custom_call``.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and every test worker
imports every test file.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

GRANITE = dict(s=4096, hq=32, hkv=8, d=128)  # granite-3-8b attention
RWKV = dict(s=512, h=40, n=64)  # rwkv6-3b: 40 heads × 64
RGLRU = dict(s=2048, w=4096)  # recurrentgemma-9b lru_width
COMMIT = (4096, 12800)  # granite-3-8b d_model × d_ff, one MLP leaf


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip cannot be read back from the
    # persistent cache without one; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means: no TPU compiler here
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _compile(fn, *args, **static):
    return fn.lower(*args, interpret=False, **static).compile()


def _cases(sh):
    g, r, lru, m = GRANITE, RWKV, RGLRU, COMMIT
    f32, bf16 = "float32", "bfloat16"
    scalar = _spec(sh, (), f32)
    return {
        "flash_attention": (ops.flash_attention, (
            _spec(sh, (1, g["s"], g["hq"], g["d"]), bf16),
            _spec(sh, (1, g["s"], g["hkv"], g["d"]), bf16),
            _spec(sh, (1, g["s"], g["hkv"], g["d"]), bf16),
        )),
        "rwkv6_scan": (ops.rwkv6_scan, (
            *(_spec(sh, (1, r["s"], r["h"], r["n"]), bf16) for _ in range(3)),
            _spec(sh, (1, r["s"], r["h"], r["n"]), f32),
            _spec(sh, (r["h"], r["n"]), f32),
        )),
        "rglru_scan": (ops.rglru_scan, (
            _spec(sh, (1, lru["s"], lru["w"]), f32),
            _spec(sh, (1, lru["s"], lru["w"]), f32),
        )),
        "quantize_int8_ef": (ops.quantize_int8_ef, (
            _spec(sh, m, bf16), _spec(sh, m, f32), scalar)),
        "encode_bf16_ef": (ops.encode_bf16_ef, (
            _spec(sh, m, f32), _spec(sh, m, f32))),
        "int8_decode_apply": (ops.int8_decode_apply, (
            _spec(sh, m, bf16), _spec(sh, m, bf16), _spec(sh, m, "int8"),
            scalar, scalar, scalar)),
        "bf16_decode_apply": (ops.bf16_decode_apply, (
            _spec(sh, m, bf16), _spec(sh, m, bf16), _spec(sh, m, bf16),
            scalar, scalar)),
        "accumulate_tree": (ops.accumulate_tree, (
            {"w": _spec(sh, m, bf16)}, {"w": _spec(sh, m, bf16)}, scalar)),
        "ps_apply_tree": (ops.ps_apply_tree, (
            {"w": _spec(sh, m, bf16)}, {"w": _spec(sh, m, bf16)},
            {"w": _spec(sh, m, bf16)}, scalar, scalar)),
    }


@pytest.mark.parametrize("name", [
    "flash_attention", "rwkv6_scan", "rglru_scan", "quantize_int8_ef",
    "encode_bf16_ef", "int8_decode_apply", "bf16_decode_apply",
    "accumulate_tree", "ps_apply_tree",
])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _cases(one_chip)[name]
    hlo = _compile(fn, *args).as_text()
    assert "tpu_custom_call" in hlo, f"{name}: the Pallas call did not survive"


# ---------------------------------------------------------------------------
# the ADSP update kernels over the training cell's whole leaf tree: each
# call is built over the leaf as it lies and writes the state it updates
# over the donated buffer, so nothing but the kernels and bitcasts may
# touch a leaf-sized buffer
# ---------------------------------------------------------------------------

# granite3-8b-l3's 12 leaves as the program stacks them (tests/
# test_leaf_tiling.py checks them against the model), and one of four
# shards of an MLP leaf as ``make_sharded_apply`` hands it over
CELL_LEAVES = [(49408, 4096), (4096,), (3, 4096, 8, 128), (3, 4096, 4096),
               (3, 4096, 32, 128), (3, 4096, 8, 128), (3, 4096, 12800),
               (3, 4096, 12800), (3, 12800, 4096), (3, 4096), (3, 4096),
               (4096, 49408), (3, 4096, 3200)]
_BYTES = {"bf16": 2, "f32": 4, "s8": 1, "u8": 1, "pred": 1, "s32": 4, "u32": 4}
_LEAF_OPS = {"parameter", "custom-call", "bitcast", "get-tuple-element", "tuple"}


def _tree_cases(sh):
    def leaves(dtype):
        return [_spec(sh, s, dtype) for s in CELL_LEAVES]

    # the state a kernel updates is donated, as the train step donates it,
    # and comes back in its own order (jit pairs donated buffers with
    # outputs by shape, in order)
    scalar = _spec(sh, (), "float32")
    return {
        "accumulate_tree": (
            jax.jit(lambda u, g, lr: ops.accumulate_tree(u, g, lr, interpret=False),
                    donate_argnums=0),
            (leaves("bfloat16"), leaves("bfloat16"), scalar)),
        "quantize_int8_ef": (
            jax.jit(lambda us, rs, s: [ops.quantize_int8_ef(u, r, s, interpret=False)
                                       for u, r in zip(us, rs)], donate_argnums=1),
            (leaves("bfloat16"), leaves("float32"), scalar)),
        "int8_decode_apply": (  # (new ws, new ds): each takes its own buffer
            jax.jit(lambda ws, ds, qs, s, lr, mu: tuple(zip(*[
                ops.int8_decode_apply(w, d, q, s, lr, mu, interpret=False)
                for w, d, q in zip(ws, ds, qs)])), donate_argnums=(0, 1)),
            (leaves("bfloat16"), leaves("bfloat16"), leaves("int8"),
             scalar, scalar, scalar)),
    }


def _large_non_kernel_ops(hlo: str, floor: int = 1 << 20) -> list[str]:
    """Instructions of the entry computation whose result holds at least
    ``floor`` bytes and that are neither a kernel nor a bitcast."""
    entry = hlo[hlo.index("\nENTRY"):]
    entry = entry[: entry.index("\n}")]
    bad = []
    for line in entry.splitlines()[1:]:
        m = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) ([a-z][a-z0-9-]*)\(", line)
        if not m or m.group(2) in _LEAF_OPS:
            continue
        size = sum(_BYTES.get(t, 4) * math.prod(int(n) for n in dims.split(",") if n)
                   for t, dims in re.findall(r"([a-z]+\d*)\[([\d,]*)\]", m.group(1)))
        if size >= floor:
            bad.append(line.strip()[:160])
    return bad


@pytest.mark.parametrize("name", ["accumulate_tree", "quantize_int8_ef", "int8_decode_apply"])
def test_update_kernels_take_leaves_as_they_lie(one_chip, name):
    fn, args = _tree_cases(one_chip)[name]
    hlo = fn.lower(*args).compile().as_text()
    assert hlo.count("custom_call_target=\"tpu_custom_call\"") == len(CELL_LEAVES)
    assert _large_non_kernel_ops(hlo) == []
