"""Fixtures of the benchmark's CPU tests: the benchmark's own cells,
shrunk to sizes a test run holds (every width cut, the same code paths)."""

import pathlib
import sys
import time

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_MODEL = {
    "dense_gqa": dict(num_layers=2, d_model=128, num_heads=4, num_kv_heads=2, head_dim=32,
                      d_ff=256, vocab_size=512),
    "rwkv6": dict(num_layers=2, d_model=128, num_heads=2, num_kv_heads=2, rwkv_head_dim=64,
                  d_ff=256, vocab_size=512),
}
TINY_TRAFFIC = {
    "adsp_train": dict(seq=64),
    "serve_open_loop": dict(prompt_lens=[8, 16, 32], prompt_weights=[2, 1, 1],
                            max_new=[4, 12], slots=4, rate=4.0),
}


@pytest.fixture
def tiny_cell():
    """``tiny_cell(name, seed)``: the Context of a benchmark cell at a
    CPU test's size, with the cell's own correctness limits."""
    from chipbench import harness

    def make(name, seed=20_250_101, seconds=1.0):
        ctx = harness.load_cell(name, seed=seed, seconds=seconds, trace=False,
                                t_start=time.perf_counter())
        ctx.config["model"].update(TINY_MODEL[ctx.config["reference"]])
        ctx.traffic.update(TINY_TRAFFIC[ctx.traffic["driver"]])
        return ctx

    return make
