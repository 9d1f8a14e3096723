"""The reduction from trace intervals to the per-layer numbers."""

import jax
import pytest

from chipbench import tracing
from chipbench.tracing import Trace

MS = 1_000_000  # ns


@pytest.fixture
def trace():
    """A window of 100 ms on two devices. Device 0: a matmul 0–40, a
    collective 40–60, flash_attention.3 70–80, flash_attention.12 85–90.
    Device 1: one op 10–30 and an all-reduce-done 50–55. Host spans name
    two gaps."""
    ops = {
        0: [("fusion.1", 0, 40 * MS), ("all-reduce.7", 40 * MS, 60 * MS),
            ("flash_attention.3", 70 * MS, 80 * MS), ("flash_attention.12", 85 * MS, 90 * MS)],
        1: [("convolution.4", 10 * MS, 30 * MS), ("all-reduce-done.2", 50 * MS, 55 * MS)],
    }
    modules = {0: [("jit__decode_fn", 0, 60 * MS), ("jit_other", 70 * MS, 90 * MS)]}
    host = [("bench.window", 0, 100 * MS), ("bench.make_batch", 58 * MS, 72 * MS),
            ("bench.stamp", 89 * MS, 100 * MS)]
    return Trace(ops=ops, modules=modules, host=host, window=(0, 100 * MS))


def test_busy_and_idle(trace):
    assert tracing.busy_ns(trace, 0) == 75 * MS  # 0–60, 70–80, 85–90
    assert tracing.busy_ns(trace, 1) == 25 * MS
    assert tracing.busy_ns(trace, 7) == 0


def test_busy_clips_to_window(trace):
    trace.window = (20 * MS, 75 * MS)
    assert tracing.busy_ns(trace, 0) == 45 * MS  # 20–60, 70–75


def test_kernel_events_by_name(trace):
    evs = tracing.ops_named(trace, "flash_attention")
    assert len(evs) == 2 and sum(e - s for _, s, e in evs) == 15 * MS
    assert tracing.ops_named(trace, "flash") == []
    mods = tracing.ops_named(trace, "jit__decode_fn", lines="modules")
    assert [m[0] for m in mods] == ["jit__decode_fn"]


def test_exposed_collectives(trace):
    assert tracing.exposed_collective_ns(trace, 0) == 20 * MS
    assert tracing.exposed_collective_ns(trace, 1) == 5 * MS


def test_idle_gaps_named_by_host_span(trace):
    gaps = tracing.idle_gaps(trace, 0)
    assert gaps[0] == ["bench.make_batch", 0.01]  # 60–70
    assert gaps[1] == ["bench.stamp", 0.01]  # 90–100
    assert gaps[2] == ["host", 0.005]  # 80–85: the stamp span covers 1 ms less
    assert len(gaps) == 3


def test_top_ops_strip_suffixes(trace):
    top = dict(tracing.top_ops(trace))
    assert top["flash_attention"] == pytest.approx(0.015)
    assert top["fusion"] == pytest.approx(0.04)
    assert top["all-reduce"] == pytest.approx(0.02)
    assert list(top)[0] == "fusion"


def test_load_finds_the_window_of_a_recorded_trace(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jax.numpy.ones((64, 64))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
    t = tracing.load(str(tmp_path))
    assert t.window_ns > 0
    names = {h[0] for h in t.host}
    assert {"bench.window", "bench.step"} <= names
    step = next(h for h in t.host if h[0] == "bench.step")
    assert t.window[0] <= step[1] <= step[2] <= t.window[1]


def test_load_refuses_a_trace_without_window(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        jax.numpy.ones(3).block_until_ready()
    with pytest.raises(ValueError, match="bench.window"):
        tracing.load(str(tmp_path))


def test_instruction_names_from_event_text():
    assert tracing.instruction(
        "%flash_attention.3 = bf16[1,32,2048,128]{3,2,1,0} custom-call(bf16[1] %a)") \
        == "flash_attention.3"
    assert tracing.instruction("%while.263 = (s32[], bf16[3]) while((s32[]) %t)") == "while.263"
    assert tracing.instruction("jit__decode_fn(12547892574248260682)") == "jit__decode_fn"


def test_a_loop_counts_through_its_body(trace):
    """A while op holding two body ops: busy counts its whole span, the
    top ops and the exposed collectives only its body."""
    trace.ops[2] = [("while.5", 0, 50 * MS), ("fusion.9", 5 * MS, 15 * MS),
                    ("all-reduce.1", 20 * MS, 30 * MS)]
    assert [x[0] for x in trace.leaves(2)] == ["fusion.9", "all-reduce.1"]
    assert tracing.busy_ns(trace, 2) == 50 * MS
    assert tracing.exposed_collective_ns(trace, 2) == 10 * MS
    assert "while" not in dict(tracing.top_ops(trace))
