"""The per-layer metrics read from the program's own spans
(``repro.fleet.metrics``): each reader on spans built by hand, each
silent where the program records no such span, and each giving a number
from a traced run of its cell's driver at a CPU test's size."""

import glob
import math
import os

import pytest

from chipbench import harness, tracing
from chipbench.tracing import Trace
from repro.fleet import metrics
from repro.fleet.metrics import SpanRecord

TRAIN = "granite3-8b-l3.adsp-1w"
SERVE = "rwkv6-3b.chat-poisson"
READERS = {
    SERVE: ["serve_queue_wait_p90_ms", "serve_host_gap_ms_per_step", "prefill_ms_per_ktok"],
    TRAIN: ["train_host_gap_ms_per_round", "control_host_ms_per_round"],
}
MS = 1_000_000  # ns


def _reader(name):
    return harness.load_module(harness.HERE / "metrics" / f"{name}.py")


def _view(trace=None, **facts):
    trace = trace or Trace(ops={}, modules={}, host=[], window=(0, 10**12))
    return harness.LayerView(trace=trace, facts=facts, peak={}, chips=1, traffic={})


class _Ids:
    def __init__(self):
        self.n = 0

    def __call__(self, name, start_ms, end_ms, parent=None, key=None, **counts):
        self.n += 1
        return SpanRecord(t=0.0, name=name, start_ns=int(start_ms * MS),
                          end_ns=int(end_ms * MS), id=self.n,
                          parent=None if parent is None else parent.id, key=key,
                          counts=counts)


@pytest.fixture
def given(monkeypatch):
    """``given(spans)``: the spans the readers find recorded."""
    def put(spans):
        monkeypatch.setattr(metrics, "recorded_spans", lambda: list(spans))
    return put


@pytest.fixture(autouse=True)
def fresh_log():
    metrics.clear_spans()
    yield
    metrics.clear_spans()


def test_queue_wait_p90_ranks_unadmitted_requests_last(given):
    s = _Ids()
    waits = [s("serve.admit", 0, w, key=i) for i, w in enumerate(range(1, 11))]
    given(waits)
    read = _reader("serve_queue_wait_p90_ms").read
    assert read(_view(requests=10)) == pytest.approx(9.0)
    assert read(_view(requests=11)) == pytest.approx(10.0)
    assert read(_view(requests=12)) == math.inf


def test_host_gap_per_step_skips_an_empty_engine(given):
    s = _Ids()
    a = s("serve.step", 0, 20, has_work=1)
    b = s("serve.step", 21, 40, has_work=0)  # the engine emptied after b
    c = s("serve.step", 100, 120, has_work=1)
    given([a, b, c,
           s("serve.dispatch", 1, 2, parent=a), s("serve.fetch", 10, 18, parent=a),
           s("serve.dispatch", 22, 24, parent=b), s("serve.dispatch", 25, 26, parent=b),
           s("serve.fetch", 30, 31, parent=b), s("serve.fetch", 33, 38, parent=b),
           s("serve.dispatch", 103, 104, parent=c), s("serve.fetch", 110, 115, parent=c)])
    # a -> b only: a's fetch ends at 18, b's first dispatch at 24
    assert _reader("serve_host_gap_ms_per_step").read(_view()) == pytest.approx(6.0)


def test_prefill_ms_per_ktok(given):
    s = _Ids()
    given([s("serve.prefill", 0, 1, key=0, valid=300, padded=512),
           s("serve.prefill", 5, 6, key=1, valid=200, padded=256),
           s("serve.step", 0, 9)])
    trace = Trace(ops={}, host=[], window=(0, 100 * MS),
                  modules={0: [("jit_prefill_bucket", 1 * MS, 4 * MS),
                               ("jit__decode_fn", 4 * MS, 5 * MS),
                               ("jit_prefill_bucket", 6 * MS, 8 * MS)]})
    # 5 ms of prefill programs for 500 valid tokens
    assert _reader("prefill_ms_per_ktok").read(_view(trace)) == pytest.approx(10.0)
    trace.modules = {0: [("jit__decode_fn", 4 * MS, 5 * MS)]}
    assert _reader("prefill_ms_per_ktok").read(_view(trace)) is None


def test_train_host_gap_per_round(given):
    s = _Ids()
    spans = []
    for k, (sync_end, next_dispatch_end) in enumerate([(100, 104), (200, 206)]):
        spans.append(s("adsp.sync", sync_end - 1, sync_end, key=k))
        spans.append(s("adsp.dispatch", next_dispatch_end - 2, next_dispatch_end, key=k + 1))
    given(spans)
    assert _reader("train_host_gap_ms_per_round").read(_view()) == pytest.approx(5.0)


def test_control_self_time_per_round(given):
    s = _Ids()
    r0, r1 = s("adsp.round", 0, 50, key=0), s("adsp.round", 60, 90, key=1)
    c0 = s("adsp.control", 1, 2, parent=r0, key=0)
    ckpt = s("adsp.control", 55, 59, key=0)
    search = s("adsp.control", 92, 130, key=2)  # a probe round inside it
    probe = s("adsp.round", 95, 125, parent=search, key=2)
    given([r0, r1, c0, ckpt, search, probe,
           s("compile", 56, 58, parent=ckpt)])
    # own time: 1 + (4 - 2) + (38 - 30) = 11 ms over 3 rounds
    assert _reader("control_host_ms_per_round").read(_view()) == pytest.approx(11.0 / 3)


@pytest.mark.parametrize("name", sum(READERS.values(), []))
def test_reader_silent_without_its_spans(given, name):
    given([SpanRecord(t=0.0, name="other", start_ns=0, end_ns=1, id=1)])
    assert _reader(name).read(_view(requests=3)) is None


@pytest.mark.parametrize("name", sum(READERS.values(), []))
def test_reader_silent_on_a_program_without_the_recorder(monkeypatch, name):
    monkeypatch.delattr(metrics, "recorded_spans")
    assert _reader(name).read(_view(requests=3)) is None


# ---------------------------------------------------------------------------
# traced runs of the drivers on the CPU
# ---------------------------------------------------------------------------


def _cpu_programs(trace_dir, program):
    """A CPU trace has no device line: the host's dispatch events of the
    jitted ``program`` (``PjitFunction(<name>)``) stand in for its module
    events, named as a TPU names them (``jit_<name>``)."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                  key=os.path.getmtime)[-1]
    want = f"PjitFunction({program})"
    return [(f"jit_{program}", int(e.start_ns), int(e.end_ns))
            for p in ProfileData.from_file(path).planes for line in p.lines
            for e in line.events if e.name == want]


@pytest.mark.parametrize("cell", [TRAIN, SERVE])
def test_traced_run_gives_each_reader_of_its_cell_a_number(tiny_cell, cell):
    ctx = tiny_cell(cell, seconds=2.0)
    ctx.trace = True
    out = ctx.driver().run(ctx)
    try:
        trace = tracing.load(out["trace_dir"])
        if cell == SERVE:
            trace.modules = {0: _cpu_programs(out["trace_dir"], "prefill_bucket")}
    finally:
        harness.cleanup(out["trace_dir"])
    view = harness.LayerView(trace=trace, facts=out["facts"], peak={}, chips=1,
                             traffic=ctx.traffic)
    got = {name: _reader(name).read(view) for name in READERS[cell]}
    assert all(isinstance(v, float) and 0 <= v < math.inf for v in got.values()), got


def test_queue_wait_and_prefill_fit_in_time_to_first_token(tiny_cell):
    from chipbench import traffic_gen
    from chipbench.drivers import serve_open_loop as d

    ctx = tiny_cell(SERVE, seconds=2.0)
    _, engine = d.build(ctx)
    reqs = traffic_gen.requests(ctx.traffic, ctx.seed, ctx.seconds)
    w = d.window(ctx, engine, reqs, trace=True)
    harness.cleanup(w["trace_dir"])
    spans = metrics.recorded_spans()
    waits = {s.key: s.duration_ns / 1e9 for s in spans if s.name == "serve.admit"}
    built = {}
    for s in spans:
        if s.name == "serve.prefill":
            built[s.key] = built.get(s.key, 0.0) + s.duration_ns / 1e9
    served = [r for r in reqs if w["stamps"][r.rid]]
    assert served and len(waits) == len(reqs)
    for r in served:
        ttft = w["stamps"][r.rid][0] - r.arrival  # as summarize computes it
        assert waits[r.rid] + built[r.rid] <= ttft, (r.rid, waits[r.rid], built[r.rid], ttft)
