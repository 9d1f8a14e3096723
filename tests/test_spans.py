"""Host spans of ``repro.fleet.metrics`` (DESIGN.md §13): the recorder is
on only under a profiler session or an open ``span_stream``; a span is
one more record kind of the metrics stream, with a profiler twin of the
same name; and the span sites of the serving engine and the ADSP round
loop record what their metrics read, without changing a token."""

import glob
import importlib.util
import json
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.dynamic import validate_records
from repro.cluster import ADSP, ClusterEngine
from repro.cluster.mesh_backend import MeshBackend, MeshTask
from repro.configs import get_smoke
from repro.fleet import (
    EvalRecord,
    JsonlSink,
    MetricsLog,
    ServeRecord,
    SpanRecord,
    from_dict,
    load_jsonl,
    to_dict,
)
from repro.fleet import metrics
from repro.models import lm
from repro.serve import Request, ServeConfig, ServeEngine

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(autouse=True)
def fresh_log():
    metrics.clear_spans()
    yield
    metrics.clear_spans()


def _names(spans):
    return [s.name for s in spans]


def test_nothing_is_recorded_without_profiler_or_stream():
    assert not metrics.recording()
    with metrics.span("a", 1, t=2.0, x=3) as sp:
        sp.set(y=4)
        with metrics.span("b"):
            pass
    metrics.event("c", 0)
    assert metrics.stamp() is None
    assert metrics.recorded_spans() == []


def test_stream_records_nesting_counts_and_writes_at_close(tmp_path):
    path = tmp_path / "s.jsonl"
    with JsonlSink(path) as sink:
        sink.record(EvalRecord(t=1.0, loss=0.5))
        with metrics.span_stream(sink):
            assert metrics.recording()
            with metrics.span("outer", 7, t=1.5, slots=2) as sp:
                t0 = metrics.stamp()
                with metrics.span("inner", 7):
                    pass
                metrics.event("waited", t0, 7, t=1.5)
                sp.set(action="decode")
        assert not metrics.recording()
        with metrics.span("after"):
            pass
    recs = load_jsonl(path)
    assert recs[0] == EvalRecord(t=1.0, loss=0.5)
    spans = recs[1:]
    assert sorted(_names(spans)) == ["inner", "outer", "waited"]
    by = {s.name: s for s in spans}
    assert by["inner"].parent == by["outer"].id == by["waited"].parent
    assert by["outer"].parent is None and by["outer"].t == 1.5
    assert by["outer"].counts == {"slots": 2, "action": "decode"}
    assert by["outer"].start_ns <= by["inner"].start_ns <= by["inner"].end_ns \
        <= by["outer"].end_ns
    assert {s.key for s in spans} == {7}


def test_compiles_inside_a_span_become_compile_spans():
    log = MetricsLog()
    f = jax.jit(lambda x: x * 3 + 1)
    with metrics.span_stream(log):
        with metrics.span("outer"):
            f(jnp.arange(5.0)).block_until_ready()
    outer = next(s for s in log.records if s.name == "outer")
    compiles = [s for s in log.records if s.name == "compile"]
    assert {s.counts["stage"] for s in compiles} == {"trace", "lower", "backend"}
    assert all(s.parent == outer.id for s in compiles)
    assert all(outer.start_ns <= s.start_ns <= s.end_ns <= outer.end_ns for s in compiles)


def test_span_record_round_trips(tmp_path):
    rec = SpanRecord(t=3.0, name="serve.step", start_ns=10, end_ns=25, id=4, parent=2,
                     key=9, counts={"action": "prefill", "slots": 3})
    assert rec.duration_ns == 15
    assert from_dict(json.loads(json.dumps(to_dict(rec)))) == rec
    with JsonlSink(tmp_path / "r.jsonl") as sink:
        sink.record(rec)
    assert load_jsonl(tmp_path / "r.jsonl") == [rec]


def test_validator_and_fleet_report_accept_spans():
    """Spans join a stream as its run ends, stamped with the virtual time
    they opened at: behind the stream's clock, and no violation."""
    recs = [
        ServeRecord(t=1.0, req=0, queue=0.0, prefill=0.1, decode=0.2, total=0.3,
                    tokens=4, slo=1.0, slo_ok=True, version=0),
        ServeRecord(t=2.0, req=1, queue=0.0, prefill=0.1, decode=0.2, total=0.3,
                    tokens=4, slo=1.0, slo_ok=True, version=0),
        SpanRecord(t=0.5, name="serve.step", start_ns=0, end_ns=2_000_000, id=1),
        SpanRecord(t=0.5, name="serve.fetch", start_ns=10, end_ns=20, id=2, parent=1),
    ]
    assert validate_records(recs) == []
    spec = importlib.util.spec_from_file_location("fleet_report",
                                                  REPO / "tools" / "fleet_report.py")
    fr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fr)
    s = fr.summarize(recs)
    assert s["t_end"] == 2.0 and s["serve"]["requests"] == 2
    assert "serving: 2 requests" in fr.format_report(s)


def test_profiler_twin_has_the_same_name_and_duration(tmp_path):
    from jax.profiler import ProfileData

    x = jnp.ones((128, 128))
    f = jax.jit(lambda a: (a @ a).sum())
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        assert metrics.recording()
        with metrics.span("twin.check", 1):
            f(x).block_until_ready()
    assert not metrics.recording()
    (rec,) = [s for s in metrics.recorded_spans() if s.name == "twin.check"]
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)[0]
    twins = [e for p in ProfileData.from_file(path).planes for line in p.lines
             for e in line.events if e.name == "twin.check"]
    assert len(twins) == 1
    assert abs(twins[0].duration_ns - rec.duration_ns) <= 500_000


# ---------------------------------------------------------------------------
# span sites
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def smoke():
    cfg = get_smoke("rwkv6-3b")
    return cfg, lm.lm_init(jax.random.PRNGKey(0), cfg)


def _serve(cfg, params, chunk, trace_dir=None):
    """A five-request run; under the profiler with ``trace_dir``."""
    reqs = [Request(rid=i, arrival=0.01 * i, prompt_len=n, max_new=m, slo=1.0)
            for i, (n, m) in enumerate([(5, 3), (12, 4), (3, 2), (9, 5), (7, 3)])]
    scfg = ServeConfig(slots=2, prefill_chunk=chunk, prefill_batch=2 if chunk else 1)
    engine = ServeEngine(cfg, params, scfg, reqs)
    if trace_dir is None:
        return reqs, engine.run()
    with jax.profiler.trace(str(trace_dir)):
        return reqs, engine.run()


@pytest.mark.parametrize("chunk", [0, 4], ids=["monolithic", "chunked"])
def test_serve_engine_spans(smoke, chunk, tmp_path):
    cfg, params = smoke
    reqs, report = _serve(cfg, params, chunk, tmp_path)
    spans = metrics.recorded_spans()
    _, plain = _serve(cfg, params, chunk)
    assert metrics.recorded_spans() == spans
    assert report.tokens_by_rid == plain.tokens_by_rid

    admits = [s for s in spans if s.name == "serve.admit"]
    assert sorted(s.key for s in admits) == [r.rid for r in reqs]
    steps = [s for s in spans if s.name == "serve.step"]
    prefills = [s for s in spans if s.name == "serve.prefill"]
    actions = report.decode_steps + (report.chunk_dispatches if chunk else len(prefills))
    if chunk:  # a chunk riding a decode step is one action
        actions -= sum(s.counts["action"] == "chunk+decode" for s in steps)
    assert len(steps) == actions
    assert sum(s.counts["valid"] for s in prefills) == sum(r.prompt_len for r in reqs)
    assert all(s.counts["padded"] >= s.counts["valid"] for s in prefills)
    step_ids = {s.id for s in steps}
    inside = [s for s in spans if s.name in ("serve.dispatch", "serve.fetch",
                                              "serve.insert", "serve.evict")]
    assert inside and all(s.parent in step_ids for s in inside)
    programs = {s.counts["program"] for s in spans if s.name == "serve.dispatch"}
    assert programs == ({"chunk", "decode"} if chunk else {"prefill_bucket", "decode"})
    assert sum(s.name == "serve.evict" and s.counts.get("pool") != "lanes"
               for s in spans) == len(reqs)
    assert steps[-1].counts["has_work"] == 0


def _quad_task(batch: int = 8) -> MeshTask:
    def loss_fn(params, mb):
        x, y = mb
        return jnp.mean((x @ params["w"] - y) ** 2)

    def make_microbatches(round_idx, tau, n_workers):
        r = np.random.default_rng(round_idx)
        x = r.normal(size=(tau, batch, 4)).astype(np.float32)
        return jnp.asarray(x), jnp.asarray(x.sum(-1, keepdims=True))

    return MeshTask(init_params={"w": jnp.zeros((4, 1), jnp.float32)},
                    loss_fn=loss_fn, make_microbatches=make_microbatches)


def test_mesh_backend_round_spans():
    mesh = jax.make_mesh((1,), ("data",))
    backend = MeshBackend(_quad_task(), mesh, worker_axes=("data",), tau=3,
                          batch_spec=jax.sharding.PartitionSpec(None, "data"))
    policy = ADSP(search=False, gamma=2.0)
    ClusterEngine(policy, backend)
    log = MetricsLog()
    with metrics.span_stream(log):
        backend.train(rounds=4, check_period=policy.gamma)
    spans = log.records
    rounds = [s for s in spans if s.name == "adsp.round"]
    assert sorted(s.key for s in rounds) == [0, 1, 2, 3]
    assert sum(s.counts["tau"] for s in rounds) == backend.workers[0].steps
    for r in rounds:
        kids = {s.name for s in spans if s.parent == r.id}
        assert {"adsp.data", "adsp.dispatch", "adsp.sync", "adsp.control"} <= kids
    # the checkpoints every Γ run outside the rounds, in the control plane
    outside = [s for s in spans if s.name == "adsp.control" and s.parent is None]
    assert len(outside) == 2
