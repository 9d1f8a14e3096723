"""Serving subsystem (repro.serve, DESIGN.md §14): traces, slot pool,
continuous-batching engine, PS sync, metrics round-trip, launcher
regressions.

The behaviors pinned here:

  * open-loop traces are seeded/deterministic and respect their bounds;
  * the engine completes every request of a trace (continuous AND
    static modes), generating exactly ``max_new`` tokens (or stopping
    at EOS), with eviction + backfill reusing slots;
  * EDF admission reorders a queue that FCFS would serve
    arrival-first;
  * static rebatching never backfills mid-batch (inserts happen only
    when the pool is fully drained);
  * ``ServeRecord``/``PullRecord`` round-trip losslessly through
    to_dict/from_dict and JSONL;
  * ``pull_stale`` pulls exactly the version-stale shards, bit-exact;
  * the one-shot launcher with ``--new-tokens 1`` reports the decode
    loop as skipped instead of fabricating a ms/token figure;
  * ``tools/fleet_report.py`` summarizes a serve stream.
"""

import importlib.util
import json
import math
import pathlib

import jax
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.fleet import (
    JsonlSink,
    MetricsLog,
    PullRecord,
    ServeRecord,
    from_dict,
    load_jsonl,
    to_dict,
)
from repro.launch import serve as serve_launch
from repro.models import lm
from repro.ps.sharding import ShardPlan
from repro.ps.state import AdspState
from repro.serve import (
    CachePool,
    CostModel,
    LoadBalancer,
    ReplicaSync,
    Request,
    ServeConfig,
    ServeEngine,
    ShardedTrainer,
    TraceConfig,
    family_of,
    get_router,
    get_scheduler,
    make_trace,
    pull_stale,
    router_names,
    scheduler_names,
    shard_versions_of,
    trace_names,
)

ARCH = "rwkv6-3b"  # cheapest family on CPU; parity across families is
# pinned separately in test_serve_parity.py


@pytest.fixture(scope="module")
def smoke():
    cfg = get_smoke(ARCH)
    params = lm.lm_init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _trace(**kw):
    defaults = dict(n_requests=10, rate=20.0, prompt_lens=(4, 8),
                    max_new=(2, 6), slo_ms=800.0, seed=1)
    defaults.update(kw)
    return make_trace("poisson", TraceConfig(**defaults))


# ---------------------------------------------------------------------------
# traces
# ---------------------------------------------------------------------------


def test_trace_registry():
    assert set(trace_names()) >= {"poisson", "bursty"}
    with pytest.raises(KeyError):
        make_trace("nope", TraceConfig())


def test_trace_deterministic_and_bounded():
    tc = TraceConfig(n_requests=50, rate=10.0, prompt_lens=(4, 16),
                     max_new=(2, 8), slo_ms=500.0, seed=7)
    for name in ("poisson", "bursty"):
        a, b = make_trace(name, tc), make_trace(name, tc)
        assert a == b
        assert len(a) == 50
        assert [r.rid for r in a] == list(range(50))
        arr = [r.arrival for r in a]
        assert arr == sorted(arr) and arr[0] >= 0.0
        for r in a:
            assert 4 <= r.prompt_len <= 16
            assert 2 <= r.max_new <= 8
            assert r.deadline == pytest.approx(r.arrival + r.slo)
    assert make_trace("poisson", tc) != make_trace(
        "poisson", TraceConfig(**{**tc.__dict__, "seed": 8}))


def test_bursty_trace_is_bursty():
    tc = TraceConfig(n_requests=400, rate=10.0, seed=3,
                     burst_factor=6.0, burst_duty=0.2, burst_period=4.0)
    tr = make_trace("bursty", tc)
    # arrivals concentrate in the burst windows: the densest quarter of
    # each period holds well above its uniform share
    in_burst = sum(1 for r in tr if (r.arrival % 4.0) < 0.8)
    assert in_burst / len(tr) > 0.4


# ---------------------------------------------------------------------------
# cache pool
# ---------------------------------------------------------------------------


def test_family_of():
    assert family_of(get_smoke("rwkv6-3b")) == "rwkv6"
    assert family_of(get_smoke("recurrentgemma-9b")) in ("rglru", "hybrid")
    assert family_of(get_smoke("granite-3-8b")) == "attention"


def test_cache_pool_occupancy(smoke):
    cfg, params = smoke
    pool = CachePool(cfg, 3, 16)
    _, caches = lm.lm_prefill(
        cfg, params, {"tokens": np.zeros((1, 4), np.int32)}, reserve=12)
    assert pool.insert(7, caches) == 0  # LIFO free list → slot 0 first
    assert pool.insert(9, caches) == 1
    assert pool.n_active == 2 and pool.n_free == 1
    with pytest.raises(ValueError):
        pool.insert(7, caches)  # already resident
    assert pool.evict(7) == 0
    assert pool.insert(11, caches) == 0  # freed slot reused
    pool.insert(13, caches)
    with pytest.raises(RuntimeError):
        pool.insert(15, caches)  # full
    nb = pool.slot_nbytes()
    assert nb["recurrent"] > 0  # rwkv6: constant-size state
    assert nb["kv"] == 0


# ---------------------------------------------------------------------------
# schedulers
# ---------------------------------------------------------------------------


def test_scheduler_registry():
    assert set(scheduler_names()) >= {"fcfs", "deadline"}
    with pytest.raises(KeyError):
        get_scheduler("nope")


def test_edf_reorders_fcfs():
    early_arrival_late_deadline = Request(
        rid=0, arrival=0.0, prompt_len=4, max_new=2, slo=10.0)
    late_arrival_tight_deadline = Request(
        rid=1, arrival=0.1, prompt_len=4, max_new=2, slo=0.5)
    queue = [early_arrival_late_deadline, late_arrival_tight_deadline]
    assert get_scheduler("fcfs").pick(queue, 0.2) == 0
    assert get_scheduler("deadline").pick(queue, 0.2) == 1


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------


def test_engine_completes_all_requests(smoke):
    cfg, params = smoke
    trace = _trace()
    log = MetricsLog()
    rep = ServeEngine(cfg, params, ServeConfig(slots=3), trace,
                      metrics=log).run()
    assert len(rep.records) == len(trace)
    assert sorted(rep.tokens_by_rid) == [r.rid for r in trace]
    for r in trace:
        assert len(rep.tokens_by_rid[r.rid]) == r.max_new
    # eviction + backfill actually reused slots (10 requests, 3 slots)
    assert rep.inserts == rep.evictions
    assert rep.inserts > 3
    assert len(log.of("serve")) == len(trace)
    for rec in log.of("serve"):
        assert rec.total == pytest.approx(
            rec.queue + rec.prefill + rec.decode, abs=1e-9)
        assert rec.slo_ok == (rec.total <= 800.0 / 1e3 + 1e-12)
    assert rep.slo_attainment == pytest.approx(
        sum(r.slo_ok for r in rep.records) / len(trace))
    assert rep.goodput > 0 and rep.tokens_per_s > 0


def test_engine_deterministic(smoke):
    cfg, params = smoke
    trace = _trace()
    r1 = ServeEngine(cfg, params, ServeConfig(slots=3), trace).run()
    r2 = ServeEngine(cfg, params, ServeConfig(slots=3), trace).run()
    assert r1.tokens_by_rid == r2.tokens_by_rid
    assert r1.t_end == r2.t_end
    assert [to_dict(a) for a in r1.records] == [to_dict(b) for b in r2.records]


def test_engine_static_mode_no_backfill(smoke):
    cfg, params = smoke
    trace = _trace(n_requests=8, max_new=(2, 8))
    events = []

    class SpyPool(CachePool):
        def insert(self, rid, src):
            events.append(("insert", rid, self.n_active))
            return super().insert(rid, src)

        def evict(self, rid):
            events.append(("evict", rid, self.n_active))
            return super().evict(rid)

    eng = ServeEngine(cfg, params, ServeConfig(slots=3, mode="static"), trace)
    eng.pool = SpyPool(cfg, 3, eng.pool.capacity)
    rep_s = eng.run()
    assert len(rep_s.records) == 8
    # static: inserts happen only in fill runs that start from an empty
    # pool — never as backfill after an eviction mid-batch
    prev = None
    occupancy = 0
    for kind, _, _ in events:
        if kind == "insert":
            assert occupancy == 0 or prev == "insert"
            occupancy += 1
        else:
            occupancy -= 1
        prev = kind
    # continuous on the same trace finishes no later than static
    rep_c = ServeEngine(cfg, params, ServeConfig(slots=3), trace).run()
    assert rep_c.t_end <= rep_s.t_end + 1e-9


def test_engine_eos_evicts_early(smoke):
    cfg, params = smoke
    trace = _trace(n_requests=6, max_new=(8, 8))
    free = ServeEngine(cfg, params, ServeConfig(slots=2), trace).run()
    # pick a token that actually occurs mid-stream so EOS fires
    eos = free.tokens_by_rid[trace[0].rid][2]
    rep = ServeEngine(cfg, params, ServeConfig(slots=2, eos_id=eos), trace).run()
    assert len(rep.records) == 6
    by_rid = {r.req: r for r in rep.records}
    for r in trace:
        toks = rep.tokens_by_rid[r.rid]
        assert len(toks) <= r.max_new
        if len(toks) < r.max_new:
            assert toks[-1] == eos
        assert by_rid[r.rid].tokens == len(toks)
    assert any(len(rep.tokens_by_rid[r.rid]) < r.max_new for r in trace)


def test_engine_rejects_bad_config(smoke):
    cfg, params = smoke
    with pytest.raises(ValueError):
        ServeConfig(slots=0)
    with pytest.raises(ValueError):
        ServeConfig(mode="adaptive")
    with pytest.raises(ValueError):
        ServeEngine(cfg, params, ServeConfig(sync_every=2), _trace())
    with pytest.raises(ValueError):  # capacity below trace requirement
        ServeEngine(cfg, params, ServeConfig(capacity=2), _trace())


def test_cost_model_monotone():
    cm = CostModel()
    assert cm.prefill(32) > cm.prefill(8) > 0
    assert cm.decode(8) > cm.decode(1) > 0


# ---------------------------------------------------------------------------
# metrics round-trip
# ---------------------------------------------------------------------------


def test_serve_records_roundtrip(tmp_path):
    recs = [
        ServeRecord(t=1.25, req=3, queue=0.01, prefill=0.004, decode=0.05,
                    total=0.064, tokens=9, slo=0.8, slo_ok=True, version=12),
        PullRecord(t=1.5, stale_shards=2, n_shards=4, nbytes=1024.0),
    ]
    for r in recs:
        assert from_dict(to_dict(r)) == r
        assert json.loads(json.dumps(to_dict(r))) == to_dict(r)
    path = tmp_path / "serve.jsonl"
    with JsonlSink(path) as sink:
        for r in recs:
            sink.record(r)
    assert load_jsonl(path) == recs


def test_engine_streams_to_jsonl(smoke, tmp_path):
    cfg, params = smoke
    trace = _trace(n_requests=5)
    path = tmp_path / "stream.jsonl"
    with JsonlSink(path) as sink:
        ServeEngine(cfg, params, ServeConfig(slots=2), trace,
                    metrics=sink).run()
    loaded = load_jsonl(path)
    assert len(loaded) == 5
    assert all(r.kind == "serve" for r in loaded)


# ---------------------------------------------------------------------------
# sync
# ---------------------------------------------------------------------------


def _tiny_params(seed=0):
    rng = np.random.default_rng(seed)
    # 4 leaves so a 4-way ShardPlan is actually 4-way (build clamps to
    # the leaf count)
    return {"a": rng.normal(size=(8, 4)).astype(np.float32),
            "b": rng.normal(size=(16,)).astype(np.float32),
            "c": rng.normal(size=(4, 4)).astype(np.float32),
            "d": rng.normal(size=(8,)).astype(np.float32)}


def test_pull_stale_exact_shards():
    params = _tiny_params()
    state = AdspState.create(_tiny_params(1), n_shards=4)
    plan = ShardPlan.build(params, 4)
    versions = np.zeros(4, np.int64)

    p2, stale, nbytes = pull_stale(params, state, plan, versions)
    assert stale == [] and nbytes == 0  # all fresh at version 0

    state.shard_versions = state.shard_versions.at[2].add(1)
    p2, stale, nbytes = pull_stale(params, state, plan, versions)
    assert stale == [2] and nbytes == plan.shard_nbytes()[2]
    assert versions[2] == 1 and versions.sum() == 1
    # pulled shard now bit-equal to PS; untouched shards unchanged
    want = plan.merge(params, 2, plan.slice(state.params, 2))
    for k in params:
        np.testing.assert_array_equal(np.asarray(p2[k]), np.asarray(want[k]))
    # second poll: nothing stale
    _, stale, nbytes = pull_stale(p2, state, plan, versions)
    assert stale == [] and nbytes == 0


def test_shard_versions_of_monolithic():
    state = AdspState.create(_tiny_params())
    state.step = 5
    assert shard_versions_of(state, 1).tolist() == [5]
    with pytest.raises(ValueError):
        shard_versions_of(state, 4)


def test_replica_sync_accounting():
    params = _tiny_params()
    state = AdspState.create(_tiny_params(1), n_shards=2)
    sync = ReplicaSync(params, lambda: state, n_shards=2, bandwidth=1e6)
    p, n, nb, secs = sync.poll(params)
    assert (n, nb, secs) == (0, 0, 0.0)
    state.shard_versions = state.shard_versions.at[0].add(1)
    p, n, nb, secs = sync.poll(p)
    assert n == 1 and nb == sync.plan.shard_nbytes()[0]
    assert secs == pytest.approx(nb / 1e6)
    assert sync.version == 1
    assert sync.bytes_pulled == nb
    assert sync.full_bytes_equiv == sync.total_nbytes  # dense baseline
    assert sync.polls == 2 and sync.pulls == 1


@pytest.mark.slow
def test_track_training_improves_loss(smoke):
    cfg, params = smoke
    trace = _trace(n_requests=12, rate=30.0, max_new=(3, 8), seed=2)
    trainer = ShardedTrainer(cfg, params, n_shards=4, commit_every=0.05)
    sync = ReplicaSync(params, lambda: trainer.state, n_shards=4)
    log = MetricsLog()
    loss0 = trainer.eval_loss(params)
    eng = ServeEngine(cfg, params, ServeConfig(slots=3, sync_every=2), trace,
                      metrics=log, sync=sync,
                      tick=lambda e, t: trainer.advance(t))
    rep = eng.run()
    assert trainer.eval_loss(eng.params) < loss0
    assert 0 < rep.pull_bytes < rep.full_pull_bytes
    assert len(log.of("pull")) == rep.sync_pulls
    # served versions are non-decreasing over completion order
    versions = [r.version for r in rep.records]
    assert versions == sorted(versions)
    assert versions[-1] > 0


# ---------------------------------------------------------------------------
# launcher regressions
# ---------------------------------------------------------------------------


def test_oneshot_new_tokens_1_skips_decode(capsys):
    stats = serve_launch.main([
        "--arch", ARCH, "--smoke", "--batch", "2",
        "--prompt-len", "8", "--new-tokens", "1"])
    out = capsys.readouterr().out
    assert stats["n_decoded"] == 0
    assert stats["decode_ms_per_token"] is None
    assert stats["decode_tok_s"] is None
    assert stats["generated"].shape == (2, 1)
    assert "skipped" in out
    assert "ms/token" not in out


def test_oneshot_decode_counts_exclude_prefill_token(capsys):
    stats = serve_launch.main([
        "--arch", ARCH, "--smoke", "--batch", "2",
        "--prompt-len", "8", "--new-tokens", "4"])
    capsys.readouterr()
    assert stats["n_decoded"] == 3  # first token came from prefill
    assert stats["generated"].shape == (2, 4)
    assert stats["decode_tok_s"] == pytest.approx(
        2 * 3 / stats["t_decode"], rel=1e-6)


def test_launcher_engine_mode(capsys, tmp_path):
    path = tmp_path / "m.jsonl"
    out = serve_launch.main([
        "--arch", ARCH, "--smoke", "--trace", "poisson",
        "--requests", "5", "--rate", "20", "--slots", "2",
        "--scheduler", "deadline", "--slo-ms", "800",
        "--metrics", str(path)])
    text = capsys.readouterr().out
    assert len(out["report"].records) == 5
    assert "SLO attainment" in text
    recs = load_jsonl(path)
    assert sum(r.kind == "serve" for r in recs) == 5
    # the engine's host spans follow: one admission wait per request
    admits = [r for r in recs if r.kind == "span" and r.name == "serve.admit"]
    assert sorted(r.key for r in admits) == sorted(r.req for r in out["report"].records)


# ---------------------------------------------------------------------------
# fleet_report serve summary
# ---------------------------------------------------------------------------


def _load_fleet_report():
    spec = importlib.util.spec_from_file_location(
        "fleet_report",
        pathlib.Path(__file__).resolve().parent.parent / "tools" / "fleet_report.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_fleet_report_serve_summary(smoke):
    cfg, params = smoke
    trace = _trace(n_requests=6)
    trainer = ShardedTrainer(cfg, params, n_shards=2, commit_every=0.05)
    sync = ReplicaSync(params, lambda: trainer.state, n_shards=2)
    log = MetricsLog()
    ServeEngine(cfg, params, ServeConfig(slots=2, sync_every=1), trace,
                metrics=log, sync=sync,
                tick=lambda e, t: trainer.advance(t)).run()
    fr = _load_fleet_report()
    s = fr.summarize(log.records)
    assert s["serve"]["requests"] == 6
    assert s["serve"]["tokens"] == sum(
        r.tokens for r in log.of("serve"))
    assert s["serve"]["slo_ok"] <= 6
    assert s["pulls"]["polls"] == len(log.of("pull"))
    assert s["pulls"]["n_shards"] == 2 or s["pulls"]["polls"] == 0
    report = fr.format_report(s)
    assert "serving: 6 requests" in report
    assert "SLO attainment" in report
    assert math.isfinite(s["serve"]["t_last"])


# ---------------------------------------------------------------------------
# chunked prefill (§17)
# ---------------------------------------------------------------------------


def test_chunked_config_validation(smoke):
    cfg, params = smoke
    with pytest.raises(ValueError):
        ServeConfig(prefill_chunk=-1)
    with pytest.raises(ValueError):
        ServeConfig(prefill_batch=0)
    with pytest.raises(ValueError):  # static mode cannot interleave
        ServeConfig(mode="static", prefill_chunk=4)


def test_cost_model_chunk_pricing():
    """One dispatch over all lanes pays the base once (the batching
    win); m chunks pay it m times (the interleaving price)."""
    cm = CostModel()
    plen = 32
    assert cm.chunk(plen) == pytest.approx(cm.prefill(plen))
    two_chunks = cm.chunk(16) + cm.chunk(16)
    assert two_chunks == pytest.approx(cm.prefill(32) + cm.prefill_base)
    # batched: two 16-token prompts in one dispatch cost one base
    assert cm.chunk(32) < cm.prefill(16) + cm.prefill(16)
    cheap = CostModel(chunk_base=1e-4)
    assert cheap.chunk(16) < cheap.prefill(16)


def test_chunked_engine_matches_monolithic_tokens(smoke):
    """Chunked prefill changes *when* work happens, never the tokens:
    same trace, same streams, and a chunk-dispatch count that reflects
    ceil(plen / chunk) per request (minus batching overlap)."""
    cfg, params = smoke
    trace = _trace(n_requests=8, prompt_lens=(4, 8, 13), rate=40.0)
    mono = ServeEngine(cfg, params, ServeConfig(slots=3), trace).run()
    eng = ServeEngine(cfg, params, ServeConfig(
        slots=3, prefill_chunk=4, prefill_batch=2), trace)
    chunked = eng.run()
    assert chunked.tokens_by_rid == mono.tokens_by_rid
    assert len(chunked.records) == len(trace)
    assert chunked.chunk_dispatches > 0 and mono.chunk_dispatches == 0
    for rec in chunked.records:
        assert rec.total == pytest.approx(
            rec.queue + rec.prefill + rec.decode, abs=1e-9)


def test_chunked_engine_deterministic(smoke):
    cfg, params = smoke
    trace = _trace(n_requests=8, prompt_lens=(4, 13), rate=40.0)
    sc = ServeConfig(slots=2, prefill_chunk=4, prefill_batch=2)
    r1 = ServeEngine(cfg, params, sc, trace).run()
    r2 = ServeEngine(cfg, params, sc, trace).run()
    assert [to_dict(a) for a in r1.records] == [to_dict(b) for b in r2.records]
    assert r1.t_end == r2.t_end


def test_prefill_jit_cache_buckets_by_pow2(smoke):
    """Monolithic prefill dispatches are jit-cached by the prompt length
    rounded up to a power of two — a trace with many distinct lengths
    compiles one fn per *bucket*, not one per length."""
    cfg, params = smoke
    lens = (3, 4, 5, 6, 7, 8, 9, 12, 13, 15)
    trace = _trace(n_requests=20, prompt_lens=lens, rate=40.0)
    eng = ServeEngine(cfg, params, ServeConfig(slots=3), trace)
    eng.run()
    seen = {r.prompt_len for r in trace}
    buckets = {1 << (n - 1).bit_length() if n > 1 else 1 for n in seen}
    assert set(eng._prefill_fns) == buckets
    assert len(eng._prefill_fns) < len(seen)


# ---------------------------------------------------------------------------
# multi-replica load balancing (§17)
# ---------------------------------------------------------------------------


def test_router_registry():
    assert set(router_names()) >= {"round_robin", "least_queue",
                                   "deadline_slack"}
    with pytest.raises(KeyError):
        get_router("nope")


@pytest.mark.parametrize("router", ["round_robin", "least_queue",
                                    "deadline_slack"])
def test_balancer_deterministic(smoke, router):
    """Same trace + seed ⇒ identical per-request records, with EDF
    honored within each replica (deadline scheduler throughout)."""
    cfg, params = smoke
    trace = _trace(n_requests=10, rate=40.0, slo_ms=(400.0))
    sc = ServeConfig(slots=2, scheduler="deadline", seed=1)
    a = LoadBalancer(cfg, params, sc, trace, n_replicas=2,
                     router=router).run()
    b = LoadBalancer(cfg, params, sc, trace, n_replicas=2,
                     router=router).run()
    assert [to_dict(x) for x in a.merged.records] == \
        [to_dict(x) for x in b.merged.records]
    assert a.merged.t_end == b.merged.t_end
    # every request served exactly once, somewhere
    assert sorted(a.merged.tokens_by_rid) == [r.rid for r in trace]
    assert {r.replica for r in a.merged.records} <= {0, 1}
    assert sum(a.per_replica_requests) == len(trace)
    # per-replica token streams match the single-engine ones (routing
    # never changes a request's tokens, only where/when it runs)
    solo = ServeEngine(cfg, params, sc, trace).run()
    assert a.merged.tokens_by_rid == solo.tokens_by_rid


def test_balancer_round_robin_alternates(smoke):
    cfg, params = smoke
    trace = _trace(n_requests=6, rate=40.0)
    out = LoadBalancer(cfg, params, ServeConfig(slots=2), trace,
                       n_replicas=2, router="round_robin").run()
    by_rid = {r.req: r.replica for r in out.merged.records}
    arrivals = sorted(trace, key=lambda r: (r.arrival, r.rid))
    assert [by_rid[r.rid] for r in arrivals] == [0, 1, 0, 1, 0, 1]


def test_balancer_spreads_load_over_idle_replica(smoke):
    """least_queue routes around a busy replica: a burst of arrivals
    lands on both replicas instead of queueing on one."""
    cfg, params = smoke
    trace = _trace(n_requests=8, rate=200.0)  # near-simultaneous burst
    out = LoadBalancer(cfg, params, ServeConfig(slots=2), trace,
                       n_replicas=2, router="least_queue").run()
    assert min(out.per_replica_requests) >= 2


def test_balancer_rejects_bad_config(smoke):
    cfg, params = smoke
    with pytest.raises(ValueError):
        LoadBalancer(cfg, params, ServeConfig(), _trace(), n_replicas=0)
    with pytest.raises(KeyError):
        LoadBalancer(cfg, params, ServeConfig(), _trace(), router="nope")
    with pytest.raises(ValueError):  # sync_every needs a factory
        LoadBalancer(cfg, params, ServeConfig(sync_every=2), _trace())


def test_fleet_report_per_replica(smoke):
    cfg, params = smoke
    trace = _trace(n_requests=8, rate=40.0)
    log = MetricsLog()
    LoadBalancer(cfg, params, ServeConfig(slots=2), trace, n_replicas=2,
                 router="round_robin", metrics=log).run()
    fr = _load_fleet_report()
    s = fr.summarize(log.records)
    assert s["serve"]["requests"] == 8
    assert set(s["per_replica"]) == {0, 1}
    assert sum(rp["requests"] for rp in s["per_replica"].values()) == 8
    report = fr.format_report(s)
    assert "replica" in report


def test_launcher_balancer_mode(capsys):
    out = serve_launch.main([
        "--arch", ARCH, "--smoke", "--trace", "poisson",
        "--requests", "6", "--rate", "30", "--slots", "2",
        "--replicas", "2", "--router", "least_queue",
        "--prefill-chunk", "4", "--prefill-batch", "2"])
    text = capsys.readouterr().out
    assert len(out["report"].records) == 6
    assert out["balance"] is not None
    assert "router=least_queue" in text
    assert "chunked prefill" in text
