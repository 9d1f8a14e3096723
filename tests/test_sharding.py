"""The sharded parameter server (repro.ps.sharding + the wiring through
the train step, simulator, and mesh backend).

Key invariants:
  * ShardPlan is deterministic (abstract == concrete builds) and
    size-balanced, and slice/merge round-trips any tree;
  * K=1 is bit-identical to the unsharded train step per granularity,
    and — because every built-in CommitRule is leaf-wise — K>1 matches
    K=1 bit for bit too (sharding reorganizes transport, not numerics);
  * the simulator's partial pulls: a worker with no interleaving writers
    pulls zero bytes, pull bytes are version-gated, push bytes are
    invariant in K, and n_shards=1 runs the exact monolithic code path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_array_equal

from repro.control.theory import WorkerProfile
from repro.cluster import make_policy
from repro.edgesim import SimConfig, Simulator
from repro.edgesim.profiles import ratio_profiles, with_links
from repro.edgesim.tasks import svm_task
from repro.ps import AdspState, CommitConfig, ShardPlan, UpdateRules, make_train_step
from repro.transport import dense_nbytes


# ---------------------------------------------------------------------------
# the plan
# ---------------------------------------------------------------------------

@pytest.fixture()
def tree():
    return {
        "emb": jnp.zeros((100, 8), jnp.float32),
        "blocks": {"w1": jnp.zeros((64, 4), jnp.float32),
                   "w2": jnp.zeros((32, 4), jnp.float32),
                   "b": jnp.zeros((7,), jnp.float32)},
        "head": jnp.zeros((60,), jnp.bfloat16),
    }


def test_plan_deterministic_and_abstract(tree):
    p1 = ShardPlan.build(tree, 3)
    p2 = ShardPlan.build(tree, 3)
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree
    )
    p3 = ShardPlan.build(abstract, 3)
    assert p1 == p2 == p3


def test_plan_k1_is_monolithic(tree):
    p = ShardPlan.build(tree, 1)
    assert p.n_shards == 1
    assert set(p.assignment) == {0}
    assert sum(p.shard_nbytes()) == dense_nbytes(tree)


def test_plan_clamps_to_leaf_count(tree):
    p = ShardPlan.build(tree, 64)
    assert p.n_shards == 5  # one shard per leaf
    assert sorted(p.assignment) == list(range(5))


def test_plan_partitions_every_leaf_once(tree):
    p = ShardPlan.build(tree, 3)
    seen = []
    for k in range(p.n_shards):
        seen.extend(p.shard_leaf_indices(k))
    assert sorted(seen) == list(range(p.n_leaves))
    assert sum(p.shard_nbytes()) == dense_nbytes(tree)


def test_plan_balance(tree):
    p = ShardPlan.build(tree, 2)
    total = sum(p.leaf_nbytes)
    # greedy best-fit bound: no shard exceeds an even split by more
    # than the largest single leaf
    assert max(p.shard_nbytes()) <= total / 2 + max(p.leaf_nbytes)


def test_plan_slice_merge_roundtrip(tree):
    p = ShardPlan.build(tree, 3)
    rebuilt = tree
    for k in range(p.n_shards):
        rebuilt = p.merge(rebuilt, k, p.slice(tree, k))
    for a, b in zip(jax.tree.leaves(rebuilt), jax.tree.leaves(tree)):
        assert a is b  # merge of unchanged slices keeps identities
    # a merge of modified leaves lands exactly on that shard's positions
    bumped = p.merge(tree, 1, [x + 1 for x in p.slice(tree, 1)])
    idx = set(p.shard_leaf_indices(1))
    for i, (a, b) in enumerate(zip(jax.tree.leaves(bumped), jax.tree.leaves(tree))):
        if i in idx:
            assert_array_equal(np.asarray(a), np.asarray(b) + 1)
        else:
            assert a is b


def test_plan_validation(tree):
    with pytest.raises(ValueError):
        ShardPlan.build(tree, 0)
    p = ShardPlan.build(tree, 2)
    with pytest.raises(IndexError):
        p.slice(tree, 2)
    with pytest.raises(ValueError):
        p.slice({"only": jnp.zeros((3,))}, 0)
    with pytest.raises(ValueError):
        p.merge(tree, 0, [])


# ---------------------------------------------------------------------------
# the train step
# ---------------------------------------------------------------------------

def quad_loss(params, batch):
    x, y = batch
    return jnp.mean((x @ params["w"] + params["b"] - y) ** 2)


@pytest.fixture()
def problem():
    rng = np.random.default_rng(0)
    w_true = rng.normal(size=(4, 1)).astype(np.float32)
    x = rng.normal(size=(64, 4)).astype(np.float32)
    y = x @ w_true
    params = {"w": jnp.zeros((4, 1), jnp.float32),
              "b": jnp.zeros((1,), jnp.float32)}
    return params, (jnp.asarray(x), jnp.asarray(y))


def _run(problem, granularity, n_shards, rounds=4, commit="momentum_delta"):
    """n_shards=None omits the field entirely (the pre-sharding call)."""
    params, batch = problem
    mesh = jax.make_mesh((1,), ("data",))
    shard_kw = {} if n_shards is None else {"n_shards": n_shards}
    cfg = CommitConfig(tau=2, local_lr=0.1, global_lr=0.7, **shard_kw)
    mbs = (jnp.stack([batch[0]] * 2), jnp.stack([batch[1]] * 2))
    step = make_train_step(
        quad_loss, cfg, UpdateRules(commit=commit, backend="reference"),
        mesh=mesh, granularity=granularity, explicit_momentum=0.3,
    )
    with jax.set_mesh(mesh):
        state = step.init(params)
        for _ in range(rounds):
            state, loss = jax.jit(step)(state, mbs, jnp.asarray([2], jnp.int32))
    return state, float(loss)


@pytest.mark.parametrize("granularity", ["data", "accum"])
@pytest.mark.parametrize("commit", ["momentum_delta", "plain_average"])
def test_k1_bit_identical_to_unsharded(problem, granularity, commit):
    s1, l1 = _run(problem, granularity, n_shards=1, commit=commit)
    s0, l0 = _run(problem, granularity, n_shards=None, commit=commit)
    assert l0 == l1
    # and K=1 state carries no version vector — the unsharded tree shape
    assert s1.shard_versions == ()
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s0.params)):
        assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("granularity", ["data", "accum"])
@pytest.mark.parametrize("commit", ["momentum_delta", "plain_average"])
def test_sharded_apply_matches_monolithic(problem, granularity, commit):
    """Leaf-wise commit rules ⇒ the K-sharded apply is the monolithic
    apply, bit for bit, at every K."""
    base, l_base = _run(problem, granularity, n_shards=1, commit=commit)
    for k in (2, 4):
        sk, lk = _run(problem, granularity, n_shards=k, commit=commit)
        assert lk == l_base
        for a, b in zip(jax.tree.leaves(sk.params), jax.tree.leaves(base.params)):
            assert_array_equal(np.asarray(a), np.asarray(b))
        assert_array_equal(
            np.asarray(sk.shard_versions), np.full((min(k, 2),), 4, np.int32)
        )


def test_single_leaf_model_clamps_to_monolithic():
    """A 1-leaf pytree with n_shards>1 degenerates to the monolithic PS:
    init produces no version vector and the step must accept it (the
    validator/version bump key off the clamped effective count)."""
    def loss(params, batch):
        x, y = batch
        return jnp.mean((x @ params["w"] - y) ** 2)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 4)), np.float32)
    y = jnp.asarray(rng.normal(size=(8, 1)), np.float32)
    params = {"w": jnp.zeros((4, 1), jnp.float32)}
    mesh = jax.make_mesh((1,), ("data",))
    cfg = CommitConfig(tau=1, local_lr=0.1, n_shards=4)
    step = make_train_step(loss, cfg, UpdateRules(backend="reference"),
                           mesh=mesh, granularity="data")
    with jax.set_mesh(mesh):
        state = step.init(params)
        assert state.shard_versions == ()
        state, _ = jax.jit(step)(state, (jnp.stack([x]), jnp.stack([y])),
                                 jnp.ones((1,), jnp.int32))
    assert state.shard_versions == ()


def test_stale_state_without_versions_raises(problem):
    params, batch = problem
    mesh = jax.make_mesh((1,), ("data",))
    cfg = CommitConfig(tau=1, local_lr=0.1, n_shards=2)
    mbs = (jnp.stack([batch[0]]), jnp.stack([batch[1]]))
    step = make_train_step(quad_loss, cfg, UpdateRules(backend="reference"),
                           mesh=mesh, granularity="data")
    with jax.set_mesh(mesh):
        with pytest.raises(ValueError, match="shard_versions"):
            step(AdspState.create(params), mbs, jnp.ones((1,), jnp.int32))


def test_commit_config_rejects_bad_shards():
    with pytest.raises(ValueError):
        CommitConfig(n_shards=0)


# ---------------------------------------------------------------------------
# the simulator: pipelined pushes, partial pulls
# ---------------------------------------------------------------------------

def _sim(n_shards, m=3, codec="identity", seconds=240.0, policy=None,
         bandwidth_div=1.0, **cfg_kw):
    task = svm_task(m)
    profiles = with_links(
        ratio_profiles(((1, 1, 3)[:m]), base_v=1.0, o=0.2),
        bandwidth=dense_nbytes(task.init_params) / bandwidth_div, latency=0.02,
    )
    cfg = SimConfig(max_seconds=seconds, base_batch=32, gamma=20.0,
                    epoch_seconds=80.0, **cfg_kw)
    policy = policy or make_policy("adsp", search=False, gamma=20.0)
    sim = Simulator(task, profiles, policy, cfg, codec=codec,
                    n_shards=n_shards)
    return sim, sim.train(seconds)


def test_k1_matches_default_exactly():
    """n_shards=1 runs the monolithic code path: every observable of a
    default run, reproduced bit for bit."""
    _, r0 = _sim(1)
    sim1 = Simulator(
        svm_task(3),
        with_links(ratio_profiles((1, 1, 3), base_v=1.0, o=0.2),
                   bandwidth=dense_nbytes(svm_task(3).init_params), latency=0.02),
        make_policy("adsp", search=False, gamma=20.0),
        SimConfig(max_seconds=240.0, base_batch=32, gamma=20.0,
                  epoch_seconds=80.0),
        codec="identity",
    )
    r1 = sim1.train(240.0)
    assert r0.bytes_to_ps == r1.bytes_to_ps
    assert r0.bytes_from_ps == r1.bytes_from_ps
    assert r0.convergence_time == r1.convergence_time
    assert r0.total_steps == r1.total_steps
    assert r0.total_commits == r1.total_commits
    assert_array_equal(r0.losses, r1.losses)


def test_k1_pull_bytes_are_dense_per_commit():
    sim, res = _sim(1, seconds=120.0)
    assert res.total_commits > 0
    assert res.bytes_from_ps == res.total_commits * sim._pull_nbytes


def test_single_worker_pulls_nothing():
    """With no interleaving writers every shard is self-tracked: the
    worker's own commits never stale its copy, so partial pulls ship
    zero bytes (the monolithic PS re-ships the dense model each time)."""
    sim, res = _sim(2, m=1, seconds=120.0)
    assert res.total_commits > 0
    assert res.bytes_from_ps == 0.0
    assert res.bytes_to_ps == res.total_commits * sim._enc_nbytes
    assert sim._ps_version == [res.total_commits] * sim.n_shards


def test_sharded_push_bytes_invariant_and_pulls_partial():
    """Per-leaf codecs partition exactly: the K per-shard encodes sum to
    the lumped payload, and multi-writer pulls move at most the dense
    bytes per commit — strictly less once any shard is self-tracked."""
    sim, res = _sim(4, seconds=240.0, bandwidth_div=8.0)
    assert sim.n_shards == 2  # svm task has two leaves
    assert sum(sim._shard_enc_nbytes) == sim._enc_nbytes
    assert sum(sim._shard_pull_nbytes) == sim._pull_nbytes
    assert res.total_commits > 0
    # push bytes: every applied shard booked (+ a possible in-flight tail)
    assert res.bytes_to_ps >= res.total_commits * sim._enc_nbytes
    assert res.bytes_from_ps < res.total_commits * sim._pull_nbytes


def test_sharded_barrier_policy_runs():
    """Barrier policies buffer complete sharded commits and release whole
    rounds; byte accounting stays consistent."""
    sim, res = _sim(2, policy=make_policy("fixed_adacomm", tau=4),
                    seconds=120.0)
    assert res.total_commits > 0
    assert res.bytes_to_ps == res.total_commits * sim._enc_nbytes
    assert res.bytes_from_ps <= res.total_commits * sim._pull_nbytes


def test_sharded_churn_join_leave():
    """Elastic churn under a sharded PS: a joiner starts current (knows
    the versions it copied), a leaver's in-flight shards are dropped."""
    from repro.cluster import ChurnSchedule, join, leave

    task = svm_task(3)
    profiles = with_links(ratio_profiles((1, 1, 3), base_v=1.0, o=0.2),
                          bandwidth=dense_nbytes(task.init_params), latency=0.02)
    churn = ChurnSchedule([
        leave(30.0, worker=2),
        join(50.0, WorkerProfile(v=1.0, o=0.2)),
    ])
    sim = Simulator(task, profiles, make_policy("adsp", search=False, gamma=20.0),
                    SimConfig(max_seconds=150.0, base_batch=32, gamma=20.0,
                              epoch_seconds=80.0),
                    churn=churn, codec="identity", n_shards=2)
    res = sim.train(150.0)
    assert res.total_commits > 0
    assert len(sim.workers) == 3
    joiner = sim.workers[-1]
    assert len(joiner.shard_known) == sim.n_shards


def test_simulator_rejects_bad_shards():
    with pytest.raises(ValueError):
        _sim(0, seconds=1.0)


# ---------------------------------------------------------------------------
# the mesh backend
# ---------------------------------------------------------------------------

def test_mesh_backend_sharded_state():
    from repro.cluster import ADSP, ClusterEngine
    from repro.cluster.mesh_backend import MeshBackend, MeshTask

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 4)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(8, 1)), jnp.float32)
    task = MeshTask(
        init_params={"w": jnp.zeros((4, 1), jnp.float32),
                     "b": jnp.zeros((1,), jnp.float32)},
        loss_fn=quad_loss,
        make_microbatches=lambda r, tau, n: (jnp.stack([x] * tau),
                                             jnp.stack([y] * tau)),
    )
    mesh = jax.make_mesh((1,), ("data",))
    outs = {}
    for k in (1, 2):
        backend = MeshBackend(task, mesh, tau=2, n_shards=k)
        ClusterEngine(ADSP(search=False, gamma=4.0), backend)
        with jax.set_mesh(mesh):
            backend.train(rounds=3)
        outs[k] = backend
    assert outs[2].n_shards == 2
    assert_array_equal(np.asarray(outs[2].state.shard_versions),
                       np.asarray([3, 3], np.int32))
    assert outs[1].state.shard_versions == ()
    for a, b in zip(jax.tree.leaves(outs[1].state.params),
                    jax.tree.leaves(outs[2].state.params)):
        assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# fused decode+apply across shard counts (§16)
# ---------------------------------------------------------------------------

def _run_fused(problem, n_shards, codec, fused, rounds=4):
    params, batch = problem
    mesh = jax.make_mesh((1,), ("data",))
    cfg = CommitConfig(tau=2, local_lr=0.1, global_lr=0.7, n_shards=n_shards)
    mbs = (jnp.stack([batch[0]] * 2), jnp.stack([batch[1]] * 2))
    step = make_train_step(
        quad_loss, cfg, UpdateRules(backend="reference"),
        mesh=mesh, granularity="data", explicit_momentum=0.3,
        codec=codec, fused_commit=fused,
    )
    with jax.set_mesh(mesh):
        state = step.init(params)
        for _ in range(rounds):
            state, loss = jax.jit(step)(state, mbs, jnp.asarray([2], jnp.int32))
    return step, state, float(loss)


@pytest.mark.parametrize("commit", ["momentum_delta", "plain_average"])
@pytest.mark.parametrize("codec_name", ["int8", "bf16"])
@pytest.mark.parametrize("k", [1, 2, 8])
def test_fused_sharded_apply_bit_identical_to_chain(codec_name, commit, k):
    """The §16 contract: given the same encoded payload, the fused
    decode+apply under the ShardPlan — int8 payloads flatten as
    {"q","scale"} units — is bit-identical to decode → apply at every K.
    (K=8 clamps to the leaf count like any plan.)"""
    from repro.ps import get_commit_rule, make_sharded_apply
    from repro.ps.fused_codec import fused_commit_name
    from repro.transport import get_codec

    rng = np.random.default_rng(11)
    params = {
        "w": jnp.asarray(rng.normal(size=(37, 5)), jnp.float32),
        "b": jnp.asarray(rng.normal(size=(1,)), jnp.float32),
        "h": {"v": jnp.asarray(rng.normal(size=(260,)), jnp.float32)},
    }
    u = jax.tree.map(lambda x: (x * 0.07 + 0.01).astype(jnp.float32), params)
    cfg = CommitConfig(tau=1, global_lr=0.7, worker_axes=(), n_shards=k)
    chain_rule = get_commit_rule(commit, cfg, backend="reference")
    fused_rule = get_commit_rule(fused_commit_name(commit, codec_name), cfg,
                                 backend="reference")
    codec = get_codec(codec_name, backend="reference")
    enc, _ = jax.jit(codec.encode)(u, jax.tree.map(jnp.zeros_like, u))
    dec = jax.jit(lambda e: codec.decode(e, params))(enc)
    cstate = chain_rule.init(params)
    fstate = fused_rule.init(params)

    out_c = jax.jit(make_sharded_apply(chain_rule, k))(params, cstate, dec, 0.3)
    out_f = jax.jit(make_sharded_apply(fused_rule, k))(params, fstate, enc, 0.3)
    for a, b in zip(jax.tree.leaves(out_c), jax.tree.leaves(out_f)):
        assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("codec", ["int8", "bf16"])
@pytest.mark.parametrize("k", [1, 2, 8])
def test_fused_commit_sharded_train_step_matches_chain(problem, codec, k):
    """End-to-end sharded train step, fused vs chain. bf16 is bit-exact
    (its EF residual is a bare subtract). int8's residual e − q·s is
    mul+sub: LLVM FMA-contracts it in the encode-only fused graph but not
    in the chain graph (where the product is CSEd with the decode), so
    across 4 rounds the trajectories agree only to ~1e-7 — the per-commit
    numerics are pinned exactly by the same-payload apply test above."""
    step_c, sc, lc = _run_fused(problem, k, codec, fused=False)
    step_f, sf, lf = _run_fused(problem, k, codec, fused=True)
    assert not step_c.fused_commit and step_f.fused_commit
    if codec == "bf16":
        assert lc == lf
        for a, b in zip(jax.tree.leaves(sc), jax.tree.leaves(sf)):
            assert_array_equal(np.asarray(a, np.float32),
                               np.asarray(b, np.float32))
    else:
        assert lc == pytest.approx(lf, rel=1e-6)
        for a, b in zip(jax.tree.leaves(sc), jax.tree.leaves(sf)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32), atol=1e-7)


def test_mesh_backend_overlapped_shards_bit_identical():
    """The overlapped per-shard commit (push once, K pull dispatches with
    no host sync between them): params, commit state, and losses match
    the monolithic fused step and the plain chain bit for bit. The
    transport residual alone is compiler-sensitive (the push graph
    compiles the local scan without the apply epilogue, shifting one
    fusion decision) and is pinned to one f32 ulp instead. Donation must
    leave the caller's init params untouched."""
    from repro.cluster import ADSP, ClusterEngine
    from repro.cluster.mesh_backend import MeshBackend, MeshTask

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 4)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(8, 1)), jnp.float32)
    init = {"w": jnp.zeros((4, 1), jnp.float32),
            "b": jnp.zeros((1,), jnp.float32)}
    init_copy = jax.tree.map(np.asarray, init)
    task = MeshTask(
        init_params=init,
        loss_fn=quad_loss,
        make_microbatches=lambda r, tau, n: (jnp.stack([x] * tau),
                                             jnp.stack([y] * tau)),
    )
    mesh = jax.make_mesh((1,), ("data",))
    variants = {
        "chain": dict(),
        "fused": dict(fused_commit=True),
        "overlap": dict(fused_commit=True, overlap_shards=True),
    }
    outs = {}
    for name, kw in variants.items():
        backend = MeshBackend(task, mesh, tau=2, codec="bf16", n_shards=2, **kw)
        ClusterEngine(ADSP(search=False, gamma=4.0), backend)
        with jax.set_mesh(mesh):
            losses = [backend.run_round() for _ in range(3)]
        outs[name] = (backend, losses)
    assert not outs["chain"][0].fused_commit
    assert outs["fused"][0].fused_commit and not outs["fused"][0].overlap_shards
    assert outs["overlap"][0].overlap_shards
    assert outs["chain"][1] == outs["fused"][1] == outs["overlap"][1]
    ref_state = outs["chain"][0].state
    for a, b in zip(jax.tree.leaves(ref_state),
                    jax.tree.leaves(outs["fused"][0].state)):
        assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
    ov = outs["overlap"][0].state
    for tree in ("params", "commit_state", "shard_versions"):
        for a, b in zip(jax.tree.leaves(getattr(ref_state, tree)),
                        jax.tree.leaves(getattr(ov, tree))):
            assert_array_equal(np.asarray(a, np.float32),
                               np.asarray(b, np.float32))
    for a, b in zip(jax.tree.leaves(ref_state.transport_state),
                    jax.tree.leaves(ov.transport_state)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-8)
    # donated round buffers never alias the caller's tree
    for a, b in zip(jax.tree.leaves(init), jax.tree.leaves(init_copy)):
        assert_array_equal(np.asarray(a), b)
