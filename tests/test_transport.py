"""The commit-transport layer (repro.transport): codec contracts, the
link model, and the wiring through both the simulator and the real train
step.

Key invariants:
  * error feedback: decode(enc) + new_residual == update + residual;
  * identity codec + infinite bandwidth == the pre-transport stack,
    bit for bit (timing, losses, and the old bytes proxy);
  * fused (Pallas) and reference backends agree from a real train step;
  * on a bandwidth-constrained link, int8 cuts measured bytes_to_ps ~4×
    with no worse convergence time.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose, assert_array_equal

from repro.control.theory import WorkerProfile
from repro.edgesim import SimConfig, Simulator
from repro.edgesim.profiles import ratio_profiles, with_links
from repro.edgesim.tasks import svm_task
from repro.cluster import make_policy
from repro.ps import AdspState, CommitConfig, UpdateRules, make_train_step
from repro.transport import (
    Codec,
    codec_backends,
    codec_names,
    dense_nbytes,
    get_codec,
)


@pytest.fixture()
def update_tree():
    rng = np.random.default_rng(3)
    return {
        "a": jnp.asarray(rng.normal(size=(1001,)), jnp.float32),
        "b": {"c": jnp.asarray(rng.normal(size=(17, 5)), jnp.float32)},
    }


def _all_codecs():
    out = []
    for name in codec_names():
        for backend in codec_backends(name):
            out.append((name, backend))
    return out


# ---------------------------------------------------------------------------
# codec contracts
# ---------------------------------------------------------------------------

def test_registry_contents():
    assert set(codec_names()) >= {"identity", "int8", "bf16", "top_k"}
    assert codec_backends("int8") == ("fused", "reference")
    assert codec_backends("top_k") == ("reference",)
    # a fused request for a codec with no fused impl falls back
    assert get_codec("top_k", backend="fused").backend == "reference"
    # Codec instances pass through; unknown names raise
    c = get_codec("int8", backend="reference")
    assert get_codec(c) is c
    with pytest.raises(KeyError):
        get_codec("gzip")


@pytest.mark.parametrize("name,backend", _all_codecs())
def test_error_feedback_identity(update_tree, name, backend):
    """decode(encode(e)) + residual' == e, the invariant that keeps lossy
    codecs unbiased across commits."""
    codec = get_codec(name, backend=backend)
    state = codec.init(update_tree)
    enc, state1 = codec.encode(update_tree, state)
    dec = codec.decode(enc, update_tree)
    res = state1 if jax.tree.leaves(state1) else jax.tree.map(
        jnp.zeros_like, update_tree
    )
    for d, r, u in zip(jax.tree.leaves(dec), jax.tree.leaves(res),
                       jax.tree.leaves(update_tree)):
        assert_allclose(np.asarray(d) + np.asarray(r), np.asarray(u),
                        atol=1e-6, rtol=1e-6)


def test_identity_is_exact_passthrough(update_tree):
    codec = get_codec("identity")
    enc, state = codec.encode(update_tree, codec.init(update_tree))
    assert enc is update_tree  # not a copy: bit-parity by construction
    assert codec.decode(enc, update_tree) is update_tree


def test_encoded_nbytes_static(update_tree):
    n = 1001 + 17 * 5
    dense = dense_nbytes(update_tree)
    assert dense == 4 * n
    assert get_codec("identity").encoded_nbytes(update_tree) == dense
    assert get_codec("int8").encoded_nbytes(update_tree) == n + 2 * 4
    assert get_codec("bf16").encoded_nbytes(update_tree) == 2 * n
    k = max(1, round(0.05 * 1001)) + max(1, round(0.05 * 85))
    assert get_codec("top_k", frac=0.05).encoded_nbytes(update_tree) == 8 * k


def test_error_feedback_recovers_lost_mass(update_tree):
    """A constant update stream through int8 must not drift: the running
    sum of decoded commits tracks the running sum of true updates."""
    codec = get_codec("int8")
    state = codec.init(update_tree)
    acc = jax.tree.map(jnp.zeros_like, update_tree)
    for _ in range(8):
        enc, state = codec.encode(update_tree, state)
        acc = jax.tree.map(jnp.add, acc, codec.decode(enc, update_tree))
    for a, u in zip(jax.tree.leaves(acc), jax.tree.leaves(update_tree)):
        # without error feedback the quantization error would be ~8× larger
        assert_allclose(np.asarray(a), 8 * np.asarray(u), atol=0.02, rtol=0.01)


def test_codec_kernel_ops_match_plain_math(update_tree):
    """The per-array kernel ops behind the fused codecs — quantize_int8,
    dequantize_int8, encode_bf16 — against their plain-jnp definitions."""
    from repro.kernels import ops

    x = jax.tree.leaves(update_tree)[0]
    scale = float(jnp.max(jnp.abs(x))) / 127.0
    q, res = ops.quantize_int8(x, scale)
    assert q.dtype == jnp.int8 and q.shape == x.shape
    expect_q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    assert_array_equal(np.asarray(q), np.asarray(expect_q))
    dec = ops.dequantize_int8(q, scale)
    assert_allclose(np.asarray(dec), np.asarray(q, np.float32) * scale,
                    atol=1e-6, rtol=1e-6)
    # residual carries exactly what the round trip lost
    assert_allclose(np.asarray(dec) + np.asarray(res), np.asarray(x),
                    atol=1e-6, rtol=1e-6)

    qb, rb = ops.encode_bf16(x)
    assert qb.dtype == jnp.bfloat16 and qb.shape == x.shape
    assert_array_equal(np.asarray(qb), np.asarray(x.astype(jnp.bfloat16)))
    assert_allclose(np.asarray(qb, np.float32) + np.asarray(rb),
                    np.asarray(x), atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("shape", [(32, 1024), (257,), (3, 5), (40_000,)])
def test_fused_codec_commit_kernel_ops_match_ref(shape):
    """The single-pass commit-path kernels (§16) — "quantize_int8_ef",
    "encode_bf16_ef", "int8_decode_apply", "bf16_decode_apply",
    "int8_decode_accum", "bf16_decode_accum" — bit-for-bit against their
    ref.py twins, which spell out the exact unfused chain. The twins run
    under jit like every real call site (eager mode skips XLA's FMA
    contraction of e − q·s and differs below one ulp of e)."""
    from repro.kernels import ops
    from repro.kernels import ref as _ref

    class ref:  # jit each twin: compare the compiled forms, as deployed
        pass
    for _n in ("quantize_int8_ef", "encode_bf16_ef", "int8_decode_apply",
               "bf16_decode_apply", "int8_decode_accum", "bf16_decode_accum"):
        setattr(ref, _n, staticmethod(jax.jit(getattr(_ref, _n))))

    rng = np.random.default_rng(int(np.prod(shape)))
    u = jnp.asarray(rng.normal(size=shape), jnp.float32)
    r = jnp.asarray(rng.normal(size=shape) * 0.01, jnp.float32)
    w = jnp.asarray(rng.normal(size=shape), jnp.float32)
    d = jnp.asarray(rng.normal(size=shape) * 0.1, jnp.float32)
    lr, mu = 0.7, 0.9

    scale = float(jnp.max(jnp.abs(u + r))) / 127.0
    q, res = ops.quantize_int8_ef(u, r, scale, interpret=True)
    q_e, res_e = ref.quantize_int8_ef(u, r, scale)
    assert q.dtype == jnp.int8
    assert_array_equal(np.asarray(q), np.asarray(q_e))
    assert_array_equal(np.asarray(res), np.asarray(res_e))

    qb, rb = ops.encode_bf16_ef(u, r, interpret=True)
    qb_e, rb_e = ref.encode_bf16_ef(u, r)
    assert qb.dtype == jnp.bfloat16
    assert_array_equal(np.asarray(qb, np.float32), np.asarray(qb_e, np.float32))
    assert_array_equal(np.asarray(rb), np.asarray(rb_e))

    nw, nd = ops.int8_decode_apply(w, d, q, scale, lr, mu, interpret=True)
    ew, ed = ref.int8_decode_apply(w, d, q, scale, lr, mu)
    assert_array_equal(np.asarray(nw), np.asarray(ew))
    assert_array_equal(np.asarray(nd), np.asarray(ed))

    nw, nd = ops.bf16_decode_apply(w, d, qb, lr, mu, interpret=True)
    ew, ed = ref.bf16_decode_apply(w, d, qb, lr, mu)
    assert_array_equal(np.asarray(nw), np.asarray(ew))
    assert_array_equal(np.asarray(nd), np.asarray(ed))

    aw = ops.int8_decode_accum(w, q, scale, lr, interpret=True)
    assert_array_equal(np.asarray(aw),
                       np.asarray(ref.int8_decode_accum(w, q, scale, lr)))
    aw = ops.bf16_decode_accum(w, qb, lr, interpret=True)
    assert_array_equal(np.asarray(aw),
                       np.asarray(ref.bf16_decode_accum(w, qb, lr)))


def test_overlapped_shard_pulls_donate_param_buffers():
    """The overlapped commit's per-shard pull jits carry
    donate_argnums=(0, 1): each shard's params and commit state are dead
    the moment the fused apply produces their successors, so the round
    updates in place. Verified by buffer identity — after a (warm)
    round, every new param leaf occupies one of the previous round's
    buffers, i.e. donation actually took effect rather than being
    silently dropped."""
    from repro.cluster import ADSP, ClusterEngine
    from repro.cluster.mesh_backend import MeshBackend, MeshTask

    def quad_loss(params, batch):
        x, y = batch
        return jnp.mean((x @ params["w"] + params["b"] - y) ** 2)

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
    backend = MeshBackend(task, mesh, tau=2, codec="bf16", n_shards=2,
                          fused_commit=True, overlap_shards=True)
    ClusterEngine(ADSP(search=False, gamma=4.0), backend)
    with jax.set_mesh(mesh):
        backend.run_round()  # warm the push/pull jits (first call compiles)
        before = {leaf.unsafe_buffer_pointer()
                  for leaf in jax.tree.leaves(backend.state.params)}
        backend.run_round()
        after = [leaf.unsafe_buffer_pointer()
                 for leaf in jax.tree.leaves(backend.state.params)]
    assert all(p in before for p in after), (
        "per-shard pull did not reuse donated param buffers")


@pytest.mark.parametrize("name", ["int8", "bf16"])
def test_fused_matches_reference_encode_decode(update_tree, name):
    ref = get_codec(name, backend="reference")
    fus = get_codec(name, backend="fused")
    assert fus.backend == "fused"
    s0 = ref.init(update_tree)
    enc_r, st_r = ref.encode(update_tree, s0)
    enc_f, st_f = fus.encode(update_tree, s0)
    for a, b in zip(jax.tree.leaves((enc_r, st_r)), jax.tree.leaves((enc_f, st_f))):
        assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                        atol=1e-6, rtol=1e-6)
    dec_r = ref.decode(enc_r, update_tree)
    dec_f = fus.decode(enc_f, update_tree)
    for a, b in zip(jax.tree.leaves(dec_r), jax.tree.leaves(dec_f)):
        assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6, rtol=1e-6)


# ---------------------------------------------------------------------------
# the real train step
# ---------------------------------------------------------------------------

def quad_loss(params, batch):
    x, y = batch
    return jnp.mean((x @ params["w"] - y) ** 2)


@pytest.fixture()
def problem():
    rng = np.random.default_rng(0)
    w_true = rng.normal(size=(4, 1)).astype(np.float32)
    x = rng.normal(size=(64, 4)).astype(np.float32)
    y = x @ w_true
    params = {"w": jnp.zeros((4, 1), jnp.float32)}
    return params, (jnp.asarray(x), jnp.asarray(y))


def _run_steps(problem, codec, rounds=4, backend="reference"):
    params, batch = problem
    cfg = CommitConfig(tau=2, local_lr=0.1, global_lr=1.0, worker_axes=("data",))
    mesh = jax.make_mesh((1,), ("data",))
    mbs = (jnp.stack([batch[0]] * 2), jnp.stack([batch[1]] * 2))
    step = make_train_step(quad_loss, cfg, UpdateRules(backend="reference"),
                           mesh=mesh, codec=codec)
    with jax.set_mesh(mesh):
        state = step.init(params)
        for _ in range(rounds):
            state, loss = jax.jit(step)(state, mbs, jnp.asarray([2], jnp.int32))
    return np.asarray(state.params["w"]), float(loss)


def test_train_step_identity_codec_bit_identical(problem):
    w_none, l_none = _run_steps(problem, codec=None)
    w_id, l_id = _run_steps(problem, codec="identity")
    assert_array_equal(w_none, w_id)
    assert l_none == l_id


@pytest.mark.parametrize("codec", ["int8", "bf16", "top_k"])
def test_train_step_lossy_codec_still_converges(problem, codec):
    w, loss = _run_steps(problem, codec=codec, rounds=30)
    assert loss < 0.05  # quad problem: near-exact recovery despite compression


def test_train_step_fused_codec_matches_reference(problem):
    params, batch = problem
    cfg = CommitConfig(tau=2, local_lr=0.1, global_lr=1.0, worker_axes=("data",))
    mesh = jax.make_mesh((1,), ("data",))
    mbs = (jnp.stack([batch[0]] * 2), jnp.stack([batch[1]] * 2))
    outs = {}
    for backend in ("reference", "fused"):
        step = make_train_step(quad_loss, cfg, UpdateRules(backend="reference"),
                               mesh=mesh, codec=get_codec("int8", backend=backend))
        with jax.set_mesh(mesh):
            state = step.init(params)
            for _ in range(3):
                state, loss = jax.jit(step)(state, mbs, jnp.asarray([2], jnp.int32))
        outs[backend] = (np.asarray(state.params["w"]), float(loss))
    assert_allclose(outs["fused"][0], outs["reference"][0], atol=1e-6, rtol=1e-6)
    assert outs["fused"][1] == pytest.approx(outs["reference"][1], rel=1e-6)


def test_transport_state_mismatch_raises(problem):
    params, batch = problem
    cfg = CommitConfig(tau=1, local_lr=0.1, worker_axes=("data",))
    mesh = jax.make_mesh((1,), ("data",))
    mbs = (jnp.stack([batch[0]]), jnp.stack([batch[1]]))
    step = make_train_step(quad_loss, cfg, UpdateRules(backend="reference"),
                           mesh=mesh, codec="int8")
    with jax.set_mesh(mesh):
        with pytest.raises(ValueError, match="transport_state does not match"):
            step(AdspState.create(params), mbs, jnp.ones((1,), jnp.int32))


def test_cli_codec_args():
    import argparse

    from repro.transport import add_codec_args, codec_from_args

    p = argparse.ArgumentParser()
    add_codec_args(p)
    c = codec_from_args(p.parse_args([]))
    assert isinstance(c, Codec) and c.name == "identity"
    c = codec_from_args(p.parse_args(
        ["--codec", "top_k", "--topk-frac", "0.25", "--codec-backend", "reference"]))
    assert c.name == "top_k"


# ---------------------------------------------------------------------------
# the simulator link model
# ---------------------------------------------------------------------------

def _sim(codec="identity", profiles=None, seconds=240.0, policy=None, **cfg_kw):
    profiles = profiles or ratio_profiles((1, 1, 3), base_v=1.0, o=0.2)
    cfg = SimConfig(max_seconds=seconds, base_batch=32, gamma=20.0,
                    epoch_seconds=80.0, **cfg_kw)
    policy = policy or make_policy("adsp", search=False, gamma=20.0)
    sim = Simulator(svm_task(len(profiles)), profiles, policy, cfg, codec=codec)
    return sim, sim.train(seconds)


def test_identity_infinite_bandwidth_matches_fixed_o():
    """The old fixed-O_i commit cost and bytes proxy, reproduced exactly:
    comm_time is commits·O_i per worker and bytes_to_ps is 4·|params|·C."""
    sim, res = _sim("identity")
    for w in sim.workers:
        # every charged commit round trip cost exactly o (o/2 + o/2)
        charged = w.comm_time / w.profile.o
        assert charged == pytest.approx(round(charged))
    assert res.bytes_to_ps == 4.0 * sim._param_sizes * sim.total_commits


def test_worker_profile_link_validation():
    with pytest.raises(ValueError):
        WorkerProfile(v=1.0, bandwidth=0.0)
    with pytest.raises(ValueError):
        WorkerProfile(v=1.0, latency=-1.0)
    p = WorkerProfile(v=1.0, o=0.2, bandwidth=100.0, latency=0.05)
    assert p.transfer_seconds(50) == pytest.approx(0.55)
    assert WorkerProfile(v=1.0).transfer_seconds(1e12) == 0.0  # inf link


def test_constrained_link_charges_payload_time():
    """With bandwidth B and latency L, each commit costs
    o + 2L + (enc + dense)/B of comm time."""
    profiles = with_links(ratio_profiles((1.0,), base_v=1.0, o=0.2),
                          bandwidth=1000.0, latency=0.05)
    sim, res = _sim("identity", profiles=profiles, seconds=60.0)
    w = sim.workers[0]
    per_commit = (w.profile.o + 2 * 0.05
                  + (sim._enc_nbytes + sim._pull_nbytes) / 1000.0)
    assert w.commits > 0
    # comm_time counts in-flight commits too; allow one round trip slack
    charged = w.comm_time / per_commit
    assert charged == pytest.approx(round(charged))
    assert round(charged) >= w.commits


def test_int8_reduces_bytes_no_worse_convergence():
    """The acceptance tradeoff on a link-bound fleet: int8 cuts wire bytes
    ~4× and converges no later than the dense identity run."""
    task_params_bytes = dense_nbytes(svm_task(3).init_params)
    profiles = with_links(ratio_profiles((1, 1, 3), base_v=1.0, o=0.2),
                          bandwidth=task_params_bytes / 1.0, latency=0.02)
    _, res_id = _sim("identity", profiles=profiles, target_loss=0.55)
    _, res_q = _sim("int8", profiles=profiles, target_loss=0.55)
    assert res_q.converged and res_id.converged
    # the tiny SVM (7 params, 2 leaves) pays 4 B of scale per leaf, so the
    # ratio is ~1.9× here rather than the asymptotic 4× (bench_transport
    # shows 4× on the CNN)
    assert res_q.bytes_to_ps < 0.6 * res_id.bytes_to_ps
    assert res_q.convergence_time <= res_id.convergence_time * 1.05


def test_simulator_rejects_unknown_codec():
    with pytest.raises(KeyError):
        _sim("gzip", seconds=1.0)


# ---------------------------------------------------------------------------
# the mesh backend
# ---------------------------------------------------------------------------

def test_mesh_backend_codec_bytes_accounting():
    from repro.cluster import ADSP, ClusterEngine
    from repro.cluster.mesh_backend import MeshBackend, MeshTask

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 4)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(8, 1)), jnp.float32)

    task = MeshTask(
        init_params={"w": jnp.zeros((4, 1), jnp.float32)},
        loss_fn=quad_loss,
        make_microbatches=lambda r, tau, n: (jnp.stack([x] * tau), jnp.stack([y] * tau)),
    )
    mesh = jax.make_mesh((1,), ("data",))
    backend = MeshBackend(task, mesh, tau=2, codec="int8")
    ClusterEngine(ADSP(search=False, gamma=4.0), backend)
    with jax.set_mesh(mesh):
        backend.train(rounds=3)
    assert backend.codec.name == "int8"
    assert backend.bytes_per_round == backend.codec.encoded_nbytes(task.init_params)
    assert backend.bytes_to_ps == 3 * backend.bytes_per_round
    assert backend.bytes_per_round < dense_nbytes(task.init_params)
