"""The pluggable update-rule API (repro.ps): legacy parity, fused-vs-
reference backend agreement, rule semantics, and AdamW-at-worker e2e.

Parity contract: ``make_train_step`` with the sgd rule must match the
seed factories bit-for-bit — checked against an inline re-statement of
the seed's arithmetic (embedded verbatim below), per granularity.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose, assert_array_equal

from repro.ps import (
    AdspState,
    CommitConfig,
    UpdateRules,
    commit_rule_names,
    get_commit_rule,
    get_local_rule,
    local_rule_names,
    make_train_step,
    resolve_backend,
    rule_backends,
    worker_axes_for,
)


def quad_loss(params, batch):
    x, y = batch
    pred = x @ params["w"]
    return jnp.mean((pred - y) ** 2)


@pytest.fixture()
def problem():
    rng = np.random.default_rng(0)
    w_true = rng.normal(size=(4, 1)).astype(np.float32)
    x = rng.normal(size=(64, 4)).astype(np.float32)
    y = x @ w_true
    params = {"w": jnp.zeros((4, 1), jnp.float32)}
    return params, (jnp.asarray(x), jnp.asarray(y))


def _mesh1():
    return jax.make_mesh((1,), ("data",))


def _null_ctx():
    import contextlib
    return contextlib.nullcontext()


def _stack(batch, tau):
    x, y = batch
    return jnp.stack([x] * tau), jnp.stack([y] * tau)


def _seed_local_update_fn(loss_fn, cfg):
    """Verbatim seed implementation (core.commit.make_local_update_fn at
    PR 1) — the bit-for-bit oracle for the sgd LocalRule."""
    grad_fn = jax.value_and_grad(loss_fn)

    def local_update(params, microbatches, tau_i):
        zeros = jax.tree.map(jnp.zeros_like, params)

        def body(carry, xs):
            p, u = carry
            mb, idx = xs
            live = (idx < tau_i).astype(jnp.float32)
            loss, g = grad_fn(p, mb)
            p = jax.tree.map(
                lambda a, b: (a - cfg.local_lr * live * b).astype(a.dtype), p, g
            )
            u = jax.tree.map(
                lambda a, b: (a + cfg.local_lr * live * b).astype(a.dtype), u, g
            )
            return (p, u), loss * live

        idxs = jnp.arange(cfg.tau, dtype=jnp.int32)
        (_, u), losses = jax.lax.scan(
            body, (params, zeros), (microbatches, idxs)
        )
        denom = jnp.maximum(tau_i.astype(jnp.float32), 1.0)
        return u, jnp.sum(losses) / denom

    return local_update


def _seed_adsp_step(loss_fn, cfg, mesh, batch_spec, explicit_momentum=0.0):
    """Verbatim seed implementation (core.commit.make_adsp_step at PR 1)."""
    local_update = _seed_local_update_fn(loss_fn, cfg)
    axes = cfg.worker_axes

    def _sharded_body(params, prev_delta, step, microbatches, tau_per_worker):
        tau_i = tau_per_worker[0]
        u, loss = local_update(params, microbatches, tau_i)
        cd = jnp.dtype(cfg.commit_dtype)
        u = jax.tree.map(lambda x: x.astype(cd), u)
        u = jax.lax.pmean(u, axes)
        loss = jax.lax.pmean(loss, axes)
        delta = jax.tree.map(
            lambda d, uu: (explicit_momentum * d - cfg.global_lr * uu).astype(d.dtype),
            prev_delta, u,
        )
        params = jax.tree.map(jnp.add, params, delta)
        return params, delta, step + 1, loss

    rep = jax.sharding.PartitionSpec()
    tau_spec = jax.sharding.PartitionSpec(axes if len(axes) > 1 else axes[0])
    sharded = jax.shard_map(
        _sharded_body, mesh=mesh,
        in_specs=(rep, rep, rep, batch_spec, tau_spec),
        out_specs=(rep, rep, rep, rep),
        axis_names=set(axes), check_vma=False,
    )

    def adsp_step(params, prev_delta, step, microbatches, tau_per_worker):
        return sharded(params, prev_delta, step, microbatches, tau_per_worker)

    return adsp_step


def _seed_accum_step(loss_fn, cfg, explicit_momentum=0.0):
    """Verbatim seed implementation (core.accum.make_accum_step at PR 1)."""
    grad_fn = jax.value_and_grad(loss_fn)

    def accum_step(params, prev_delta, step, microbatches, tau_active):
        zeros = jax.tree.map(jnp.zeros_like, params)

        def body(carry, xs):
            p, u = carry
            mb, idx = xs
            live = (idx < tau_active).astype(jnp.float32)
            loss, g = grad_fn(p, mb)
            p = jax.tree.map(
                lambda a, b: (a - cfg.local_lr * live * b).astype(a.dtype), p, g
            )
            u = jax.tree.map(
                lambda a, b: (a + cfg.local_lr * live * b).astype(a.dtype), u, g
            )
            return (p, u), loss * live

        idxs = jnp.arange(cfg.tau, dtype=jnp.int32)
        (_, u), losses = jax.lax.scan(body, (params, zeros), (microbatches, idxs))
        loss = jnp.sum(losses) / jnp.maximum(tau_active.astype(jnp.float32), 1.0)
        delta = jax.tree.map(
            lambda d, uu: (explicit_momentum * d - cfg.global_lr * uu).astype(d.dtype),
            prev_delta, u,
        )
        params = jax.tree.map(jnp.add, params, delta)
        return params, delta, step + 1, loss

    return accum_step


# ---------------------------------------------------------------------------
# legacy parity (the SGD rule must reproduce the seed factories exactly)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("granularity", ["data", "accum", "pod"])
def test_train_step_matches_seed_arithmetic(problem, granularity):
    """Bit-for-bit against the seed factories (their PR 1 implementations,
    embedded verbatim above). 'pod' on a pod-less mesh degenerates to
    accum (DESIGN.md §3)."""
    params, batch = problem
    tau, tau_i = 3, 2
    cfg = CommitConfig(tau=tau, local_lr=0.1, global_lr=0.7, worker_axes=("data",))
    mesh = _mesh1()
    mbs = _stack(batch, tau)
    mu = 0.25
    step = make_train_step(
        quad_loss, cfg, UpdateRules(backend="reference"),
        mesh=mesh, granularity=granularity, explicit_momentum=mu,
    )
    worker_path = granularity == "data"
    if worker_path:
        seed = jax.jit(_seed_adsp_step(
            quad_loss, cfg, mesh,
            batch_spec=jax.sharding.PartitionSpec(None, "data"),
            explicit_momentum=mu,
        ))
        tau_seed = jnp.asarray([tau_i], jnp.int32)
    else:
        import dataclasses as _dc
        seed = jax.jit(_seed_accum_step(
            quad_loss, _dc.replace(cfg, worker_axes=()), explicit_momentum=mu
        ))
        tau_seed = jnp.asarray(tau_i, jnp.int32)
    with jax.set_mesh(mesh):
        state = step.init(params)
        p, d, s = params, jax.tree.map(jnp.zeros_like, params), jnp.zeros((), jnp.int32)
        for _ in range(3):
            state, loss = jax.jit(step)(state, mbs, jnp.asarray([tau_i], jnp.int32))
            p, d, s, ref_loss = seed(p, d, s, mbs, tau_seed)
    assert_array_equal(np.asarray(state.params["w"]), np.asarray(p["w"]))
    assert_array_equal(np.asarray(state.commit_state["w"]), np.asarray(d["w"]))
    assert_array_equal(np.asarray(loss), np.asarray(ref_loss))
    assert int(state.step) == int(s) == 3


def test_legacy_state_and_scalar_tau_still_accepted(problem):
    """Seed-era entry conventions survive the shim retirement: a bare
    ``AdspState.create(params)`` (no rule-owned state) and the legacy
    scalar ``tau_active`` both work against the unified factory."""
    params, batch = problem
    cfg = CommitConfig(tau=2, local_lr=0.05, global_lr=1.0, worker_axes=("data",))
    mesh = _mesh1()
    mbs = _stack(batch, 2)
    tau = jnp.asarray([2], jnp.int32)
    direct = make_train_step(quad_loss, cfg, UpdateRules(backend="reference"),
                             mesh=mesh, batch_spec=jax.sharding.PartitionSpec(None, "data"))
    accum = make_train_step(quad_loss,
                            CommitConfig(tau=2, local_lr=0.05, global_lr=1.0,
                                         worker_axes=()),
                            UpdateRules(backend="reference"))
    with jax.set_mesh(mesh):
        s_direct, l_direct = direct(direct.init(params), mbs, tau)
        s_legacy, l_legacy = direct(AdspState.create(params), mbs, tau)
        # legacy scalar tau_active still accepted by the accum path
        s_accum, _ = accum(AdspState.create(params), mbs, jnp.asarray(2, jnp.int32))
    assert_array_equal(np.asarray(s_direct.params["w"]), np.asarray(s_legacy.params["w"]))
    assert_array_equal(np.asarray(l_direct), np.asarray(l_legacy))
    assert np.asarray(s_accum.params["w"]).shape == (4, 1)


# ---------------------------------------------------------------------------
# fused backend: exercised from a real train step, parity vs reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("granularity", ["data", "accum"])
def test_fused_backend_matches_reference_from_train_step(problem, granularity):
    """The Pallas-fused commit path (accumulate + ps_apply kernels) runs
    inside the actual train step and agrees with the reference rules."""
    params, batch = problem
    cfg = CommitConfig(tau=2, local_lr=0.05, global_lr=1.0, worker_axes=("data",))
    mesh = _mesh1()
    mbs = _stack(batch, 2)
    tau = jnp.asarray([2], jnp.int32)
    outs = {}
    for backend in ("reference", "fused"):
        step = make_train_step(quad_loss, cfg, UpdateRules(backend=backend),
                               mesh=mesh, granularity=granularity,
                               explicit_momentum=0.5)
        assert step.rules[1].backend == backend
        with jax.set_mesh(mesh):
            state = step.init(params)
            for _ in range(3):
                state, loss = jax.jit(step)(state, mbs, tau)
        outs[backend] = (np.asarray(state.params["w"]), float(loss))
    assert_allclose(outs["fused"][0], outs["reference"][0], atol=1e-6, rtol=1e-6)
    assert outs["fused"][1] == pytest.approx(outs["reference"][1], rel=1e-6)


@pytest.mark.parametrize("dtype,momentum", [
    (jnp.float32, 0.9),
    (jnp.bfloat16, 0.9),
    (jnp.float32, 0.0),
])
def test_ps_apply_backends_agree_fixed(dtype, momentum):
    """Fixed ragged/dtype cases of the fused-vs-reference commit parity
    (the hypothesis sweep lives in test_rule_backends_property.py)."""
    rng = np.random.default_rng(7)
    cfg = CommitConfig(tau=1, global_lr=0.3, worker_axes=())
    w = {
        "a": jnp.asarray(rng.normal(size=(10_007,)), dtype),
        "b": {"c": jnp.asarray(rng.normal(size=(3, 5)), dtype)},
    }
    d = jax.tree.map(lambda t: (t * 0.1).astype(t.dtype), w)
    u = jax.tree.map(lambda t: (t * 0.2 + 0.3).astype(jnp.float32), w)
    ref = get_commit_rule("momentum_delta", cfg, backend="reference")
    fus = get_commit_rule("momentum_delta", cfg, backend="fused")
    rw, rd = ref.apply(w, d, u, momentum)
    fw, fd = fus.apply(w, d, u, momentum)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-6
    for a, b in zip(jax.tree.leaves((rw, rd)), jax.tree.leaves((fw, fd))):
        assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                        atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# rule semantics
# ---------------------------------------------------------------------------

def test_registry_contents():
    assert set(local_rule_names()) >= {"sgd", "sgd_momentum", "adamw"}
    assert set(commit_rule_names()) >= {"momentum_delta", "plain_average"}
    assert rule_backends("local", "sgd") == ("fused", "reference")
    assert rule_backends("commit", "momentum_delta") == ("fused", "reference")
    # auto resolves off-TPU to reference; explicit names pass through
    assert resolve_backend(None) in ("reference", "fused")
    assert resolve_backend("fused") == "fused"
    with pytest.raises(ValueError):
        resolve_backend("magic")
    # fused request for a rule with no fused impl falls back to reference
    cfg = CommitConfig(tau=1, worker_axes=())
    assert get_local_rule("adamw", cfg, backend="fused").backend == "reference"


def test_plain_average_is_worker_mean(problem):
    """One round of plain_average equals W − η·mean-over-workers(U)."""
    params, batch = problem
    cfg = CommitConfig(tau=1, local_lr=0.1, global_lr=1.0, worker_axes=("data",))
    mesh = _mesh1()
    mbs = _stack(batch, 1)
    step = make_train_step(
        quad_loss, cfg,
        UpdateRules(commit="plain_average", backend="reference"), mesh=mesh,
    )
    with jax.set_mesh(mesh):
        state, _ = jax.jit(step)(step.init(params), mbs, jnp.ones((1,), jnp.int32))
    _, g = jax.value_and_grad(quad_loss)(params, batch)
    expect = params["w"] - 0.1 * g["w"]
    assert_allclose(np.asarray(state.params["w"]), np.asarray(expect), rtol=1e-6)
    assert state.commit_state == ()


def test_adamw_state_masking(problem):
    """Masked microsteps must freeze the local optimizer state: with
    cfg.tau=3 and τ_i=1 the adam step counter advances by exactly 1."""
    params, batch = problem
    cfg = CommitConfig(tau=3, local_lr=0.05, worker_axes=("data",))
    mesh = _mesh1()
    mbs = _stack(batch, 3)
    step = make_train_step(quad_loss, cfg,
                           UpdateRules(local="adamw", backend="reference"),
                           mesh=mesh)
    with jax.set_mesh(mesh):
        state = step.init(params)
        state, _ = jax.jit(step)(state, mbs, jnp.asarray([1], jnp.int32))
        assert int(state.local_state.step[0]) == 1
        state, _ = jax.jit(step)(state, mbs, jnp.asarray([3], jnp.int32))
    # local adam moments persist across commit rounds (1 + 3 live steps)
    assert int(state.local_state.step[0]) == 4


def test_adamw_at_worker_converges(problem):
    params, batch = problem
    cfg = CommitConfig(tau=2, local_lr=0.05, worker_axes=("data",))
    mesh = _mesh1()
    mbs = _stack(batch, 2)
    step = make_train_step(
        quad_loss, cfg,
        UpdateRules(local="adamw", backend="reference", local_hp={"lr": 0.05}),
        mesh=mesh,
    )
    with jax.set_mesh(mesh):
        state = step.init(params)
        losses = []
        for _ in range(30):
            state, loss = jax.jit(step)(state, mbs, jnp.asarray([2], jnp.int32))
            losses.append(float(loss))
    assert losses[-1] < 0.02 * losses[0]


def test_sgd_momentum_local_rule_converges(problem):
    params, batch = problem
    cfg = CommitConfig(tau=2, local_lr=0.02, worker_axes=("data",))
    mesh = _mesh1()
    mbs = _stack(batch, 2)
    step = make_train_step(
        quad_loss, cfg,
        UpdateRules(local="sgd_momentum", backend="reference",
                    local_hp={"momentum": 0.8}),
        mesh=mesh,
    )
    with jax.set_mesh(mesh):
        state = step.init(params)
        losses = []
        for _ in range(30):
            state, loss = jax.jit(step)(state, mbs, jnp.asarray([2], jnp.int32))
            losses.append(float(loss))
    assert losses[-1] < 0.05 * losses[0]


def test_default_interpret_cached_and_env_override(monkeypatch):
    """kernels.ops probes the backend once (cached); the retired
    REPRO_PALLAS_INTERPRET override no longer changes the resolution, and
    interpret mode on a TPU backend (steered here) is an error."""
    from repro.kernels import ops

    try:
        ops.default_interpret.cache_clear()
        auto = ops.default_interpret()
        assert auto == (jax.default_backend() != "tpu")
        assert ops._interp(None) is auto and ops._interp(False) is False

        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
        ops.default_interpret.cache_clear()
        assert ops.default_interpret() is auto

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        ops.default_interpret.cache_clear()
        assert ops.default_interpret() is False
        assert ops._interp(None) is False and ops._interp(False) is False
        with pytest.raises(ValueError, match="interpret mode"):
            ops._interp(True)
    finally:
        monkeypatch.undo()
        ops.default_interpret.cache_clear()


def test_worker_axes_for_mapping():
    mesh = _mesh1()
    assert worker_axes_for("data", mesh) == ("data",)
    assert worker_axes_for("pod", mesh) == ()
    assert worker_axes_for("accum", mesh) == ()
    with pytest.raises(ValueError):
        worker_axes_for("bogus", mesh)


def test_worker_granularity_without_mesh_raises():
    """granularity='data' with no mesh must fail loudly, not silently
    degrade to single-worker accumulation."""
    cfg = CommitConfig(tau=1, worker_axes=("data",))
    with pytest.raises(ValueError, match="needs a mesh"):
        make_train_step(quad_loss, cfg, UpdateRules(backend="reference"),
                        granularity="data")
    # accum is the one mesh-free granularity
    step = make_train_step(quad_loss, cfg, UpdateRules(backend="reference"),
                           granularity="accum")
    assert step.n_workers == 1


def test_mismatched_state_raises_clearly(problem):
    """Seed-era AdspState.create(params) paired with a stateful local rule
    must raise a pointed error, not a tree-structure failure mid-scan."""
    params, batch = problem
    cfg = CommitConfig(tau=1, local_lr=0.05, worker_axes=("data",))
    mesh = _mesh1()
    mbs = _stack(batch, 1)
    step = make_train_step(quad_loss, cfg,
                           UpdateRules(local="adamw", backend="reference"),
                           mesh=mesh)
    with jax.set_mesh(mesh):
        with pytest.raises(ValueError, match="local_state does not match"):
            step(AdspState.create(params), mbs, jnp.ones((1,), jnp.int32))


# ---------------------------------------------------------------------------
# integration: AdamW-at-worker through the launcher (smoke example)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_launch_train_smoke_adamw(tmp_path, capsys):
    """`python -m repro.launch.train --smoke --local-rule adamw` trains
    end-to-end: the full control plane over the unified train step."""
    from repro.launch import train as launch_train

    ckpt = tmp_path / "adamw.npz"
    launch_train.main([
        "--arch", "granite-3-8b", "--smoke", "--steps", "3",
        "--seq", "16", "--batch", "2", "--tau", "2",
        "--local-rule", "adamw", "--local-opt-lr", "1e-3",
        "--checkpoint", str(ckpt),
    ])
    out = capsys.readouterr().out
    assert "rules=adamw+momentum_delta" in out
    assert ckpt.exists()


# ---------------------------------------------------------------------------
# fused decode+apply commit path (DESIGN.md §16)
# ---------------------------------------------------------------------------

def test_fused_codec_rule_registry():
    """The combined decode+apply rules register under "<rule>@<codec>" in
    both backends: "momentum_delta@int8", "momentum_delta@bf16",
    "plain_average@int8", "plain_average@bf16"."""
    combined = {
        "momentum_delta@int8", "momentum_delta@bf16",
        "plain_average@int8", "plain_average@bf16",
    }
    assert combined <= set(commit_rule_names())
    cfg = CommitConfig(tau=1, worker_axes=())
    for name in combined:
        assert rule_backends("commit", name) == ("fused", "reference")
        rule = get_commit_rule(name, cfg, backend="reference")
        if name.endswith("@int8"):
            # int8 payloads are {"q","scale"} dicts the tree flattener
            # must treat as leaves
            assert rule.is_payload({"q": 1, "scale": 2})
            assert not rule.is_payload({"q": 1})
            assert not rule.is_payload(jnp.zeros(3))
        else:
            assert rule.is_payload is None


@pytest.mark.parametrize("granularity", ["data", "accum"])
@pytest.mark.parametrize("commit", ["momentum_delta", "plain_average"])
@pytest.mark.parametrize("codec", ["identity", "int8", "bf16", "top_k"])
def test_fused_commit_bit_identical_to_chain(problem, codec, commit,
                                             granularity):
    """fused_commit=True must be bit-for-bit the encode → decode → apply
    chain for every codec: fusable codecs take the single-pass rule,
    the rest silently fall back to the chain itself."""
    params, batch = problem
    cfg = CommitConfig(tau=2, local_lr=0.05, global_lr=1.0,
                       worker_axes=("data",) if granularity == "data" else ())
    mesh = _mesh1() if granularity == "data" else None
    mbs = _stack(batch, 2)
    tau = jnp.asarray([2], jnp.int32)
    rules = UpdateRules(commit=commit, backend="reference")
    outs = {}
    for fused in (False, True):
        step = make_train_step(quad_loss, cfg, rules, mesh=mesh,
                               granularity=granularity, codec=codec,
                               explicit_momentum=0.5, fused_commit=fused)
        assert step.fused_commit is (fused and codec in ("int8", "bf16"))
        with jax.set_mesh(mesh) if mesh is not None else _null_ctx():
            state = step.init(params)
            for _ in range(3):
                state, loss = jax.jit(step)(state, mbs, tau)
        outs[fused] = (state, float(loss))
    sa, sb = outs[False][0], outs[True][0]
    for a, b in zip(jax.tree.leaves(sa), jax.tree.leaves(sb)):
        assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
    assert outs[False][1] == outs[True][1]


def test_fused_commit_kernel_backend_matches_reference(problem):
    """backend="fused" routes the combined rule through the Pallas
    single-pass kernels (interpret on CPU) — same bits as the reference
    combined rule."""
    params, batch = problem
    cfg = CommitConfig(tau=2, local_lr=0.05, global_lr=1.0, worker_axes=())
    mbs = _stack(batch, 2)
    tau = jnp.asarray([2], jnp.int32)
    outs = {}
    for backend in ("reference", "fused"):
        step = make_train_step(
            quad_loss, cfg, UpdateRules(backend=backend), granularity="accum",
            codec="int8", explicit_momentum=0.5, fused_commit=True,
        )
        assert step.fused_commit
        state = step.init(params)
        for _ in range(3):
            state, loss = jax.jit(step)(state, mbs, tau)
        outs[backend] = (np.asarray(state.params["w"]), float(loss))
    assert_array_equal(outs["fused"][0], outs["reference"][0])
    assert outs["fused"][1] == outs["reference"][1]


def test_fused_commit_gate_falls_back():
    """Fusion preconditions: codec present + fusable, one worker, f32
    commit dtype — anything else silently uses the chain path."""
    cfg = CommitConfig(tau=1, worker_axes=())
    mk = lambda **kw: make_train_step(quad_loss, kw.pop("cfg", cfg),
                                      UpdateRules(backend="reference"),
                                      granularity="accum", **kw)
    assert mk(codec="int8", fused_commit=True).fused_commit
    assert not mk(codec="int8", fused_commit=False).fused_commit
    assert not mk(codec=None, fused_commit=True).fused_commit
    assert not mk(codec="top_k", fused_commit=True).fused_commit
    cfg16 = CommitConfig(tau=1, worker_axes=(), commit_dtype="bfloat16")
    assert not mk(cfg=cfg16, codec="int8", fused_commit=True).fused_commit


def test_train_step_exposes_donate_argnums():
    """The state argument is safe to donate: callers jit with
    step.donate_argnums and reuse buffers round over round."""
    cfg = CommitConfig(tau=1, worker_axes=())
    step = make_train_step(quad_loss, cfg, UpdateRules(backend="reference"),
                           granularity="accum")
    assert step.donate_argnums == (0,)
